"""The backend dispatch policy and the VMEM-aware autotuner: flag
resolution (the serve/kernels interpret-default divergence fix), plan
construction, decision-table caching and persistence, and end-to-end
agreement of the consumers (BatchedEvaluator, WhatIfService) that now
route through one policy."""

import json

import numpy as np
import pytest

import jax.numpy as jnp

from repro import obs
from repro.core.graph import linear_graph
from repro.kernels import autotune, dispatch
from repro.kernels.autotune import KernelConfig, ShapeKey
from repro.obs.registry import MetricsRegistry, set_registry
from repro.perf import roofline
from repro.sim.batched import BatchedEvaluator, pack_fleets, pack_placements
from repro.serve.service import WhatIfService


@pytest.fixture(autouse=True)
def _fresh_autotune_table():
    autotune.clear_table()
    yield
    autotune.clear_table()


@pytest.fixture
def metrics():
    reg = MetricsRegistry()
    reg.enabled = True
    old = obs.registry()
    set_registry(reg)
    yield reg
    set_registry(old)


V5E = "TPU v5 lite"


def _counter_total(reg, name):
    return sum(r["value"] for r in reg.snapshot()
               if r["name"] == name and r["type"] == "counter")


# -- resolve_flags policy -----------------------------------------------------

def test_auto_flags_resolve_per_backend():
    assert dispatch.resolve_flags(None, None, backend="cpu") == (False, True)
    assert dispatch.resolve_flags(None, None, backend="tpu") == (True, False)


def test_explicit_pallas_on_cpu_keeps_interpret():
    assert dispatch.resolve_flags(True, None, backend="cpu") == (True, True)


def test_compiled_on_cpu_is_coerced_to_interpret(metrics):
    """The divergence fix's teeth: an explicit interpret=False on CPU
    cannot survive resolution (compiled Pallas can't lower there) and the
    coercion is observable."""
    assert dispatch.resolve_flags(True, False, backend="cpu") == (True, True)
    assert _counter_total(metrics, "kernels.dispatch.coerced") == 1


def test_interpret_on_accelerator_is_honored_but_counted(metrics):
    assert dispatch.resolve_flags(True, True, backend="tpu") == (True, True)
    assert _counter_total(
        metrics, "kernels.dispatch.interpret_on_accelerator") == 1


# -- plans --------------------------------------------------------------------

def test_plan_auto_on_cpu_is_xla():
    plan = dispatch.plan_edge_kernel("dense", 4, 24, 256, backend="cpu")
    assert plan.impl == "xla" and plan.interpret and plan.config is None


def test_plan_pallas_config_fits_vmem_budget(monkeypatch):
    monkeypatch.setattr(autotune, "local_device_kind", lambda backend: V5E)
    plan = dispatch.plan_edge_kernel("dense", 4, 24, 8192, use_pallas=True,
                                     backend="tpu")
    assert plan.impl == "pallas" and not plan.interpret
    assert autotune.vmem_bytes("dense", 24, 8192, None, plan.config) \
        <= autotune.VMEM_BUDGET_BYTES


def test_plan_pinned_blocks_bypass_autotuner():
    plan = dispatch.plan_edge_kernel("dense", 4, 24, 1024, use_pallas=True,
                                     backend="cpu", block_edges=64,
                                     block_v=256)
    assert plan.config == KernelConfig(block_edges=64, block_v=256)
    assert autotune.table_rows() == []  # no decision was recorded


def test_dispatch_routes_agree_numerically():
    rng = np.random.default_rng(0)
    xi = jnp.asarray(rng.standard_normal((2, 5, 300)), jnp.float32)
    xj = jnp.asarray(rng.standard_normal((2, 5, 300)), jnp.float32)
    com = jnp.asarray(rng.standard_normal((1, 300, 300)), jnp.float32)
    xla = np.asarray(dispatch.edge_latency(xi, xj, com, use_pallas=False))
    pal = np.asarray(dispatch.edge_latency(xi, xj, com, use_pallas=True,
                                           interpret=True))
    np.testing.assert_allclose(pal, xla, rtol=1e-5, atol=1e-5)


# -- autotuner ----------------------------------------------------------------

def test_candidates_all_fit_budget_and_dedupe():
    cands = autotune.candidate_configs("dense", 24, 300, None)
    assert cands
    geoms = set()
    from repro.kernels.edge_latency import block_geometry
    for c in cands:
        assert autotune.vmem_bytes("dense", 24, 300, None, c) \
            <= autotune.VMEM_BUDGET_BYTES
        g = block_geometry("dense", 24, 300, None, c.block_edges, c.block_v)
        assert (g.be, g.bv) not in geoms  # clamped duplicates dropped
        geoms.add((g.be, g.bv))


def test_candidates_raise_when_nothing_fits_vmem():
    """No silent fallback to tiles that cannot lower: at an R whose (R, bv)
    tile alone overflows VMEM, not even the smallest block shape fits."""
    with pytest.raises(ValueError, match="VMEM"):
        autotune.candidate_configs("structured", 24, 4096, 10 ** 6)


def test_unknown_tpu_kind_has_no_peaks():
    """A TPU missing from the peak table is an error, for the table itself
    and for the tuner that ranks by it; the CPU branch needs no entry."""
    with pytest.raises(ValueError, match="no peak entry"):
        roofline.peaks_for("tpu", "TPU v0 imaginary")
    with pytest.raises(ValueError, match="no peak entry"):
        autotune.get_config("dense", 4, 24, 1024, backend="tpu",
                            device_kind="TPU v0 imaginary")
    assert roofline.peaks_for("tpu", V5E) == roofline.CHIP_PEAKS[V5E]
    assert roofline.peaks_for("cpu", None).flops == roofline.PEAK_FLOPS


def test_cpu_model_prefers_fewer_grid_steps():
    """On CPU (interpret mode) per-step overhead dominates, so the model
    must rank a one-tile config above many small tiles."""
    best = autotune.rank("dense", 4, 24, 1024, backend="cpu")[0]
    from repro.kernels.edge_latency import block_geometry
    g = block_geometry("dense", 24, 1024, None, best.block_edges,
                       best.block_v)
    assert g.n_u * g.n_v == 1


def test_decision_is_cached_per_shape_key(metrics):
    a = autotune.get_config("dense", 4, 24, 1024, backend="cpu")
    b = autotune.get_config("dense", 4, 24, 1024, backend="cpu")
    assert a == b
    rows = [r for r in metrics.snapshot()
            if r["name"] == "kernels.autotune.decisions"]
    by_source = {r["labels"]["source"]: r["value"] for r in rows}
    assert by_source == {"analytic": 1, "table": 1}
    # B buckets to powers of two: B=3 shares B=4's entry
    assert autotune.get_config("dense", 3, 24, 1024, backend="cpu") == a
    assert len(autotune.table_rows()) == 1


def test_empirical_timer_overrides_analytic_ranking():
    ranked = autotune.rank("dense", 4, 24, 1024, backend="cpu")
    want = ranked[1]  # force a non-analytic winner
    cfg = autotune.get_config(
        "dense", 4, 24, 1024, backend="cpu",
        timer=lambda c: 0.0 if c == want else 1.0)
    assert cfg == want
    assert autotune.table_rows()[0]["source"] == "empirical"


def test_table_round_trips_through_json(tmp_path):
    autotune.get_config("dense", 4, 24, 1024, backend="cpu")
    autotune.get_config("structured", 2, 12, 131072, 8, backend="tpu",
                        device_kind=V5E)
    path = tmp_path / "table.json"
    autotune.save_table(path)
    rows_before = autotune.table_rows()
    autotune.clear_table()
    assert autotune.table_rows() == []
    assert autotune.load_table(path) == 2
    assert autotune.table_rows() == rows_before
    doc = json.loads(path.read_text())
    assert doc["version"] == 1 and len(doc["entries"]) == 2


def test_load_rejects_unknown_version(tmp_path):
    path = tmp_path / "bad.json"
    path.write_text(json.dumps({"version": 99, "entries": []}))
    with pytest.raises(ValueError):
        autotune.load_table(path)


def test_shape_key_buckets_batch():
    assert ShapeKey.of("cpu", "dense", 3, 24, 64, None).b_bucket == 4
    assert ShapeKey.of("cpu", "dense", 4, 24, 64, None).b_bucket == 4
    assert ShapeKey.of("cpu", "dense", 5, 24, 64, None).b_bucket == 8
    assert ShapeKey.of("cpu", "dense", 1, 24, 64, None).b_bucket == 1


# -- consumers agree through the one policy -----------------------------------

def test_evaluator_and_service_resolve_to_same_flags():
    """The interpret-default divergence fix: a default-constructed service
    and a default-constructed shared evaluator land on the SAME concrete
    flags (and therefore the same executables / coalesce keys)."""
    g = linear_graph([1.0, 1.0, 1.0])
    svc = WhatIfService(g)
    ev = BatchedEvaluator.shared(g)
    assert isinstance(svc.use_pallas, bool)
    assert isinstance(svc.interpret, bool)
    assert (svc.use_pallas, svc.interpret) == (ev.use_pallas, ev.interpret)
    assert svc._ev is ev  # literally the same shared instance


def test_shared_memo_key_uses_resolved_flags():
    g = linear_graph([1.0, 1.0])
    auto = BatchedEvaluator.shared(g)
    concrete = BatchedEvaluator.shared(g, use_pallas=auto.use_pallas,
                                       interpret=auto.interpret)
    assert auto is concrete


def test_evaluator_pallas_path_matches_jnp_path():
    from repro.core import ExplicitFleet
    rng = np.random.default_rng(5)
    g = linear_graph([1.0, 0.5, 2.0, 1.5])
    com = rng.uniform(0.1, 2.0, (6, 6))
    com = (com + com.T) / 2
    np.fill_diagonal(com, 0.0)
    coms = pack_fleets([ExplicitFleet(com_cost=com)])
    xs = pack_placements([rng.uniform(0, 1, (4, 6)) for _ in range(3)])
    jnp_grid = np.asarray(BatchedEvaluator(g, use_pallas=False)
                          .score_grid(xs, coms))
    pal_grid = np.asarray(
        BatchedEvaluator(g, use_pallas=True, interpret=True)
        .score_grid(xs, coms))
    np.testing.assert_allclose(pal_grid, jnp_grid, rtol=1e-5, atol=1e-6)


def test_dense_score_grid_span_counts_kernel_rows(telemetry):
    """On the Pallas route the ``score_grid`` span records ``kernel_rows``,
    the V-sized rows a placement row costs the edge kernel: the dense
    kernel reads the two padded per-edge endpoint rows, 2·e_pad."""
    from repro.core import ExplicitFleet
    from repro.kernels.edge_latency import block_geometry

    rng = np.random.default_rng(6)
    g = linear_graph([1.0, 0.5, 2.0, 1.5])
    coms = pack_fleets([ExplicitFleet(com_cost=rng.uniform(0.1, 2.0,
                                                           (6, 6)))])
    xs = pack_placements([rng.uniform(0, 1, (4, 6)) for _ in range(3)])
    BatchedEvaluator(g, use_pallas=True, interpret=True).score_grid(xs, coms)
    BatchedEvaluator(g, use_pallas=False).score_grid(xs, coms)
    cfg = autotune.get_config("dense", 3, g.n_edges, 6)
    e_pad = block_geometry("dense", g.n_edges, 6, None, cfg.block_edges,
                           cfg.block_v).e_pad
    spans = [e["args"] for e in obs.trace_events()
             if e["name"] == "score_grid"]
    assert [a.get("kernel_rows") for a in spans] == [2 * e_pad, None]
