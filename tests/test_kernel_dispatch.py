"""The backend dispatch route and the VMEM-aware autotuner: the route per
backend, plan construction, decision-table caching and persistence, and
the consumers (BatchedEvaluator, WhatIfService) that share one route."""

import json

import numpy as np
import pytest

import jax.numpy as jnp

from repro import obs
from repro.core import RegionFleet
from repro.core.graph import linear_graph
from repro.kernels import autotune, dispatch, ref
from repro.kernels.autotune import ShapeKey
from repro.kernels.edge_latency import edge_list
from repro.obs.registry import MetricsRegistry, set_registry
from repro.perf import roofline
from repro.sim.batched import (BatchedEvaluator, pack_fleets,
                               pack_placements, pack_region_fleets)
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


# -- the route ----------------------------------------------------------------

@pytest.mark.parametrize("backend", ["cpu", "tpu", "gpu"])
def test_auto_flags_resolve_per_backend(monkeypatch, backend):
    """The route is the backend's alone: the jaxmodel twin with any Pallas
    call interpreted on the CPU, compiled Pallas on an accelerator."""
    monkeypatch.setattr(dispatch, "backend_name", lambda: backend)
    on_cpu = backend == "cpu"
    assert dispatch.route() == dispatch.Route(pallas=not on_cpu,
                                              interpret=on_cpu)


# -- plans --------------------------------------------------------------------

def test_plan_pallas_config_fits_vmem_budget(monkeypatch):
    monkeypatch.setattr(dispatch, "backend_name", lambda: "tpu")
    monkeypatch.setattr(autotune, "local_device_kind", lambda backend: V5E)
    plan = dispatch.plan_edge_kernel("dense", 4, 24, 8192)
    assert not plan.interpret
    assert autotune.vmem_bytes("dense", 24, 8192, None, plan.config) \
        <= autotune.VMEM_BUDGET_BYTES


@pytest.mark.parametrize("kind,R", [("dense", None), ("structured", 4)])
def test_plans_take_the_table_and_are_counted(metrics, kind, R):
    """A plan's blocks are the decision table's, and each plan is counted
    by kind and by whether it runs interpreted (the CPU's route)."""
    plan = dispatch.plan_edge_kernel(kind, 4, 24, 1024, R, n_ops=6)
    assert plan.interpret
    assert plan.config == autotune.get_config(kind, 4, 24, 1024, R,
                                              n_ops=6)
    rows = [r for r in metrics.snapshot()
            if r["name"] == "kernels.dispatch.plans"]
    assert [(r["labels"], r["value"]) for r in rows] == [
        ({"kind": kind, "interpret": "True"}, 1)]


@pytest.mark.parametrize("kind", ["dense", "structured"])
def test_dispatch_routes_agree_numerically(kind):
    """The dispatched kernel (interpreted here) against its jnp reference,
    with a shared scenario and V off the lane width."""
    rng = np.random.default_rng(0)

    def arr(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    if kind == "dense":
        xi, xj, com = arr(2, 5, 300), arr(2, 5, 300), arr(1, 300, 300)
        got = dispatch.edge_latency(xi, xj, com)
        want = ref.edge_latency_ref(xi, xj, com)
    else:
        x, mass, a, w = arr(2, 4, 300), arr(2, 4, 5), arr(1, 5, 300), \
            arr(2, 4, 300)
        edges = edge_list([0, 0, 1, 2], [1, 2, 3, 3], [0.5, 1.0, 2.0, 1.5])
        got = dispatch.edge_latency_structured(x, mass, a, w, edges)
        want = ref.edge_latency_structured_ref(x, mass, a, w, edges)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


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


# -- consumers share the one route --------------------------------------------

def test_evaluator_and_service_resolve_to_same_flags():
    """The service scores through the process-shared evaluator, so the
    serving layer and the sim layer run the same executables."""
    g = linear_graph([1.0, 1.0, 1.0])
    svc = WhatIfService(g)
    ev = BatchedEvaluator.shared(g)
    assert svc._ev is ev  # literally the same shared instance


def test_substituted_route_gets_its_own_evaluator(pallas_route):
    """An evaluator built under a substituted route is its own shared
    instance with its own executables, and never the default's."""
    g = linear_graph([1.0, 1.0])
    fam = pack_region_fleets([RegionFleet(np.array([0, 0, 1]),
                                          np.array([[0.0, 1.0],
                                                    [1.0, 0.0]]))])
    default = BatchedEvaluator.shared(g)
    with pallas_route() as substituted_route:
        substituted = BatchedEvaluator.shared(g)
        assert BatchedEvaluator.shared(g) is substituted
        assert substituted._route == substituted_route
        substituted_structured = substituted._structured(fam)
    assert substituted is not default
    assert default._route == dispatch.route()
    assert substituted._jit_grid is not default._jit_grid
    assert substituted_structured is not default._structured(fam)
    assert BatchedEvaluator.shared(g) is default


def test_evaluator_pallas_path_matches_jnp_path(pallas_route):
    from repro.core import ExplicitFleet
    rng = np.random.default_rng(5)
    g = linear_graph([1.0, 0.5, 2.0, 1.5])
    com = rng.uniform(0.1, 2.0, (6, 6))
    com = (com + com.T) / 2
    np.fill_diagonal(com, 0.0)
    coms = pack_fleets([ExplicitFleet(com_cost=com)])
    xs = pack_placements([rng.uniform(0, 1, (4, 6)) for _ in range(3)])
    jnp_grid = np.asarray(BatchedEvaluator(g).score_grid(xs, coms))
    with pallas_route():
        pal_grid = np.asarray(BatchedEvaluator(g).score_grid(xs, coms))
    np.testing.assert_allclose(pal_grid, jnp_grid, rtol=1e-5, atol=1e-6)


def test_dense_score_grid_span_counts_kernel_rows(telemetry, pallas_route):
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
    with pallas_route():
        BatchedEvaluator(g).score_grid(xs, coms)
    BatchedEvaluator(g).score_grid(xs, coms)
    cfg = autotune.get_config("dense", 3, g.n_edges, 6)
    e_pad = block_geometry("dense", g.n_edges, 6, None, cfg.block_edges,
                           cfg.block_v).e_pad
    spans = [e["args"] for e in obs.trace_events()
             if e["name"] == "score_grid"]
    assert [a.get("kernel_rows") for a in spans] == [2 * e_pad, None]
