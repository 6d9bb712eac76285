"""HLO analyzer: trip-count weighting, dot flops, collective accounting."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.perf.hlo import analyze_module, parse_collectives
from repro.perf.roofline import compute_terms


def test_scan_flops_equal_unrolled():
    def scanned(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        y, _ = jax.lax.scan(body, x, w)
        return y.sum()

    def unrolled(x, w):
        for i in range(8):
            x = jnp.tanh(x @ w[i])
        return x.sum()

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((8, 128, 128), jnp.float32)
    fs = analyze_module(jax.jit(scanned).lower(x, w).compile().as_text())
    fu = analyze_module(jax.jit(unrolled).lower(x, w).compile().as_text())
    assert fs.flops == pytest.approx(fu.flops, rel=1e-6)
    assert fs.flops == pytest.approx(8 * 2 * 128 ** 3, rel=0.01)


def test_dot_flops_with_batch_dims():
    def f(a, b):
        return jnp.einsum("bij,bjk->bik", a, b).sum()

    a = jax.ShapeDtypeStruct((4, 32, 64), jnp.float32)
    b = jax.ShapeDtypeStruct((4, 64, 16), jnp.float32)
    s = analyze_module(jax.jit(f).lower(a, b).compile().as_text())
    assert s.flops == pytest.approx(2 * 4 * 32 * 64 * 16, rel=0.02)


def test_nested_scan_multiplies():
    def f(x, w):
        def outer(c, _):
            def inner(ci, wi):
                return ci @ wi, None
            y, _ = jax.lax.scan(inner, c, w)
            return y, None
        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y.sum()

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((3, 64, 64), jnp.float32)
    s = analyze_module(jax.jit(f).lower(x, w).compile().as_text())
    assert s.flops == pytest.approx(5 * 3 * 2 * 64 ** 3, rel=0.02)


def test_collective_wire_model():
    from repro.perf.hlo import CollectiveStats
    hlo = """
HloModule test, is_scheduled=true

ENTRY %main (x: f32[1024]) -> f32[1024] {
  %x = f32[1024]{0} parameter(0)
  ROOT %ar = f32[1024]{0} all-reduce(%x), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%add
}
"""
    stats = parse_collectives(hlo)
    assert stats.counts["all-reduce"] == 1
    # ring: 2·B·(n−1)/n = 2·4096·0.75
    assert stats.wire_bytes["all-reduce"] == pytest.approx(2 * 4096 * 0.75)


def test_roofline_terms_and_dominance():
    t = compute_terms(hlo_flops=197e12, hlo_bytes=819e9, wire_bytes=0.0,
                      chips=4, model_flops=4 * 197e12 * 0.5, per_device=True)
    assert t.compute_s == pytest.approx(1.0)
    assert t.memory_s == pytest.approx(1.0)
    assert t.dominant in ("compute", "memory")
    assert t.useful_flops_fraction == pytest.approx(0.5)
    t2 = compute_terms(1e12, 1e9, 500e9, chips=4, model_flops=1e12)
    assert t2.dominant == "collective"
    assert t2.collective_s == pytest.approx(10.0)


# -- pinned against actually-compiled edge-latency kernels --------------------
# Costs of the paper's edge-latency contraction (B=2, E=6, V=8, R=4) as the
# V-BLOCKED kernels actually compile it: the wrappers pad V (and R) to the
# lane width and, on the dense kernel, E to the sublane width
# (block_geometry is the single source of truth), so the dominant dot
# costs 2·B·e_pad·v_pad² (dense) / 2·B·n_ops·r_pad·v_pad (structured: per
# operator, not per edge).  FLOPs are pinned to a tight band around that
# dot — exact equality would re-pin XLA's deterministic but
# version-dependent accounting of the elementwise mask/mul/max tail, which
# is O(1/v_pad) of the dot.  HBM bytes only as >= the PADDED I/O lower
# bound, since interpret-mode Pallas lowering adds interpreter traffic;
# the output term is the kernels' lane-dense output block.

_B, _E, _V, _R = 2, 6, 8, 4


def _kernel_hlo(fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _flops_band(dot: int, elementwise_outputs: int):
    """[dot, dot + slack]: the non-dot tail is a few ops per padded output
    element (mask compare, mul, max fold), bounded well below 8."""
    return dot, dot + 8 * elementwise_outputs


def test_dense_edge_latency_kernel_flops_pinned():
    from repro.kernels.edge_latency import (block_geometry,
                                            edge_latency_pallas)

    text = _kernel_hlo(
        lambda xi, xj, com: edge_latency_pallas(xi, xj, com, interpret=True),
        (_B, _E, _V), (_B, _E, _V), (1, _V, _V))
    s = analyze_module(text)
    g = block_geometry("dense", _E, _V, None, 128, 512)
    lo, hi = _flops_band(2 * _B * g.e_pad * g.v_pad * g.v_pad,
                         _B * g.e_pad * g.v_pad)
    assert lo <= s.flops <= hi
    # I/O floor: padded x_i + x_j + com + out, f32
    io_floor = 4 * (2 * _B * g.e_pad * g.v_pad + g.v_pad * g.v_pad
                    + _B * g.e_pad * g.out_lanes)
    assert s.hbm_bytes >= io_floor


def test_structured_edge_latency_kernel_flops_pinned():
    """The structured kernel contracts ``mass @ a`` once per OPERATOR row
    (n_ops = 4 here), not once per edge (E = 6), and reads the per-operator
    rows x and w once: 2·B·n_ops·r_pad·v_pad dot flops, and an I/O floor
    of those rows, the masses, ``a`` and the lane-dense (E, B, 128)
    output."""
    from repro.kernels.edge_latency import (block_geometry,
                                            edge_latency_structured_pallas,
                                            edge_list)

    n = 4
    edges = edge_list((0, 0, 1, 2, 1, 0), (1, 2, 3, 3, 2, 3), np.ones(_E))
    text = _kernel_hlo(
        lambda x, m, a, w: edge_latency_structured_pallas(
            x, m, a, w, edges, interpret=True),
        (_B, n, _V), (_B, n, _R), (1, _R, _V), (_B, n, _V))
    s = analyze_module(text)
    g = block_geometry("structured", _E, _V, _R, _E, 2048)
    lo, hi = _flops_band(2 * _B * n * g.r_pad * g.v_pad,
                         _B * (_E + n) * g.v_pad)
    assert lo <= s.flops <= hi
    io_floor = 4 * (2 * _B * n * g.v_pad + _B * n * g.r_pad
                    + g.r_pad * g.v_pad + _E * _B * g.out_lanes)
    assert s.hbm_bytes >= io_floor


def test_kernel_roofline_terms_finite():
    """The perf bridge's roofline on a real compiled module yields finite,
    positive step-time terms (the BENCH_* fields are well-defined)."""
    from repro.kernels.edge_latency import edge_latency_pallas

    text = _kernel_hlo(
        lambda xi, xj, com: edge_latency_pallas(xi, xj, com, interpret=True),
        (_B, _E, _V), (_B, _E, _V), (1, _V, _V))
    s = analyze_module(text)
    t = compute_terms(hlo_flops=s.flops, hlo_bytes=s.hbm_bytes,
                      wire_bytes=0.0, chips=1, model_flops=s.flops)
    assert t.step_time_s > 0 and np.isfinite(t.step_time_s)
    assert t.dominant in ("compute", "memory")


def test_structured_score_grid_writes_no_per_edge_rows(pallas_route):
    """A compiled structured score_grid (the Pallas route, interpreted)
    holds no float32 buffer of per-edge rows, (·, E, V) or (·, e_pad, V):
    the edge kernel reads per-operator rows and gathers to edges itself
    (its (e_pad, bv) tile is the kernel body's own, two-dimensional)."""
    import re

    from repro.kernels.edge_latency import SUBLANE
    from repro.sim import (BatchedEvaluator, ScenarioConfig, fresh_cache,
                           random_graph, region_fleet_family)

    rng = np.random.default_rng(0)
    graph = random_graph(rng, ScenarioConfig(n_ops=(7, 7),
                                             graph_families=("layered",)))
    V, R, P, S = 300, 4, 5, 2
    fam = region_fleet_family(rng, S, ScenarioConfig(n_regions=(R, R)),
                              n_devices=V)
    E, n = graph.n_edges, graph.n_ops
    e_pad = -(-E // SUBLANE) * SUBLANE
    assert n not in (E, e_pad)  # operator rows are told from edge rows
    with fresh_cache(), pallas_route():
        ev = BatchedEvaluator(graph)
        args = [jax.ShapeDtypeStruct(s, jnp.float32)
                for s in ((P, n, V), (S, R, R), (S, V), (), ())]
        text = jax.jit(ev._structured(fam).grid).lower(*args).compile() \
            .as_text()
    shapes = {tuple(int(d) for d in m.split(","))
              for m in re.findall(r"f32\[(\d+(?:,\d+)+)\]", text)}
    assert (P, n, V) in shapes or (P, n, 384) in shapes  # rows are there
    per_edge = [s for s in shapes if len(s) >= 3 and s[-2] in (E, e_pad)
                and s[-1] >= V]
    assert per_edge == [], per_edge
