"""The V-blocked edge-latency kernels against the float64 oracle and the
single-tile kernels they replaced: padding/blocking edge cases (V, E, R not
multiples of lane/block sizes, E ∈ {0, 1}, shared vs per-scenario com),
≤1e-5 oracle parity in interpret mode, exact parity at small V, and
block-shape invariance."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels.edge_latency import (
    LANE,
    SUBLANE,
    block_geometry,
    edge_latency_pallas,
    edge_latency_pallas_single_tile,
    edge_latency_structured_pallas,
    edge_latency_structured_pallas_single_tile,
)

REL = 1e-5


def _dense_oracle(xi, xj, com):
    """float64 numpy reference: max_u xi · (com @ xj)_u, com (Bc, V, V)."""
    xi = np.asarray(xi, np.float64)
    xj = np.asarray(xj, np.float64)
    com = np.broadcast_to(np.asarray(com, np.float64),
                          (xi.shape[0],) + np.asarray(com).shape[1:])
    t = np.einsum("buv,bev->beu", com, xj)
    return np.max(xi * t, axis=-1)


def _structured_oracle(xi, mass, a, w):
    """float64 reference: max_u xi · (mass @ a + w)_u."""
    xi = np.asarray(xi, np.float64)
    B = xi.shape[0]
    a64 = np.broadcast_to(np.asarray(a, np.float64),
                          (B,) + np.asarray(a).shape[1:])
    t = np.einsum("ber,bru->beu", np.asarray(mass, np.float64), a64)
    return np.max(xi * (t + np.asarray(w, np.float64)), axis=-1)


def _rel_err(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() / max(np.abs(want).max(), 1e-12)


def _dense_inputs(rng, B, E, V, shared_com):
    xi = jnp.asarray(rng.standard_normal((B, E, V)), jnp.float32)
    xj = jnp.asarray(rng.standard_normal((B, E, V)), jnp.float32)
    bc = 1 if shared_com else B
    com = jnp.asarray(rng.standard_normal((bc, V, V)), jnp.float32)
    return xi, xj, com


def _structured_inputs(rng, B, E, V, R, shared):
    xi = jnp.asarray(rng.standard_normal((B, E, V)), jnp.float32)
    mass = jnp.asarray(rng.standard_normal((B, E, R)), jnp.float32)
    bc = 1 if shared else B
    a = jnp.asarray(rng.standard_normal((bc, R, V)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((B, E, V)), jnp.float32)
    return xi, mass, a, w


# -- geometry -----------------------------------------------------------------

def test_geometry_rounds_blocks_and_pads_axes():
    g = block_geometry("dense", E=33, V=300, R=None,
                       block_edges=16, block_v=200)
    assert g.bv % LANE == 0 and g.be % SUBLANE == 0
    assert g.v_pad % g.bv == 0 and g.v_pad >= 300
    assert g.e_pad % g.be == 0 and g.e_pad >= 33
    assert g.n_u == g.v_pad // g.bv and g.n_v == g.n_u


def test_geometry_clamps_oversized_blocks_to_padded_axis():
    g = block_geometry("dense", E=5, V=129, R=None,
                       block_edges=512, block_v=4096)
    assert g.bv == ((129 + LANE - 1) // LANE) * LANE  # one V tile
    assert g.be == SUBLANE  # E=5 rounds to one sublane tile
    assert g.n_e == g.n_u == g.n_v == 1


def test_geometry_structured_pads_r_to_lane():
    g = block_geometry("structured", E=12, V=300, R=3,
                       block_edges=128, block_v=512)
    assert g.r_pad == LANE and g.n_v == 1


def test_geometry_rejects_bad_inputs():
    with pytest.raises(ValueError):
        block_geometry("diag", 4, 64, None, 128, 512)
    with pytest.raises(ValueError):
        block_geometry("dense", 0, 64, None, 128, 512)
    with pytest.raises(ValueError):
        block_geometry("structured", 4, 64, None, 128, 512)


# -- dense oracle parity ------------------------------------------------------

@pytest.mark.parametrize("V", [7, 129, 300])
@pytest.mark.parametrize("shared_com", [True, False])
def test_dense_oracle_parity_odd_V(V, shared_com):
    """≤1e-5 float64-oracle parity at V not divisible by the lane width
    (and at V=300, not divisible by the block either)."""
    rng = np.random.default_rng(V)
    xi, xj, com = _dense_inputs(rng, B=2, E=5, V=V, shared_com=shared_com)
    got = edge_latency_pallas(xi, xj, com, block_edges=16, block_v=128,
                              interpret=True)
    assert _rel_err(got, _dense_oracle(xi, xj, com)) <= REL


@pytest.mark.parametrize("E", [1, 33, 130])
def test_dense_oracle_parity_odd_E(E):
    """E not a multiple of the sublane/block size still pads and reduces
    correctly (padded edge rows are sliced off, padded u masked to -inf)."""
    rng = np.random.default_rng(E)
    xi, xj, com = _dense_inputs(rng, B=2, E=E, V=129, shared_com=True)
    got = edge_latency_pallas(xi, xj, com, block_edges=16, block_v=128,
                              interpret=True)
    assert got.shape == (2, E)
    assert _rel_err(got, _dense_oracle(xi, xj, com)) <= REL


def test_dense_empty_edge_set_returns_empty():
    xi = jnp.zeros((3, 0, 64), jnp.float32)
    com = jnp.zeros((1, 64, 64), jnp.float32)
    out = edge_latency_pallas(xi, xi, com, interpret=True)
    assert out.shape == (3, 0)


def test_dense_negative_operands_padded_columns_masked():
    """All-negative operands: a padded u column contributing 0 would win
    the max if it weren't masked to -inf."""
    rng = np.random.default_rng(7)
    V = 130  # pads 126 fake u columns at bv=256
    xi = -jnp.asarray(rng.uniform(0.5, 1.0, (2, 4, V)), jnp.float32)
    xj = jnp.asarray(rng.uniform(0.5, 1.0, (2, 4, V)), jnp.float32)
    com = jnp.asarray(rng.uniform(0.5, 1.0, (1, V, V)), jnp.float32)
    got = edge_latency_pallas(xi, xj, com, interpret=True)
    want = _dense_oracle(xi, xj, com)
    assert float(np.asarray(got).max()) < 0
    assert _rel_err(got, want) <= REL


def test_dense_rejects_mismatched_com_batch():
    xi = jnp.zeros((3, 2, 64), jnp.float32)
    com = jnp.zeros((2, 64, 64), jnp.float32)
    with pytest.raises(ValueError):
        edge_latency_pallas(xi, xi, com, interpret=True)


# -- structured oracle parity -------------------------------------------------

@pytest.mark.parametrize("R", [3, 5, 130])
@pytest.mark.parametrize("shared", [True, False])
def test_structured_oracle_parity_odd_R(R, shared):
    """R not a multiple of the lane width (including R > LANE) pads with
    exact-zero rows; ≤1e-5 oracle parity at odd V too."""
    rng = np.random.default_rng(R)
    xi, mass, a, w = _structured_inputs(rng, B=2, E=5, V=300, R=R,
                                        shared=shared)
    got = edge_latency_structured_pallas(xi, mass, a, w, block_v=128,
                                         interpret=True)
    assert _rel_err(got, _structured_oracle(xi, mass, a, w)) <= REL


@pytest.mark.parametrize("E", [1, 33])
def test_structured_oracle_parity_odd_E(E):
    rng = np.random.default_rng(E + 100)
    xi, mass, a, w = _structured_inputs(rng, B=2, E=E, V=129, R=8,
                                        shared=True)
    got = edge_latency_structured_pallas(xi, mass, a, w, interpret=True)
    assert got.shape == (2, E)
    assert _rel_err(got, _structured_oracle(xi, mass, a, w)) <= REL


def test_structured_empty_edge_set_returns_empty():
    xi = jnp.zeros((2, 0, 64), jnp.float32)
    mass = jnp.zeros((2, 0, 4), jnp.float32)
    a = jnp.zeros((1, 4, 64), jnp.float32)
    out = edge_latency_structured_pallas(xi, mass, a, xi, interpret=True)
    assert out.shape == (2, 0)


@pytest.mark.parametrize("a_batch, w_shape", [(2, (3, 2, 64)),
                                             (1, (1, 2, 64))])
def test_structured_rejects_mismatched_scenario_batch(a_batch, w_shape):
    """A scenario batch that is neither 1 nor B, or a ``w`` that is not
    one row per (placement, edge, device), is refused."""
    xi = jnp.zeros((3, 2, 64), jnp.float32)
    mass = jnp.zeros((3, 2, 4), jnp.float32)
    a = jnp.zeros((a_batch, 4, 64), jnp.float32)
    w = jnp.zeros(w_shape, jnp.float32)
    with pytest.raises(ValueError):
        edge_latency_structured_pallas(xi, mass, a, w, interpret=True)


# -- per-operator rows with the edge list vs rows gathered to edges ----------

# (n_ops, src, dst): more edges than operators, fewer, and an operator
# (4) that no edge touches
_DAGS = {
    "more_edges": (4, (0, 0, 1, 2, 1, 0), (1, 2, 3, 3, 2, 3)),
    "fewer_edges": (6, (0, 2, 4), (1, 3, 5)),
    "idle_operator": (5, (0, 1, 2, 0), (1, 2, 3, 3)),
}


@pytest.mark.parametrize("route", ["pallas", "xla"])
@pytest.mark.parametrize("shared", [True, False])
@pytest.mark.parametrize("dag", sorted(_DAGS))
def test_structured_edge_list_matches_gathered_route(dag, shared, route):
    """Per-operator rows, masses and ``w`` with the static edge list give
    the bits the same route gives on rows gathered to edges
    (``x[src]·sel``, ``mass[dst]``, ``w[dst]``): selectivities off 1, V
    not a multiple of the lane width (padded u-columns masked), and row 0
    with an outage-sized device term that sets its max.  The ``xla`` route
    is the jnp reference."""
    from repro.kernels.edge_latency import edge_list
    from repro.kernels.ref import edge_latency_structured_ref

    n, src, dst = _DAGS[dag]
    B, V, R = 3, 300, 5
    rng = np.random.default_rng(len(src) * 10 + n)
    sel = rng.uniform(0.3, 2.0, len(src))
    x = rng.uniform(0.0, 1.0, (B, n, V)).astype(np.float32)
    w = rng.uniform(0.0, 1.0, (B, n, V)).astype(np.float32)
    w[0, :, 17] *= 1e4
    mass = jnp.asarray(rng.uniform(0.0, 1.0, (B, n, R)), jnp.float32)
    a = jnp.asarray(rng.uniform(0.0, 1.0, (1 if shared else B, R, V)),
                    jnp.float32)
    x, w = jnp.asarray(x), jnp.asarray(w)
    edges = edge_list(src, dst, sel)
    s, d = np.array(src), np.array(dst)
    x_i = x[:, s] * jnp.asarray(edges[2], jnp.float32)[None, :, None]
    if route == "pallas":
        got = edge_latency_structured_pallas(x, mass, a, w, edges,
                                             block_v=128, interpret=True)
        want = edge_latency_structured_pallas(x_i, mass[:, d], a, w[:, d],
                                              block_v=128, interpret=True)
    else:
        got = edge_latency_structured_ref(x, mass, a, w, edges)
        want = edge_latency_structured_ref(x_i, mass[:, d], a, w[:, d])
    assert got.shape == (B, len(src))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(np.argmax(np.asarray(x_i[0, 0]) * np.asarray(w[0, d[0]]))) \
        == 17
    assert _rel_err(got, _structured_oracle(x_i, mass[:, d], a,
                                            w[:, d])) <= REL


# -- exact parity vs the single-tile kernels ----------------------------------

@pytest.mark.parametrize("shared_com", [True, False])
def test_dense_blocked_exact_vs_single_tile_small_V(shared_com):
    """At V within one lane-aligned tile the blocked kernel performs the
    IDENTICAL dot (appended zero columns add exact +0.0 in f32) and max —
    bitwise parity with the original single-tile kernel."""
    rng = np.random.default_rng(0)
    xi, xj, com = _dense_inputs(rng, B=2, E=5, V=64, shared_com=shared_com)
    blocked = np.asarray(edge_latency_pallas(xi, xj, com, interpret=True))
    single = np.asarray(edge_latency_pallas_single_tile(xi, xj, com,
                                                        interpret=True))
    np.testing.assert_array_equal(blocked, single)


@pytest.mark.parametrize("shared", [True, False])
def test_structured_blocked_exact_vs_single_tile_small_V(shared):
    rng = np.random.default_rng(1)
    xi, mass, a, w = _structured_inputs(rng, B=2, E=5, V=64, R=4,
                                        shared=shared)
    blocked = np.asarray(edge_latency_structured_pallas(
        xi, mass, a, w, interpret=True))
    single = np.asarray(edge_latency_structured_pallas_single_tile(
        xi, mass, a, w, interpret=True))
    np.testing.assert_array_equal(blocked, single)


# -- block-shape invariance ---------------------------------------------------

def test_dense_result_invariant_to_block_shape():
    """Different (block_edges, block_v) choices change the accumulation
    ORDER but not the value beyond f32 roundoff — the autotuner is free to
    pick any feasible config."""
    rng = np.random.default_rng(3)
    xi, xj, com = _dense_inputs(rng, B=2, E=33, V=300, shared_com=True)
    outs = [np.asarray(edge_latency_pallas(xi, xj, com, block_edges=be,
                                           block_v=bv, interpret=True))
            for be, bv in [(8, 128), (16, 256), (64, 512), (128, 1024)]]
    for other in outs[1:]:
        np.testing.assert_allclose(other, outs[0], rtol=1e-5, atol=1e-5)


def test_structured_result_invariant_to_block_shape():
    rng = np.random.default_rng(4)
    xi, mass, a, w = _structured_inputs(rng, B=2, E=17, V=300, R=5,
                                        shared=True)
    outs = [np.asarray(edge_latency_structured_pallas(
        xi, mass, a, w, block_v=bv, interpret=True))
        for bv in [128, 256, 512]]
    for other in outs[1:]:
        np.testing.assert_allclose(other, outs[0], rtol=1e-5, atol=1e-5)


# -- region sums (the structured path's precompute) ---------------------------

@pytest.mark.parametrize("M, V, R", [(1, 7, 1), (37, 300, 5), (300, 2500, 32)])
def test_region_sum_matches_segment_sums(M, V, R):
    """Rows, devices and regions off every block and tile multiple: each
    row's region sums at float32 accuracy against float64."""
    from repro.kernels.region_sum import region_sum_pallas

    rng = np.random.default_rng(M + V)
    v = rng.uniform(0.0, 4.0, (M, V)).astype(np.float32)
    region = rng.integers(0, R, V)
    onehot = (np.arange(R)[:, None] == region[None, :]).astype(np.float32)
    got = np.asarray(region_sum_pallas(jnp.asarray(v), jnp.asarray(onehot),
                                       interpret=True))
    want = np.zeros((M, R))
    np.add.at(want.T, region, v.astype(np.float64).T)
    assert got.shape == (M, R)
    np.testing.assert_allclose(got, want, rtol=REL, atol=1e-6)


def test_region_sum_rows_independent_of_batch():
    """A row's region sums are the same bits alone, in a small batch and
    in a large one, at any offset: the served path's answers must equal
    direct scoring bit for bit."""
    from repro.kernels.region_sum import region_sum_pallas

    rng = np.random.default_rng(11)
    V, R = 2100, 6
    v = jnp.asarray(rng.uniform(0.0, 1e4, (300, V)), jnp.float32)
    region = rng.integers(0, R, V)
    onehot = jnp.asarray((np.arange(R)[:, None] == region[None, :])
                         .astype(np.float32))
    full = np.asarray(region_sum_pallas(v, onehot, interpret=True))
    for lo, hi in [(0, 1), (5, 21), (130, 300)]:
        part = np.asarray(region_sum_pallas(v[lo:hi], onehot,
                                            interpret=True))
        np.testing.assert_array_equal(part, full[lo:hi])
