"""Property tests: the batched what-if evaluator (repro.sim.batched) against
the float64 numpy oracle (repro.core.costmodel), plus the Pallas edge-latency
kernel and the one-dispatch grid contract."""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # container lacks hypothesis — use the shim
    from repro.testing.propcheck import given, settings, strategies as st

from repro.core import (
    CostConfig,
    ExplicitFleet,
    RegionFleet,
    edge_latencies,
    latency,
    objective_F,
    random_dag,
    random_placement,
)
from repro.sim import BatchedEvaluator, pack_fleets, pack_placements

SETTINGS = dict(max_examples=25, deadline=None)
REL = 1e-5


def _random_fleets(rng, n_dev, n_fleets):
    fleets = []
    for k in range(n_fleets):
        if k % 2 == 0:
            com = rng.uniform(0.1, 3.0, (n_dev, n_dev))
            com = (com + com.T) / 2
            np.fill_diagonal(com, 0.0)
            fleets.append(ExplicitFleet(com_cost=com))
        else:
            n_regions = int(rng.integers(1, n_dev + 1))
            inter = rng.uniform(0.1, 2.0, (n_regions, n_regions))
            inter = (inter + inter.T) / 2
            fleets.append(RegionFleet(
                region=rng.integers(0, n_regions, n_dev), inter=inter))
    return fleets


@st.composite
def instances(draw):
    seed = draw(st.integers(0, 2**31 - 1))
    alpha = draw(st.sampled_from([0.0, 0.25, 1.0]))
    rng = np.random.default_rng(seed)
    n_ops = int(rng.integers(2, 8))
    n_dev = int(rng.integers(2, 7))
    g = random_dag(n_ops, edge_prob=0.5, rng=rng)
    fleets = _random_fleets(rng, n_dev, int(rng.integers(1, 4)))
    xs = [random_placement(n_ops, np.ones((n_ops, n_dev), bool), rng,
                           sparsity=float(rng.uniform(0.0, 0.7)))
          for _ in range(int(rng.integers(1, 5)))]
    return g, fleets, xs, CostConfig(alpha=alpha), rng


@given(instances())
@settings(**SETTINGS)
def test_batched_matches_oracle(inst):
    """edge_latencies / latency / objective_F: batched == numpy oracle to
    ≤1e-5 relative, over ExplicitFleet AND RegionFleet, alpha 0 and >0."""
    g, fleets, xs, cfg, _ = inst
    ev = BatchedEvaluator(g, cfg)
    coms = pack_fleets(fleets)
    P = pack_placements(xs)
    beta, dq = 0.7, 0.3
    grid = np.asarray(ev.score_grid(P, coms, dq=dq, beta=beta))
    assert grid.shape == (len(fleets), len(xs))
    for si, fleet in enumerate(fleets):
        for pi, x in enumerate(xs):
            want = objective_F(latency(g, fleet, x, cfg), dq, beta)
            assert grid[si, pi] == pytest.approx(want, rel=REL, abs=1e-6)
    # per-edge agreement on the first placement across every fleet
    b = len(fleets)
    xb = np.stack([xs[0]] * b)
    el = np.asarray(ev.edge_latencies(xb, coms))
    lat = np.asarray(ev.latency(xb, coms))
    for si, fleet in enumerate(fleets):
        np.testing.assert_allclose(
            el[si], edge_latencies(g, fleet, xs[0], cfg), rtol=REL, atol=1e-6)
        assert lat[si] == pytest.approx(latency(g, fleet, xs[0], cfg),
                                        rel=REL, abs=1e-6)


@given(instances())
@settings(max_examples=10, deadline=None)
def test_pallas_path_matches_jnp_path(pallas_route, inst):
    """The Pallas route (interpreted) produces the same grid as the jnp
    path."""
    g, fleets, xs, cfg, _ = inst
    coms = pack_fleets(fleets)
    P = pack_placements(xs)
    a = np.asarray(BatchedEvaluator(g, cfg).score_grid(P, coms, beta=0.5,
                                                       dq=0.5))
    with pallas_route():
        b = np.asarray(BatchedEvaluator(g, cfg)
                       .score_grid(P, coms, beta=0.5, dq=0.5))
    np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_pallas_kernel_against_ref():
    """The raw kernel against its jnp oracle over odd shapes."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import dispatch, ref

    rng = np.random.default_rng(0)
    for B, E, V in [(1, 1, 2), (3, 7, 5), (2, 128, 16), (4, 33, 12)]:
        xi = jnp.asarray(rng.random((B, E, V)), jnp.float32)
        xj = jnp.asarray(rng.random((B, E, V)), jnp.float32)
        com = jnp.asarray(rng.random((B, V, V)), jnp.float32)
        out = dispatch.edge_latency(xi, xj, com)
        # one batched device→host transfer per shape, not one per operand
        got, want = jax.device_get((out, ref.edge_latency_ref(xi, xj, com)))
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_thousand_candidates_single_dispatch():
    """Acceptance: ≥1000 (scenario × placement) scores from ONE jitted call,
    spot-checked against the oracle."""
    rng = np.random.default_rng(7)
    n_ops, n_dev = 10, 16
    g = random_dag(n_ops, 0.4, rng)
    fleets = _random_fleets(rng, n_dev, 8)
    xs = [random_placement(n_ops, np.ones((n_ops, n_dev), bool), rng, 0.5)
          for _ in range(128)]
    ev = BatchedEvaluator(g)
    grid = np.asarray(ev.score_grid(pack_placements(xs), pack_fleets(fleets)))
    assert grid.size == 8 * 128 >= 1000
    assert np.isfinite(grid).all() and (grid >= 0).all()
    for si, pi in [(0, 0), (3, 77), (7, 127)]:
        want = latency(g, fleets[si], xs[pi])
        assert grid[si, pi] == pytest.approx(want, rel=REL, abs=1e-6)


def test_compute_extension_rejected():
    rng = np.random.default_rng(0)
    g = random_dag(3, 0.5, rng)
    with pytest.raises(NotImplementedError):
        BatchedEvaluator(g, CostConfig(include_compute=True))


def test_mismatched_fleet_sizes_rejected():
    rng = np.random.default_rng(0)
    fleets = _random_fleets(rng, 4, 1) + _random_fleets(rng, 5, 1)
    with pytest.raises(ValueError):
        pack_fleets(fleets)


def test_latency_com_fn_scalar_twin():
    """The unbatched com-traced twin (what BatchedEvaluator vmaps) matches
    the oracle on a single (placement, fleet) pair, alpha on and off."""
    import jax
    import jax.numpy as jnp

    from repro.core import SmoothConfig
    from repro.core.jaxmodel import make_latency_com_fn

    rng = np.random.default_rng(11)
    g = random_dag(6, 0.5, rng)
    fleet = _random_fleets(rng, 5, 1)[0]
    x = random_placement(6, np.ones((6, 5), bool), rng, 0.3)
    # hoist the host→device conversions out of the alpha loop; pull each
    # scalar back with one explicit device_get per dispatch
    x32 = jnp.asarray(x, jnp.float32)
    com32 = jnp.asarray(fleet.com_matrix(), jnp.float32)
    for alpha in (0.0, 0.4):
        lat_fn = make_latency_com_fn(g, SmoothConfig(alpha=alpha))
        got = jax.device_get(lat_fn(x32, com32))
        want = latency(g, fleet, x, CostConfig(alpha=alpha))
        assert float(got) == pytest.approx(want, rel=REL, abs=1e-6)
