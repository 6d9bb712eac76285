"""Property tests: the STRUCTURED batched path (RegionFleetFamily through
BatchedEvaluator) against the float64 numpy oracle, on both the vmap and
Pallas routes, including ``alpha > 0`` and the shared-family (S == 1)
broadcast case — plus the family pack/generator contracts."""

import contextlib

import numpy as np
import pytest

try:
    from hypothesis import example, given, settings, strategies as st
except ModuleNotFoundError:  # container lacks hypothesis — use the shim
    from repro.testing.propcheck import given, settings, strategies as st

    def example(**_):  # the shim replays no pinned examples
        return lambda f: f

from repro.core import (
    CostConfig,
    RegionFleet,
    RegionFleetFamily,
    edge_latencies,
    latency,
    objective_F,
    random_dag,
    random_placement,
)
from repro.sim import (
    BatchedEvaluator,
    ScenarioConfig,
    pack_placements,
    pack_region_fleets,
    region_fleet_family,
    region_scenario_batch,
)

SETTINGS = dict(max_examples=20, deadline=None)
REL = 1e-5


def _random_region_fleets(rng, n_dev, n_fleets):
    """RegionFleets sharing one region layout, with random inter matrices
    and degrade multipliers (some healthy, some straggling)."""
    n_regions = int(rng.integers(1, n_dev + 1))
    region = rng.integers(0, n_regions, n_dev)
    fleets = []
    for k in range(n_fleets):
        inter = rng.uniform(0.1, 2.0, (n_regions, n_regions))
        inter = (inter + inter.T) / 2
        degrade = None if k == 0 else rng.uniform(0.5, 4.0, n_dev)
        fleets.append(RegionFleet(region=region, inter=inter,
                                  degrade=degrade))
    return fleets


@st.composite
def instances(draw):
    return _instance(draw(st.integers(0, 2**31 - 1)),
                     draw(st.sampled_from([0.0, 0.25, 1.0])),
                     draw(st.sampled_from([False, True])))


def _instance(seed, alpha, pallas):
    rng = np.random.default_rng(seed)
    n_ops = int(rng.integers(2, 8))
    n_dev = int(rng.integers(2, 9))
    g = random_dag(n_ops, edge_prob=0.5, rng=rng)
    fleets = _random_region_fleets(rng, n_dev, int(rng.integers(1, 4)))
    xs = [random_placement(n_ops, np.ones((n_ops, n_dev), bool), rng,
                           sparsity=float(rng.uniform(0.0, 0.7)))
          for _ in range(int(rng.integers(1, 5)))]
    return g, fleets, xs, CostConfig(alpha=alpha), pallas


@given(instances())
@settings(**SETTINGS)
# both ends of an edge on one device of a two-device region: the oracle
# reads 0.0 where a self-pair added and subtracted again left 2.45e-06
@example(inst=_instance(31834, 0.0, False))
def test_structured_matches_oracle(pallas_route, inst):
    """score_grid / latency / edge_latencies over a RegionFleetFamily ==
    numpy oracle to ≤1e-5 relative, vmap AND Pallas routes, alpha 0/>0."""
    g, fleets, xs, cfg, pallas = inst
    fam = pack_region_fleets(fleets)
    P = pack_placements(xs)
    beta, dq = 0.7, 0.3
    b = len(fleets)
    xb = np.stack([xs[0]] * b)
    with pallas_route() if pallas else contextlib.nullcontext():
        ev = BatchedEvaluator(g, cfg)
        grid = np.asarray(ev.score_grid(P, fam, dq=dq, beta=beta))
        el = np.asarray(ev.edge_latencies(xb, fam))
        lat = np.asarray(ev.latency(xb, fam))
    assert grid.shape == (len(fleets), len(xs))
    for si, fleet in enumerate(fleets):
        for pi, x in enumerate(xs):
            want = objective_F(latency(g, fleet, x, cfg), dq, beta)
            assert grid[si, pi] == pytest.approx(want, rel=REL, abs=1e-6)
    # per-edge + latency agreement on the first placement across every fleet
    for si, fleet in enumerate(fleets):
        np.testing.assert_allclose(
            el[si], edge_latencies(g, fleet, xs[0], cfg), rtol=REL, atol=1e-6)
        assert lat[si] == pytest.approx(latency(g, fleet, xs[0], cfg),
                                        rel=REL, abs=1e-6)


@given(instances())
@settings(**SETTINGS)
def test_structured_shared_family_broadcast(pallas_route, inst):
    """An S == 1 family broadcasts against a placement batch exactly like a
    (1, V, V) dense com — on both routes."""
    g, fleets, xs, cfg, pallas = inst
    fam1 = pack_region_fleets(fleets[:1])
    with pallas_route() if pallas else contextlib.nullcontext():
        lat = np.asarray(BatchedEvaluator(g, cfg).latency(
            pack_placements(xs), fam1))
    assert lat.shape == (len(xs),)
    for pi, x in enumerate(xs):
        assert lat[pi] == pytest.approx(latency(g, fleets[0], x, cfg),
                                        rel=REL, abs=1e-6)


def test_structured_and_dense_paths_agree():
    """The SAME family scored structurally and via its materialized dense
    pack produces the same grid (the dispatch is an implementation detail)."""
    from repro.sim import pack_fleets

    rng = np.random.default_rng(3)
    g = random_dag(6, 0.5, rng)
    fleets = _random_region_fleets(rng, 7, 3)
    xs = [random_placement(6, np.ones((6, 7), bool), rng, 0.4)
          for _ in range(4)]
    ev = BatchedEvaluator(g, CostConfig(alpha=0.3))
    P = pack_placements(xs)
    a = np.asarray(ev.score_grid(P, pack_region_fleets(fleets), dq=0.2,
                                 beta=0.9))
    b = np.asarray(ev.score_grid(P, pack_fleets(fleets), dq=0.2, beta=0.9))
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_structured_kernel_against_ref():
    """The raw structured Pallas kernel against a jnp reference."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.dispatch import edge_latency_structured

    rng = np.random.default_rng(0)
    for B, E, V, R, Bc in [(1, 1, 2, 1, 1), (3, 7, 5, 2, 3),
                           (2, 130, 16, 4, 2), (4, 33, 12, 3, 1)]:
        xi = jnp.asarray(rng.random((B, E, V)), jnp.float32)
        mass = jnp.asarray(rng.random((B, E, R)), jnp.float32)
        a = jnp.asarray(rng.random((Bc, R, V)), jnp.float32)
        w = jnp.asarray(rng.random((B, E, V)), jnp.float32)
        out = edge_latency_structured(xi, mass, a, w)
        # one batched device→host transfer per shape, not one per operand
        out_h, xi_h, mass_h, a_h, w_h = jax.device_get(
            (out, xi, mass, a, w))
        t = np.einsum("ber,brv->bev", mass_h,
                      np.broadcast_to(a_h, (B, R, V)))
        want = (xi_h * (t + w_h)).max(axis=2)
        np.testing.assert_allclose(out_h, want, atol=1e-5, rtol=1e-5)


def test_pack_region_fleets_rejects_mismatched_layouts():
    rng = np.random.default_rng(1)
    a = _random_region_fleets(rng, 6, 1)[0]
    b = RegionFleet(region=(a.region + 1) % a.n_regions if a.n_regions > 1
                    else a.region, inter=a.inter * 2.0)
    if a.n_regions > 1:
        with pytest.raises(ValueError):
            pack_region_fleets([a, b])
    from repro.core import ExplicitFleet
    with pytest.raises(ValueError):
        pack_region_fleets([a, ExplicitFleet(com_cost=a.com_matrix())])
    # ValueError (not AttributeError) even when the FIRST element is dense
    with pytest.raises(ValueError):
        RegionFleetFamily.from_fleets([ExplicitFleet(com_cost=a.com_matrix()),
                                       a])


def test_region_fleet_family_generator_contract():
    """Generated families: shared layout, healthy region under outages,
    perturbations actually move link costs, and the pack round-trips."""
    rng = np.random.default_rng(7)
    cfg = ScenarioConfig(n_regions=(4, 4), devices_per_region=(3, 3),
                         outage_prob=0.5, straggler_prob=0.3,
                         outage_factor=100.0)
    fam = region_fleet_family(rng, 8, cfg)
    assert fam.inter.shape == (8, 4, 4)
    assert fam.degrade.shape == (8, fam.n_devices)
    assert (fam.degrade >= 1.0).all()
    for s in range(8):
        # at least one region fully healthy (no outage multiplier)
        healthy = [r for r in range(4)
                   if (fam.degrade[s][fam.region == r] < cfg.outage_factor).all()]
        assert healthy
    # scenarios differ
    assert not np.allclose(fam.inter[0], fam.inter[1])
    # round-trip: unpacking to fleets and re-packing preserves the family
    fam2 = pack_region_fleets(fam.fleets())
    np.testing.assert_allclose(fam2.inter, fam.inter)
    np.testing.assert_allclose(fam2.degrade, fam.degrade)


def test_region_scenario_batch_scores_structurally():
    """region_scenario_batch fleets share one layout, so robust_placement
    runs the structured path and still matches the scalar oracle."""
    from repro.sim import robust_placement

    rng = np.random.default_rng(9)
    cfg = ScenarioConfig(trace_len=4, n_regions=(3, 3),
                         devices_per_region=(2, 3))
    scens = region_scenario_batch(rng, 4, cfg)
    g = scens[0].graph
    assert all(isinstance(s.fleet, RegionFleet) for s in scens)
    assert all(np.array_equal(s.fleet.region, scens[0].fleet.region)
               for s in scens)
    x, worst, grid = robust_placement(g, scens, rng, n_candidates=32)
    assert grid.shape == (4, 32)
    k = int(grid.max(axis=0).argmin())
    for si, s in enumerate(scens):
        assert grid[si, k] == pytest.approx(
            latency(g, s.fleet, x), rel=2e-5, abs=1e-6)


def test_family_fleet_oracle_equivalence():
    """family.fleet(s) prices identically through the RegionFleet segment-sum
    oracle and the materialized ExplicitFleet — the degrade algebra check."""
    from repro.core import ExplicitFleet

    rng = np.random.default_rng(11)
    g = random_dag(5, 0.5, rng)
    fleets = _random_region_fleets(rng, 8, 3)
    fam = RegionFleetFamily.from_fleets(fleets)
    x = random_placement(5, np.ones((5, 8), bool), rng, 0.3)
    for s in range(fam.n_scenarios):
        rf = fam.fleet(s)
        ef = ExplicitFleet(com_cost=rf.com_matrix())
        assert latency(g, rf, x) == pytest.approx(latency(g, ef, x),
                                                  rel=1e-12)


def test_from_fleets_preserves_per_scenario_speed():
    """degrade_device keeps nominal speed and encodes the slowdown in
    ``degrade`` alone; packing and unpacking a family must round-trip each
    scenario's EFFECTIVE speed — the compute/occupancy objectives price the
    degraded fleet correctly without double-counting the multiplier."""
    rng = np.random.default_rng(13)
    base = _random_region_fleets(rng, 6, 1)[0]
    slow = base.degrade_device(2, 4.0)
    assert slow.speed[2] == pytest.approx(base.speed[2])  # nominal untouched
    assert slow.effective_speed()[2] == pytest.approx(base.speed[2] / 4.0)
    fam = RegionFleetFamily.from_fleets([base, slow])
    np.testing.assert_allclose(fam.fleet(0).effective_speed(),
                               base.effective_speed())
    np.testing.assert_allclose(fam.fleet(1).effective_speed(),
                               slow.effective_speed())
    np.testing.assert_allclose(fam.effective_speeds()[1],
                               slow.effective_speed())
    # shared nominal speeds stay a single (V,) vector
    assert fam.speed.ndim == 1
