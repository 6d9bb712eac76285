"""Co-located edges on the structured path: both ends of an edge mostly on
one device, which holds over 90% of its region's degrade-weighted mass.

The structured twins price a device's transfers within its own region
from the OTHER devices of the region (``jaxmodel.region_terms``).  The
formulation they replaced priced every same-region pair through the
region mass, the device's pair with itself included, and subtracted that
self-pair afterwards; where one heavily degraded device holds both ends
of an edge the two float32 terms cancel.  These cases pin both routes of
the batched evaluator, the scalar twin and the weighted network movement
to the float64 oracle, and show that the replaced formulation, rebuilt
here, misses them."""

import contextlib

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (CostConfig, RegionFleet, edge_latencies, latency,
                        make_latency_fn, network_movement)
from repro.core.graph import Operator, OpGraph
from repro.core.jaxmodel import region_a_off, region_own, region_terms
from repro.core.objectives import OBJECTIVES
from repro.sim import BatchedEvaluator, pack_placements, pack_region_fleets

REL = 1e-5
V, R = 512, 8
HOT = 5            # the device both ends of the co-located edge share


def _graph() -> OpGraph:
    ops = [Operator(f"op{i}", 1.0 + 0.25 * i, out_bytes=1.0 + i)
           for i in range(4)]
    return OpGraph(ops, [(0, 1), (1, 2), (0, 2), (2, 3)])


def _case(hot_degrade: float, seed: int = 0):
    """Two fleets on one layout, the HOT device degraded ``hot_degrade``×
    in the second, and placements whose operators 1 and 2 (edge 1 → 2)
    put 95% of their mass on HOT, the rest spread over its region and the
    fleet."""
    rng = np.random.default_rng(seed)
    region = np.sort(rng.integers(0, R, V))
    region[HOT] = region[0]
    inter = rng.uniform(0.1, 2.0, (R, R))
    inter = (inter + inter.T) / 2
    fleets = []
    for d_hot in (1.0 + 1e-3, hot_degrade):
        degrade = rng.uniform(1.0, 4.0, V)
        degrade[HOT] = d_hot
        fleets.append(RegionFleet(region=region, inter=inter,
                                  degrade=degrade))
    mates = np.flatnonzero(region == region[HOT])
    mates = mates[mates != HOT]
    xs = []
    for _ in range(3):
        x = rng.dirichlet(np.full(V, 0.3), size=4)
        for op in (1, 2):
            x[op] = 0.0
            x[op, HOT] = 0.95
            x[op, mates] = 0.04 * rng.dirichlet(np.ones(len(mates)))
            x[op, rng.integers(0, V, 3)] += 0.01 / 3
        xs.append(x)
    for fleet in fleets:   # the case is what it says: HOT dominates
        dj = fleet.degrade_or_ones() * xs[0][2]
        assert dj[HOT] > 0.9 * dj[region == region[HOT]].sum()
    return _graph(), fleets, xs


CASES = {"degrade_1": 1.0 + 1e-3, "degrade_1e4": 1e4}


def _replaced_edge_latencies(g, fleet, x):
    """(E,) float32 edge latencies by the replaced formulation:
    ``t = mass @ a + (self_cost − d²·inter[r, r])·x_j``."""
    f32 = np.float32
    d = fleet.degrade_or_ones().astype(f32)
    inter = fleet.inter.astype(f32)
    x = x.astype(f32)
    a = d[None, :] * inter.T[:, fleet.region]
    corr = f32(fleet.self_cost) - d * d * np.diag(inter)[fleet.region]
    out = []
    for i, j in g.edges:
        mass = np.zeros(R, f32)
        np.add.at(mass, fleet.region, d * x[j])
        t = mass @ a + corr * x[j]
        out.append((x[i] * f32(g.operators[i].selectivity) * t).max())
    return np.array(out)


def _rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want) / np.abs(want)))


@pytest.mark.parametrize("pallas", [False, True], ids=["vmap", "pallas"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_colocated_edges_match_oracle(case, pallas, pallas_route):
    """Edge latencies, latency and the score grid at 1e-5 relative to the
    float64 oracle, on the vmap route and the Pallas route (interpret)."""
    g, fleets, xs = _case(CASES[case])
    fam = pack_region_fleets(fleets)
    xb = np.stack([xs[0]] * len(fleets))
    with pallas_route() if pallas else contextlib.nullcontext():
        ev = BatchedEvaluator(g, CostConfig())
        el = np.asarray(ev.edge_latencies(xb, fam))
        lat = np.asarray(ev.latency(xb, fam))
        grid = np.asarray(ev.score_grid(pack_placements(xs), fam))
    for s, fleet in enumerate(fleets):
        assert _rel(el[s], edge_latencies(g, fleet, xs[0])) <= REL
        assert _rel(lat[s], latency(g, fleet, xs[0])) <= REL
        assert _rel(grid[s], [latency(g, fleet, x) for x in xs]) <= REL


@pytest.mark.parametrize("case", sorted(CASES))
def test_colocated_scalar_twin_matches_oracle(case):
    """The single-fleet jnp twin (``make_latency_fn``, the gradient path)
    prices the same cases through the same region terms."""
    g, fleets, xs = _case(CASES[case])
    for fleet in fleets:
        lat = make_latency_fn(g, fleet)
        for x in xs:
            got = float(lat(jnp.asarray(x, jnp.float32)))
            assert _rel(got, latency(g, fleet, x)) <= REL


def test_replaced_formulation_misses_a_colocated_case():
    """The self-pair added into the region mass and subtracted afterwards,
    rebuilt in float32, is off by more than the limit on the degraded
    co-located device, where the evaluator is not."""
    worst = 0.0
    for case in CASES.values():
        g, fleets, xs = _case(case)
        for fleet in fleets:
            worst = max(worst, _rel(_replaced_edge_latencies(g, fleet, xs[0]),
                                    edge_latencies(g, fleet, xs[0])))
    assert worst > 10 * REL


@pytest.mark.parametrize("hot_degrade", [1.0, 1e2, 1e4])
def test_region_terms_relative_error_stays_a_few_ulps(hot_degrade):
    """Every device's transfer time ``mass @ a_off + w`` against the dense
    float64 ``com @ x_j``, over 200 draws of one co-located row with
    Dirichlet(0.3) fractions: a small multiple of float32 ε everywhere,
    whatever the degrade."""
    rng = np.random.default_rng(7)
    region = rng.integers(0, R, V)
    inter = rng.uniform(0.1, 2.0, (R, R))
    degrade = rng.uniform(1.0, 8.0, V)
    degrade[HOT] = hot_degrade
    fleet = RegionFleet(region=region, inter=inter, degrade=degrade,
                        self_cost=0.5)
    com = fleet.com_matrix()
    xj = rng.dirichlet(np.full(V, 0.3), size=200)
    xj[:, HOT] += rng.uniform(1.0, 50.0, 200)   # HOT holds most of the row
    xj /= xj.sum(1, keepdims=True)
    d = jnp.asarray(degrade, jnp.float32)
    inter32 = jnp.asarray(inter, jnp.float32)
    mass, w = region_terms(jnp.asarray(xj, jnp.float32), d,
                           region_own(inter32, d, region), region, R,
                           fleet.self_cost)
    a_off = region_a_off(inter32, d, region)
    t = np.asarray(jnp.matmul(mass, a_off, precision="highest") + w)
    want = xj @ com.T
    assert _rel(t, want) <= 16 * np.finfo(np.float32).eps


@pytest.mark.parametrize("case", sorted(CASES))
def test_colocated_weighted_movement_matches_oracle(case):
    """``network_movement_cost`` on the structured twin against
    ``network_movement(weight_by_cost=True)``: the same-region pairs come
    from the region terms, never a u == v pair added and subtracted."""
    g, fleets, xs = _case(CASES[case])
    spec = OBJECTIVES["network_movement_cost"]
    f = spec.build_structured(g, fleets[0].region, R, 0.0, CostConfig())
    for fleet in fleets:
        d = jnp.asarray(fleet.degrade_or_ones(), jnp.float32)
        inter = jnp.asarray(fleet.inter, jnp.float32)
        for x in xs:
            got = float(f(jnp.asarray(x, jnp.float32), inter, d, None))
            want = network_movement(g, fleet, x, weight_by_cost=True)
            assert _rel(got, want) <= REL


def test_structured_span_and_scope(telemetry, pallas_route):
    """The ``score_grid`` span carries ``R`` on the structured path, and
    on the Pallas route ``kernel_rows``: the edge kernel reads x and w per
    operator, 2·n_ops V-sized rows a placement row.  The region terms'
    device operations carry the ``region.terms`` scope on both routes."""
    import jax

    from repro import obs

    g, fleets, xs = _case(1e4)
    fam = pack_region_fleets(fleets)
    P = pack_placements(xs[:1])
    for route in (contextlib.nullcontext, pallas_route):
        with route():
            ev = BatchedEvaluator(g, CostConfig())
            ev.score_grid(P, fam)
            args = (P, *ev._family_args(fam), np.zeros((2, 1), np.float32),
                    np.float32(0.0))
            hlo = jax.jit(ev._structured(fam).grid).lower(*args).compile()
        assert "region.terms" in hlo.as_text()
    spans = [e["args"] for e in obs.trace_events()
             if e["name"] == "score_grid"]
    assert [(a["path"], a["R"]) for a in spans] == [("structured", R)] * 2
    assert [a.get("kernel_rows") for a in spans] == [None, 2 * g.n_ops]
