"""The region terms of rows given as their nonzeros
(``jaxmodel.region_terms_sparse``) against the dense ``region_terms``, on
the vmap route (scatter-add and gather) and on the Pallas route's region
sum and one-hot matmul (interpreted): bitwise where no two support devices
of a row share a region, within 4 ulp where they do, and each row's bits
the same whatever rows ride with it."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import RegionFleet, random_dag
from repro.core.jaxmodel import region_own, region_terms, region_terms_sparse
from repro.sim import (BatchedEvaluator, SparsePlacements, pack_region_fleets,
                       sparse_placements)
from repro.sim.batched import _region_ops, takes_sparse_terms

V, R = 2048, 32
HOT = 5


def _layout(rng):
    region = rng.integers(0, R, V)
    region[:R] = np.arange(R)            # every region holds a device
    return region


def _scenario(rng, region, hot_degrade=1e4):
    inter = rng.uniform(0.1, 2.0, (R, R))
    degrade = rng.uniform(1.0, 4.0, V)
    degrade[HOT] = hot_degrade
    return RegionFleet(region=region, inter=(inter + inter.T) / 2,
                       degrade=degrade, self_cost=0.5)


def _distinct_rows(rng, region, P, n_ops, per_op):
    """(P, n_ops, V) rows whose support devices lie in distinct regions,
    with a ``-0.0`` entry in a region of its own, and every operator of
    row 0 on the HOT device (alone in its region there: it dominates it),
    so it holds both ends of each edge."""
    x = np.zeros((P, n_ops, V), np.float32)
    others = np.delete(np.arange(R), region[HOT])
    for p in range(P):
        for i in range(n_ops):
            regs = rng.choice(others, per_op + 1, replace=False)
            devs = [rng.choice(np.flatnonzero(region == r)) for r in regs]
            if p == 0:
                devs[0] = HOT
            x[p, i, devs[:-1]] = rng.dirichlet(np.ones(per_op))
            x[p, i, devs[-1]] = -0.0
    return x


def _shared_rows(rng, region, P, n_ops):
    """Rows over several devices of one region, the HOT device holding
    most of the row: ``loo`` comes from the rest of its region there."""
    mates = np.flatnonzero(region == region[HOT])
    mates = mates[mates != HOT]
    x = np.zeros((P, n_ops, V), np.float32)
    for p in range(P):
        for i in range(n_ops):
            x[p, i, HOT] = 0.9
            x[p, i, rng.choice(mates, 3, replace=False)] = \
                0.06 * rng.dirichlet(np.ones(3))
            x[p, i, rng.choice(V, 4, replace=False)] += \
                0.04 * rng.dirichlet(np.ones(4))
    return x


def _route_ops(route, region_ix):
    if route == "vmap":
        return None, None
    return _region_ops(region_ix, R, interpret=True)


def _terms(x, sp, fleet, route):
    """(dense mass, dense w, sparse mass, sparse w) of one scenario."""
    region_ix = jnp.asarray(fleet.region)
    d = jnp.asarray(fleet.degrade, jnp.float32)
    own = region_own(jnp.asarray(fleet.inter, jnp.float32), d, region_ix)
    segment_sum, per_device = _route_ops(route, region_ix)
    dense = jax.jit(lambda x: region_terms(x, d, own, region_ix, R,
                                           fleet.self_cost, segment_sum,
                                           per_device))(jnp.asarray(x))
    sparse = jax.jit(lambda i, v: region_terms_sparse(
        i, v, d, own, region_ix, R, fleet.self_cost, per_device))(
        sp.idx, sp.val)
    return [np.asarray(t) for t in (*dense, *sparse)]


def _bits(a) -> np.ndarray:
    return np.asarray(a, np.float32).view(np.uint32)


@pytest.mark.parametrize("per_op, k", [(4, 8), (12, 16)])
@pytest.mark.parametrize("route", ["vmap", "pallas"])
def test_sparse_terms_are_the_dense_bits_on_distinct_regions(route, per_op,
                                                             k):
    """Support devices in distinct regions: every region sum has one
    term, so mass and w are the dense terms bit for bit, ``-0.0``
    entries, the dominant HOT device and the pad slots (index V)
    included, at 8 and at ``SELECT_SLOTS`` (16) slots."""
    rng = np.random.default_rng(per_op)
    region = _layout(rng)
    fleet = _scenario(rng, region)
    x = _distinct_rows(rng, region, 3, 4, per_op)
    sp = sparse_placements(x)
    assert sp.idx.shape[2] == k and (sp.idx == V).any()
    mass, w, s_mass, s_w = _terms(x, sp, fleet, route)
    np.testing.assert_array_equal(_bits(s_mass), _bits(mass))
    np.testing.assert_array_equal(_bits(s_w), _bits(w))


@pytest.mark.parametrize("route", ["vmap", "pallas"])
@pytest.mark.parametrize("hot_degrade", [1.0, 1e4])
def test_sparse_terms_within_4_ulp_on_shared_regions(route, hot_degrade):
    """Several support devices in one region, one heavily degraded device
    holding most of the row (the co-located case): a region's sum adds
    its terms in another order, so mass and w agree within 4 ulp."""
    rng = np.random.default_rng(11)
    region = _layout(rng)
    fleet = _scenario(rng, region, hot_degrade)
    x = _shared_rows(rng, region, 3, 4)
    mass, w, s_mass, s_w = _terms(x, sparse_placements(x), fleet, route)
    for got, want in ((s_mass, mass), (s_w, w)):
        assert np.all(np.abs(got - want)
                      <= 4 * np.spacing(np.abs(want).astype(np.float32)))


@pytest.mark.parametrize("route", ["vmap", "pallas"])
def test_sparse_terms_of_a_row_do_not_depend_on_its_batch(route):
    """A row's mass and w alone and among seven other rows: the same
    bits."""
    rng = np.random.default_rng(5)
    region = _layout(rng)
    fleet = _scenario(rng, region)
    x = np.concatenate([_shared_rows(rng, region, 4, 4),
                        _distinct_rows(rng, region, 4, 4, 4)])
    sp = sparse_placements(x)
    _, _, mass8, w8 = _terms(x, sp, fleet, route)
    for p in (0, 5):
        _, _, mass1, w1 = _terms(x[p:p + 1], sp.rows(p, p + 1), fleet, route)
        np.testing.assert_array_equal(_bits(mass1[0]), _bits(mass8[p]))
        np.testing.assert_array_equal(_bits(w1[0]), _bits(w8[p]))


@pytest.mark.parametrize("route", ["vmap", "pallas"])
def test_sparse_terms_do_not_depend_on_the_slot_count(route):
    """The same rows with their slots padded from 8 to 16, as a served
    chunk pads them to its widest query: the same bits."""
    rng = np.random.default_rng(12)
    region = _layout(rng)
    fleet = _scenario(rng, region)
    x = _shared_rows(rng, region, 3, 4)
    sp = sparse_placements(x)
    wide = SparsePlacements(
        np.pad(sp.idx, ((0, 0), (0, 0), (0, 8)), constant_values=V),
        np.pad(sp.val, ((0, 0), (0, 0), (0, 8))), V)
    _, _, mass8, w8 = _terms(x, sp, fleet, route)
    _, _, mass16, w16 = _terms(x, wide, fleet, route)
    np.testing.assert_array_equal(_bits(mass16), _bits(mass8))
    np.testing.assert_array_equal(_bits(w16), _bits(w8))


@pytest.mark.parametrize("route", ["vmap", "pallas"])
def test_rows_of_more_than_16_slots_take_the_dense_terms(route, telemetry,
                                                         pallas_route):
    """Rows over 20 devices an operator cross as 32 slots: the sparse
    terms refuse them, and score_grid prices them by the dense terms
    (span ``terms=dense``), the bits of the same rows as a device
    array."""
    rng = np.random.default_rng(20)
    g = random_dag(5, 0.5, rng=rng)
    region = _layout(rng)
    fam = _family(rng, region)
    x = _distinct_rows(rng, region, 3, g.n_ops, 20)
    sp = sparse_placements(x)
    assert sp.idx.shape[2] == 32 and not takes_sparse_terms(sp)
    fleet = _scenario(rng, region)
    with pytest.raises(ValueError, match="at most 16"):
        _terms(x, sp, fleet, route)
    with pallas_route() if route == "pallas" else contextlib.nullcontext():
        ev = BatchedEvaluator(g)
        got = ev.score_grid(sp, fam, dq=0.2, beta=0.5)
        want = ev.score_grid(jnp.asarray(x), fam, dq=0.2, beta=0.5)
    np.testing.assert_array_equal(_bits(got), _bits(want))
    assert [e["args"]["terms"] for e in obs.trace_events()
            if e["name"] == "score_grid"] == ["dense", "dense"]


def _family(rng, region, S=3):
    return pack_region_fleets([_scenario(rng, region, 10.0 ** s)
                               for s in range(S)])


def test_sparse_scores_match_dense_device_rows_on_distinct_regions(
        pallas_route):
    """score_grid on the Pallas route of rows sent as their nonzeros
    against the same rows as a device array (the dense terms): bitwise,
    where no two support devices of a row share a region.  (On the vmap
    route the terms are the same bits too, but XLA fuses the region
    times' sum over R with the dense terms and may order it otherwise.)"""
    rng = np.random.default_rng(8)
    g = random_dag(5, 0.5, rng=rng)
    region = _layout(rng)
    fam = _family(rng, region)
    x = _distinct_rows(rng, region, 4, g.n_ops, 4)
    with pallas_route():
        ev = BatchedEvaluator(g)
        got = ev.score_grid(sparse_placements(x), fam, dq=0.2, beta=0.5)
        want = ev.score_grid(jnp.asarray(x), fam, dq=0.2, beta=0.5)
    np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("route", ["vmap", "pallas"])
def test_sparse_scores_of_a_row_do_not_depend_on_its_batch(route,
                                                           pallas_route):
    """A row scored alone and among seven other rows, as its nonzeros:
    the same bits in every scenario."""
    rng = np.random.default_rng(9)
    g = random_dag(5, 0.5, rng=rng)
    region = _layout(rng)
    fam = _family(rng, region)
    x = np.concatenate([_shared_rows(rng, region, 4, g.n_ops),
                        _distinct_rows(rng, region, 4, g.n_ops, 4)])
    sp = sparse_placements(x)
    with pallas_route() if route == "pallas" else contextlib.nullcontext():
        ev = BatchedEvaluator(g)
        batch = np.asarray(ev.score_grid(sp, fam))
        for p in (0, 6):
            one = ev.score_grid(sp.rows(p, p + 1), fam)
            np.testing.assert_array_equal(_bits(one)[:, 0],
                                          _bits(batch[:, p]))
