"""Region-fleet placements cross to the device as their nonzeros:
``sparse_placements`` and the device rebuild give back the dense rows bit
for bit, ``score_grid`` scores them (the region terms from the nonzeros)
as it scores the dense rows (the dense terms), and the service uploads a
region fleet's rows that way, on the route a direct call of its rows
takes."""

import contextlib

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import ObjectiveSet, RegionFleet, random_dag
from repro.serve import AdmissionConfig, QueryResult, WhatIfQuery, \
    WhatIfService
from repro.serve.bucketing import pad_rows
from repro.sim import (BatchedEvaluator, SparsePlacements,
                       pack_region_fleets, sparse_placements)
from repro.sim.batched import _densify

OBJ2 = ObjectiveSet.from_weights(latency_f=1.0, network_movement=0.05)


def _rows(rng, P, n_ops, V, per_op):
    """(P, n_ops, V) float32 rows, each operator over ``per_op`` devices."""
    x = np.zeros((P, n_ops, V), np.float32)
    for p in range(P):
        for i in range(n_ops):
            x[p, i, rng.choice(V, per_op, replace=False)] = \
                rng.dirichlet(np.ones(per_op))
    return x


def _dense(sp: SparsePlacements) -> np.ndarray:
    return np.asarray(_densify(sp.idx, sp.val, n_devices=sp.n_devices))


def _bits(x) -> np.ndarray:
    return np.asarray(x, np.float32).view(np.uint32)


@pytest.mark.parametrize("V, per_op, slots", [
    (1024, 4, 8),      # the slot floor
    (1000, 3, 8),      # V not a multiple of the 512-word scan block
    (2048, 20, 32),    # slots round up to a power of two
])
def test_sparse_placements_round_trip_bit_for_bit(V, per_op, slots):
    x = _rows(np.random.default_rng(V), 5, 6, V, per_op)
    x[0, 0, 7] = -0.0                        # kept by its bits
    x[1, 2, 0] = np.float32(3e-41)           # a subnormal at index 0
    sp = sparse_placements(x)
    assert sp.shape == x.shape and sp.idx.shape[2] == slots
    assert sp.idx.dtype == np.int32 and sp.val.dtype == np.float32
    np.testing.assert_array_equal(_bits(_dense(sp)), _bits(x))


@pytest.mark.parametrize("V, per_op", [(96, 4), (1024, 100)])
def test_sparse_placements_declines_rows_it_cannot_shrink(V, per_op):
    """More than V/16 nonzeros in some (row, operator): the dense rows
    cost no more to send."""
    assert sparse_placements(_rows(np.random.default_rng(1), 2, 3, V,
                                   per_op)) is None


def test_concat_pads_slots_and_rows_as_pad_rows_does():
    rng = np.random.default_rng(2)
    a, b = _rows(rng, 3, 4, 1024, 2), _rows(rng, 2, 4, 1024, 12)
    sa, sb = sparse_placements(a), sparse_placements(b)
    assert (sa.idx.shape[2], sb.idx.shape[2]) == (8, 16)
    got = SparsePlacements.concat([sa.rows(1, 3), sb], 8)
    assert got.shape == (8, 4, 1024)
    want = pad_rows(np.concatenate([a[1:3], b]), 8)
    np.testing.assert_array_equal(_bits(_dense(got)), _bits(want))
    with pytest.raises(ValueError, match="exceeds"):
        SparsePlacements.concat([sa, sb], 4)


def _family(rng, V=1024, R=4, S=3):
    region = rng.integers(0, R, V)
    fleets = []
    for k in range(S):
        inter = rng.uniform(0.1, 2.0, (R, R))
        fleets.append(RegionFleet(
            region=region, inter=(inter + inter.T) / 2,
            degrade=None if k == 0 else rng.uniform(1.0, 4.0, V)))
    return pack_region_fleets(fleets)


def _colocated_rows(rng, region, P, n_ops, per_op, distinct):
    """(P, n_ops, V) rows whose operators all spread over the same
    ``per_op`` devices of their row, so both ends of every edge sit on
    the support and the support's own entries of ``w`` enter each score;
    with ``distinct`` those devices lie in distinct regions (every region
    sum has one term), else anywhere."""
    n_regions = int(region.max()) + 1
    x = np.zeros((P, n_ops, region.size), np.float32)
    for p in range(P):
        devs = ([rng.choice(np.flatnonzero(region == r)) for r in
                 rng.choice(n_regions, per_op, replace=False)] if distinct
                else rng.choice(region.size, per_op, replace=False))
        for i in range(n_ops):
            x[p, i, devs] = rng.dirichlet(np.ones(per_op))
    return x


def _within_4_ulp(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert np.all(np.abs(got - want)
                  <= 4 * np.spacing(np.abs(want).astype(np.float32)))


def _score_sparse_as_dense():
    """score_grid of rows sent as their nonzeros (the sparse region terms)
    against the same rows as a device array (the dense terms), single- and
    multi-objective: bitwise where the support devices of each (row,
    operator) lie in distinct regions, within 4 ulp where they share
    one (a region's sum then adds its terms in another order)."""
    rng = np.random.default_rng(3)
    g = random_dag(5, 0.5, rng=rng)
    fam = _family(rng)
    ev = BatchedEvaluator.shared(g)
    shared = _colocated_rows(rng, fam.region, 4, g.n_ops, 4, False)
    distinct = _colocated_rows(rng, fam.region, 4, g.n_ops, 4, True)
    for x, same in ((distinct, np.testing.assert_array_equal),
                    (shared, _within_4_ulp)):
        sp = sparse_placements(x)
        assert sp is not None
        same(ev.score_grid(sp, fam, dq=0.3, beta=0.5),
             ev.score_grid(jnp.asarray(x), fam, dq=0.3, beta=0.5))
        got = ev.score_grid(sp, fam, objectives=OBJ2)
        want = ev.score_grid(jnp.asarray(x), fam, objectives=OBJ2)
        for name in OBJ2.names:
            same(got[name], want[name])


def test_score_grid_scores_sparse_rows_as_the_dense_rows():
    _score_sparse_as_dense()


def test_score_grid_scores_sparse_rows_as_the_dense_rows_on_pallas(
        pallas_route):
    """The same on the Pallas route (interpreted)."""
    with pallas_route():
        _score_sparse_as_dense()


def _serve_two_queries(rng, g, fam):
    """A 3-row and a 1-row query served in one 4-row chunk; returns the
    (ticket, rows, dq, β) of each and the served results by query id."""
    V, n_ops = fam.n_devices, g.n_ops
    svc = WhatIfService(g, admission=AdmissionConfig(p99_budget_s=1e6),
                        max_chunk_rows=4)
    fid = svc.register_fleet("a", fam)
    x, y = _rows(rng, 3, n_ops, V, 4), _rows(rng, 1, n_ops, V, 3)
    ta = svc.submit("a", fid, WhatIfQuery(kind="score", placements=x,
                                          dq=0.3, beta=0.5))
    tb = svc.submit("b", fid, WhatIfQuery(kind="score", placements=y))
    svc.drain()
    done = {m.query_id: m for t in ("a", "b") for m in svc.poll(t)
            if isinstance(m, QueryResult)}
    return [(ta, x, 0.3, 0.5), (tb, y, 0.0, 0.0)], done


def _uploads_the_nonzeros():
    rng = np.random.default_rng(4)
    g = random_dag(5, 0.5, rng=rng)
    fam = _family(rng)
    S, V, n_ops = fam.n_scenarios, 1024, g.n_ops
    queries, done = _serve_two_queries(rng, g, fam)
    got = [e["args"]["h2d_bytes"] for e in obs.trace_events()
           if e["name"] == "grid.upload"]
    # one 4-row chunk: 8 int32 + 8 float32 slots a row-operator, the
    # family's inter (S, R, R) and degrade (S, V), dq (S, 4), beta (4,)
    family = 4 * (S * 4 * 4 + S * V)
    assert got == [4 * (2 * 8 * 4 * n_ops + S * 4 + 4) + family]
    ev = BatchedEvaluator.shared(g)
    for tk, rows, dq, beta in queries:
        res = done[tk.query_id]
        np.testing.assert_array_equal(
            res.scores, np.asarray(ev.score_grid(rows, fam, dq=dq,
                                                 beta=beta), np.float32))


def test_region_fleet_service_uploads_the_nonzeros(telemetry):
    """A served region-fleet chunk sends (idx, val) slots, not V floats a
    row-operator, and its scores are bitwise a direct score_grid of the
    same host rows."""
    _uploads_the_nonzeros()


def test_region_fleet_service_uploads_the_nonzeros_on_pallas(telemetry,
                                                             pallas_route):
    """The same on the Pallas route (interpreted): served scores are
    bitwise a direct score_grid of the same host rows."""
    with pallas_route():
        _uploads_the_nonzeros()


def _terms_seen(reg) -> list:
    """(``terms`` of each structured score_grid span, region-terms counter
    values by route)."""
    spans = [e["args"].get("terms") for e in obs.trace_events()
             if e["name"] == "score_grid"]
    counts = {r["labels"]["route"]: r["value"] for r in reg.snapshot()
              if r["name"] == "eval.region_terms"}
    return [spans, counts]


@pytest.mark.parametrize("route", ["vmap", "pallas"])
def test_served_region_fleet_chunk_takes_the_sparse_terms(route, telemetry,
                                                          pallas_route):
    """The served chunk, and the direct call of the same host rows, record
    ``terms=sparse`` and count ``eval.region_terms{route=sparse}``; rows
    given as a device array keep the dense terms and record ``dense``."""
    rng = np.random.default_rng(6)
    g = random_dag(5, 0.5, rng=rng)
    fam = _family(rng)
    with pallas_route() if route == "pallas" else contextlib.nullcontext():
        queries, _ = _serve_two_queries(rng, g, fam)
        assert _terms_seen(telemetry) == [["sparse"], {"sparse": 1}]
        ev = BatchedEvaluator.shared(g)
        ev.score_grid(queries[0][1], fam)
        ev.score_grid(jnp.asarray(queries[0][1]), fam, objectives=OBJ2)
    assert _terms_seen(telemetry) == [["sparse", "sparse", "dense"],
                                      {"sparse": 2, "dense": 1}]


def test_rows_sparse_placements_refuses_are_served_apart(telemetry,
                                                         pallas_route):
    """A query over 100 devices an operator (more than V/16:
    ``sparse_placements`` refuses it) queued beside one over 4: each is
    its own super-batch, so each takes the region terms a direct call of
    its rows takes, and its scores are that call's bits."""
    rng = np.random.default_rng(7)
    g = random_dag(5, 0.5, rng=rng)
    fam = _family(rng)
    narrow, wide = _rows(rng, 3, g.n_ops, 1024, 4), \
        _rows(rng, 2, g.n_ops, 1024, 100)
    assert sparse_placements(wide) is None
    with pallas_route():
        svc = WhatIfService(g, admission=AdmissionConfig(p99_budget_s=1e6),
                            max_chunk_rows=8)
        fid = svc.register_fleet("a", fam)
        tickets = [svc.submit("a", fid, WhatIfQuery(
            kind="score", placements=rows, dq=0.3, beta=0.5))
            for rows in (wide, narrow)]
        svc.drain()
        assert _terms_seen(telemetry) == [["sparse", "dense"],
                                          {"sparse": 1, "dense": 1}]
        done = {m.query_id: m for m in svc.poll("a")
                if isinstance(m, QueryResult)}
        ev = BatchedEvaluator.shared(g)
        for tk, rows in zip(tickets, (wide, narrow)):
            np.testing.assert_array_equal(
                done[tk.query_id].scores,
                np.asarray(ev.score_grid(rows, fam, dq=0.3, beta=0.5),
                           np.float32))


def test_a_refused_query_split_across_chunks_keeps_the_dense_terms(
        telemetry, pallas_route):
    """A query whose last row spreads over 100 devices an operator (so
    ``sparse_placements`` refuses the whole query) and whose first three
    rows over 4, served in 2-row chunks: the chunk of narrow rows alone
    keeps the dense terms, as the direct call of the whole query does,
    and the served scores are that call's bits."""
    rng = np.random.default_rng(10)
    g = random_dag(5, 0.5, rng=rng)
    fam = _family(rng)
    rows = np.concatenate([_rows(rng, 3, g.n_ops, 1024, 4),
                           _rows(rng, 1, g.n_ops, 1024, 100)])
    assert sparse_placements(rows) is None
    assert sparse_placements(rows[:2]) is not None
    with pallas_route():
        svc = WhatIfService(g, admission=AdmissionConfig(p99_budget_s=1e6),
                            max_chunk_rows=2)
        fid = svc.register_fleet("a", fam)
        tk = svc.submit("a", fid, WhatIfQuery(kind="score", placements=rows,
                                              dq=0.3, beta=0.5))
        svc.drain()
        done = {m.query_id: m for m in svc.poll("a")
                if isinstance(m, QueryResult)}
        direct = BatchedEvaluator.shared(g).score_grid(rows, fam, dq=0.3,
                                                      beta=0.5)
    np.testing.assert_array_equal(done[tk.query_id].scores,
                                  np.asarray(direct, np.float32))
    assert _terms_seen(telemetry) == [["dense"] * 3, {"dense": 3}]
