"""Region-fleet placements cross to the device as their nonzeros:
``sparse_placements`` and the device rebuild give back the dense rows bit
for bit, ``score_grid`` scores them as it scores the dense rows, and the
service uploads a region fleet's rows that way."""

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


def test_score_grid_scores_sparse_rows_as_the_dense_rows():
    rng = np.random.default_rng(3)
    g = random_dag(5, 0.5, rng=rng)
    fam = _family(rng)
    x = _rows(rng, 4, g.n_ops, 1024, 4)
    ev = BatchedEvaluator.shared(g)
    sp = sparse_placements(x)
    np.testing.assert_array_equal(
        np.asarray(ev.score_grid(sp, fam, dq=0.3, beta=0.5)),
        np.asarray(ev.score_grid(x, fam, dq=0.3, beta=0.5)))
    got = ev.score_grid(sp, fam, objectives=OBJ2)
    want = ev.score_grid(x, fam, objectives=OBJ2)
    for name in OBJ2.names:
        np.testing.assert_array_equal(np.asarray(got[name]),
                                      np.asarray(want[name]))


def test_region_fleet_service_uploads_the_nonzeros(telemetry):
    """A served region-fleet chunk sends (idx, val) slots, not V floats a
    row-operator, and its scores are bitwise a direct dense score_grid."""
    rng = np.random.default_rng(4)
    g = random_dag(5, 0.5, rng=rng)
    fam = _family(rng)
    S, V, n_ops = fam.n_scenarios, 1024, g.n_ops
    svc = WhatIfService(g, admission=AdmissionConfig(p99_budget_s=1e6),
                        max_chunk_rows=4)
    fid = svc.register_fleet("a", fam)
    x, y = _rows(rng, 3, n_ops, V, 4), _rows(rng, 1, n_ops, V, 3)
    ta = svc.submit("a", fid, WhatIfQuery(kind="score", placements=x,
                                          dq=0.3, beta=0.5))
    tb = svc.submit("b", fid, WhatIfQuery(kind="score", placements=y))
    svc.drain()
    got = [e["args"]["h2d_bytes"] for e in obs.trace_events()
           if e["name"] == "grid.upload"]
    # one 4-row chunk: 8 int32 + 8 float32 slots a row-operator, the
    # family's inter (S, R, R) and degrade (S, V), dq (S, 4), beta (4,)
    family = 4 * (S * 4 * 4 + S * V)
    assert got == [4 * (2 * 8 * 4 * n_ops + S * 4 + 4) + family]
    ev = BatchedEvaluator.shared(g)
    done = {m.query_id: m for t in ("a", "b") for m in svc.poll(t)
            if isinstance(m, QueryResult)}
    for tk, rows, dq, beta in ((ta, x, 0.3, 0.5), (tb, y, 0.0, 0.0)):
        res = done[tk.query_id]
        np.testing.assert_array_equal(
            res.scores, np.asarray(ev.score_grid(rows, fam, dq=dq,
                                                 beta=beta), np.float32))
