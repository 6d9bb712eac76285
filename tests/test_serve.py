"""repro.serve: coalescing correctness (bitwise parity with direct
score_grid, padding non-leak, cross-tenant merging), streaming, typed
admission verdicts, and per-kind post-processing parity with the decision
layer."""

import gc

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import (CostConfig, DQCoupling, ExplicitFleet, ObjectiveSet,
                        random_dag, random_placement)
from repro.search import (epsilon_constraint, joint_dq_scores, pareto_front,
                          robust_select, split_dq_term)
from repro.serve import (AdmissionConfig, Admitted, Degraded, Rejected,
                         QueryResult, ResultChunk, WhatIfQuery,
                         WhatIfService, fleet_digest, next_pow2, pad_rows)
from repro.sim import BatchedEvaluator, fresh_cache, pack_fleets, \
    pack_placements

RELAXED = AdmissionConfig(p99_budget_s=1e6)     # never refuse
OBJ2 = ObjectiveSet.from_weights(latency_f=1.0, network_movement=0.05)


def _setup(seed=0, n_ops=5, n_dev=4, n_fleets=3):
    rng = np.random.default_rng(seed)
    g = random_dag(n_ops, edge_prob=0.6, rng=rng)
    fleets = []
    for _ in range(n_fleets):
        com = rng.uniform(0.1, 3.0, (n_dev, n_dev))
        com = (com + com.T) / 2
        np.fill_diagonal(com, 0.0)
        fleets.append(ExplicitFleet(com_cost=com))
    coms = np.asarray(pack_fleets(fleets))

    def placements(n):
        return np.stack([
            random_placement(n_ops, np.ones((n_ops, n_dev), bool), rng)
            for _ in range(n)]).astype(np.float32)

    return g, coms, placements


def _result(msgs, qid):
    (res,) = [m for m in msgs
              if isinstance(m, QueryResult) and m.query_id == qid]
    return res


def test_interleaved_tenants_bitwise_parity():
    """The core coalescing contract: many tenants, different row counts,
    different dq (scalar AND per-scenario) and β, all merged into shared
    padded dispatches — every tenant's scores are BITWISE what a direct
    dedicated score_grid call returns, and exactly their own rows (no
    padding, no neighbor rows leak)."""
    g, coms, placements = _setup()
    svc = WhatIfService(g, admission=RELAXED)
    fid = svc.register_fleet("anyone", coms)
    S = coms.shape[0]
    queries = [
        ("alice", placements(7), 0.3, 0.7),
        ("bob", placements(3), 0.0, 0.0),
        ("carol", placements(11), np.linspace(0.1, 0.8, S), 1.3),
        ("alice", placements(2), 0.9, 0.2),
    ]
    tickets = [(t, svc.submit(t, fid, WhatIfQuery(
        kind="score", placements=x, dq=dq, beta=beta)))
        for t, x, dq, beta in queries]
    assert all(isinstance(tk.admission, Admitted) for _, tk in tickets)
    svc.drain()
    mail = {t: svc.poll(t) for t in {"alice", "bob", "carol"}}
    ev = BatchedEvaluator.shared(g)
    for (tenant, x, dq, beta), (_, tk) in zip(queries, tickets):
        res = _result(mail[tenant], tk.query_id)
        direct = np.asarray(ev.score_grid(x, coms, dq=dq, beta=beta),
                            dtype=np.float32)
        assert res.scores.shape == (S, x.shape[0])
        np.testing.assert_array_equal(res.scores, direct)


def test_chunking_streams_partials_and_pads_safely():
    """max_chunk_rows smaller than the super-batch: queries stream as
    multiple ResultChunks whose offsets tile [0, P) exactly, concatenate
    to the final scores, and padded buckets never leak rows."""
    g, coms, placements = _setup()
    svc = WhatIfService(g, admission=RELAXED, max_chunk_rows=8)
    fid = svc.register_fleet("t", coms)
    x = placements(13)              # spans 2 chunks: 8 + 5 (padded to 8)
    tk = svc.submit("t", fid, WhatIfQuery(kind="score", placements=x,
                                          dq=0.4, beta=0.6))
    svc.drain()
    msgs = svc.poll("t")
    chunks = [m for m in msgs if isinstance(m, ResultChunk)]
    res = _result(msgs, tk.query_id)
    assert [c.offset for c in chunks] == [0, 8]
    assert [c.rows for c in chunks] == [8, 5]
    np.testing.assert_array_equal(
        np.concatenate([c.scores for c in chunks], axis=1), res.scores)
    direct = np.asarray(
        BatchedEvaluator.shared(g).score_grid(x, coms, dq=0.4, beta=0.6),
        dtype=np.float32)
    np.testing.assert_array_equal(res.scores, direct)


def test_pad_rows_contract():
    x = np.ones((3, 2, 4), np.float32)
    padded = pad_rows(x, 8)
    assert padded.shape == (8, 2, 4)
    np.testing.assert_array_equal(padded[3:], np.repeat(x[-1:], 5, axis=0))
    assert pad_rows(x, 3) is x
    with pytest.raises(ValueError, match="exceeds"):
        pad_rows(x, 2)
    assert [next_pow2(n) for n in (1, 2, 3, 9)] == [1, 2, 4, 16]


def test_equal_fleets_coalesce_across_tenants():
    """Two tenants registering EQUAL packs get the same fleet id (content
    digest), and their queries ride one dispatch — while a different
    objective set forks the coalesce key."""
    g, coms, placements = _setup()
    svc = WhatIfService(g, admission=RELAXED)
    fa = svc.register_fleet("a", coms.copy())
    fb = svc.register_fleet("b", coms.copy())
    assert fa == fb == svc.register_fleet("c", coms)
    assert svc.register_fleet("a", coms, objectives=OBJ2) != fa
    svc.submit("a", fa, WhatIfQuery(kind="score", placements=placements(4)))
    svc.submit("b", fb, WhatIfQuery(kind="score", placements=placements(4),
                                    dq=0.5, beta=2.0))
    svc.drain()
    snap = svc.stats.snapshot()
    assert len(snap["buckets"]) == 1          # ONE coalesced dispatch
    assert snap["buckets"][0]["dispatches"] == 1
    assert snap["buckets"][0]["queries"] == 2
    assert snap["buckets"][0]["rows"] == 8


def test_queues_key_on_fleet_and_objectives():
    """A service's queues are keyed by what separates them: the fleet's
    content digest and the objective set (the service owns one graph and
    one CostConfig, and the kernel route is the evaluator's own)."""
    import dataclasses

    from repro.serve import CoalesceKey

    assert [f.name for f in dataclasses.fields(CoalesceKey)] == [
        "fleet", "objectives"]
    g, coms, placements = _setup()
    svc = WhatIfService(g, admission=RELAXED)
    plain = svc.register_fleet("a", coms)
    multi = svc.register_fleet("b", coms, objectives=OBJ2)
    for tenant, fid in (("a", plain), ("b", multi), ("c", plain)):
        svc.submit(tenant, fid, WhatIfQuery(kind="score",
                                            placements=placements(2)))
    assert {k: len(v) for k, v in svc._queues.items()} == {
        CoalesceKey(fleet=plain, objectives=None): 2,
        CoalesceKey(fleet=multi, objectives=OBJ2): 1}


def test_multi_objective_grids_parity():
    """Multi-objective serving finishes dq/β in the shared dispatch: every
    per-objective grid and the scalarization are bitwise a direct
    dispatch at the query's own dq/β, scalar and per-scenario alike."""
    g, coms, placements = _setup()
    svc = WhatIfService(g, admission=RELAXED)
    fid = svc.register_fleet("t", coms, objectives=OBJ2)
    x, y = placements(6), placements(5)
    dq_y = np.linspace(0.1, 0.7, coms.shape[0]).astype(np.float32)
    tk = svc.submit("t", fid, WhatIfQuery(kind="score", placements=x,
                                          dq=0.35, beta=0.8))
    tk_y = svc.submit("t", fid, WhatIfQuery(kind="score", placements=y,
                                            dq=dq_y, beta=1.3))
    svc.drain()
    msgs = svc.poll("t")
    ev = BatchedEvaluator.shared(g)
    for ticket, xs, dq, beta in ((tk, x, 0.35, 0.8), (tk_y, y, dq_y, 1.3)):
        res = _result(msgs, ticket.query_id)
        direct = ev.score_grid(xs, coms, dq=dq, beta=beta, objectives=OBJ2)
        for name in OBJ2.names:
            np.testing.assert_array_equal(
                res.grids[name], np.asarray(direct.grids[name], np.float32))
        np.testing.assert_array_equal(
            res.scores, np.asarray(direct.scalarized, np.float32))


def test_rank_pareto_joint_match_decision_layer():
    """Per-kind post-processing == applying the decision layer directly to
    the same served grids."""
    g, coms, placements = _setup()
    svc = WhatIfService(g, admission=RELAXED)
    fid = svc.register_fleet("t", coms)
    fid_m = svc.register_fleet("t", coms, objectives=OBJ2)
    x = placements(9)
    dqv = np.linspace(0.0, 0.9, 7)
    coupling = DQCoupling(cap0=np.full(coms.shape[1], 3.0),
                          load=np.full(coms.shape[1], 2.0))
    t_rank = svc.submit("t", fid, WhatIfQuery(
        kind="rank", placements=x, dq=0.2, beta=0.5, top_k=4))
    t_par = svc.submit("t", fid_m, WhatIfQuery(kind="pareto", placements=x))
    t_joint = svc.submit("t", fid, WhatIfQuery(
        kind="joint", placements=x, beta=0.9, dq_values=dqv,
        coupling=coupling))
    svc.drain()
    msgs = svc.poll("t")
    ev = BatchedEvaluator.shared(g)

    rank = _result(msgs, t_rank.query_id)
    best, worst = robust_select(np.asarray(
        ev.score_grid(x, coms, dq=0.2, beta=0.5), dtype=np.float32))
    np.testing.assert_array_equal(rank.worst, worst)
    assert rank.top[0] == best and len(rank.top) == 4

    par = _result(msgs, t_par.query_id)
    want_front = pareto_front(ev.score_grid(x, coms, objectives=OBJ2))
    np.testing.assert_array_equal(par.front.indices, want_front.indices)

    joint = _result(msgs, t_joint.query_id)
    lat, rest, w_lat = split_dq_term(
        np.asarray(ev.score_grid(x, coms), dtype=np.float32))
    from repro.search import dq_caps_mask
    want_scores, want_idx = joint_dq_scores(
        lat, dqv, 0.9, rest=rest, w_lat=w_lat,
        feasible=dq_caps_mask(x, dqv, coupling))
    np.testing.assert_array_equal(joint.scores, want_scores)
    np.testing.assert_array_equal(joint.dq_idx, want_idx)
    assert joint.best == robust_select(want_scores)[0]


def test_eps_constraint_rank_and_infeasible_flag():
    g, coms, placements = _setup()
    svc = WhatIfService(g, admission=RELAXED)
    fid = svc.register_fleet("t", coms, objectives=OBJ2)
    x = placements(8)
    t_ok = svc.submit("t", fid, WhatIfQuery(
        kind="rank", placements=x, minimize="latency_f",
        eps_caps={"network_movement": 1e9}, top_k=2))
    t_bad = svc.submit("t", fid, WhatIfQuery(
        kind="rank", placements=x, minimize="latency_f",
        eps_caps={"network_movement": -1.0}))
    svc.drain()
    msgs = svc.poll("t")
    ok = _result(msgs, t_ok.query_id)
    grids = BatchedEvaluator.shared(g).score_grid(x, coms, objectives=OBJ2)
    want_idx, _ = epsilon_constraint(grids, "latency_f",
                                     {"network_movement": 1e9})
    assert not ok.infeasible and ok.top[0] == want_idx
    bad = _result(msgs, t_bad.query_id)
    assert bad.infeasible and np.all(np.isinf(bad.worst))


def test_admission_rejects_and_degrades_typed():
    """A zero-ish budget rejects with the price it refused; a budget that
    fits a prefix degrades: the ticket says keep_rows/actions, and the
    result covers exactly the kept prefix (bitwise)."""
    g, coms, placements = _setup()
    x = placements(64)
    with fresh_cache():             # pricer must not see a warm cache
        svc = WhatIfService(g, admission=AdmissionConfig(
            p99_budget_s=0.0, allow_degrade=False))
        fid = svc.register_fleet("t", coms)
        verdict = svc.submit("t", fid, WhatIfQuery(kind="score",
                                                   placements=x))
        assert isinstance(verdict, Rejected)
        assert verdict.predicted_s > verdict.budget_s == 0.0
        assert "exceeds p99 budget" in verdict.reason
        assert svc.stats.snapshot()["admission"]["rejected"] == 1

    with fresh_cache():
        svc = WhatIfService(g, admission=AdmissionConfig(p99_budget_s=1e6))
        fid = svc.register_fleet("t", coms)
        # warm once so the pricer is calibrated on real dispatch time,
        # then set the budget to ~45% of the 64-row price: degrade land
        svc.submit("t", fid, WhatIfQuery(kind="score", placements=x))
        svc.drain()
        svc.poll("t")
        price = svc._fleets[fid].pricer.price_s(coms.shape[0], 64)
        svc.admission = AdmissionConfig(p99_budget_s=price * 0.45,
                                        min_rows=8)
        tk = svc.submit("t", fid, WhatIfQuery(kind="score", placements=x,
                                              dq=0.3, beta=0.7))
        assert isinstance(tk, type(tk)) and isinstance(tk.admission,
                                                       Degraded)
        assert "subsample_candidates" in tk.admission.actions
        assert tk.rows == tk.admission.keep_rows < 64
        svc.drain()
        res = _result(svc.poll("t"), tk.query_id)
        direct = np.asarray(BatchedEvaluator.shared(g).score_grid(
            x[:tk.rows], coms, dq=0.3, beta=0.7), dtype=np.float32)
        np.testing.assert_array_equal(res.scores, direct)
        assert res.degraded is tk.admission
        assert svc.stats.snapshot()["admission"]["degraded"] == 1


def test_joint_degrade_coarsens_dq_grid():
    g, coms, placements = _setup()
    with fresh_cache():
        svc = WhatIfService(g, admission=AdmissionConfig(p99_budget_s=1e6))
        fid = svc.register_fleet("t", coms)
        x = placements(32)
        svc.submit("t", fid, WhatIfQuery(kind="score", placements=x))
        svc.drain(); svc.poll("t")
        price = svc._fleets[fid].pricer.price_s(coms.shape[0], 32)
        svc.admission = AdmissionConfig(p99_budget_s=price * 0.45,
                                        min_rows=4, degrade_dq_steps=3)
        tk = svc.submit("t", fid, WhatIfQuery(
            kind="joint", placements=x, beta=0.5,
            dq_values=np.linspace(0, 0.9, 11)))
        assert isinstance(tk.admission, Degraded)
        assert "coarsen_dq_grid" in tk.admission.actions
        assert tk.dq_steps == 3
        svc.drain()
        res = _result(svc.poll("t"), tk.query_id)
        assert res.dq_idx.max() <= 2


def test_fleet_digest_is_content_addressed():
    rng = np.random.default_rng(3)
    a = rng.uniform(0.1, 1.0, (2, 4, 4)).astype(np.float32)
    assert fleet_digest(a) == fleet_digest(a.copy())
    b = a.copy()
    b[0, 1, 2] += 1e-3
    assert fleet_digest(a) != fleet_digest(b)
    with pytest.raises(ValueError, match=r"\(S, V, V\)"):
        fleet_digest(np.zeros((3, 4)))


def test_submit_validation():
    g, coms, placements = _setup()
    svc = WhatIfService(g, admission=RELAXED)
    fid = svc.register_fleet("t", coms)
    with pytest.raises(ValueError, match="kind"):
        WhatIfQuery(kind="nope", placements=placements(2))
    with pytest.raises(ValueError, match="dq_values"):
        WhatIfQuery(kind="joint", placements=placements(2))
    with pytest.raises(ValueError, match="ObjectiveSet"):
        svc.submit("t", fid, WhatIfQuery(kind="pareto",
                                         placements=placements(2)))
    with pytest.raises(ValueError, match="devices"):
        svc.submit("t", fid, WhatIfQuery(
            kind="score", placements=np.ones((2, 5, 9), np.float32)))


# -- the served path's spans --------------------------------------------------

SPAN_PARENT = {"serve.assemble": "serve.chunk", "score_grid": "serve.chunk",
               "grid.upload": "score_grid", "serve.fetch": "serve.chunk",
               "serve.finalize": "serve.chunk", "serve.chunk": "serve.step"}


def _serve_once(g, coms, xs):
    """Two tenants' score queries through one fresh service and one
    step(): (query ids, each query's final scores)."""
    svc = WhatIfService(g, admission=RELAXED, max_chunk_rows=4)
    fid = svc.register_fleet("a", coms)
    ids = [svc.submit(t, fid, WhatIfQuery(kind="score", placements=x,
                                          dq=0.3, beta=0.5)).query_id
           for t, x in zip(("a", "b"), xs)]
    assert svc.step() == len(xs)
    mail = svc.poll("a") + svc.poll("b")
    return ids, [_result(mail, q).scores for q in ids]


def test_served_step_spans_under_the_profiler(telemetry, host_trace):
    """One step of 3 + 2 rows in chunks of 4: the span tree of the served
    path on the profiler's host plane, with the stats the benchmark reads;
    the answers are bitwise those served with telemetry off."""
    g, coms, placements = _setup(n_fleets=2)
    xs = [placements(3), placements(2)]
    with host_trace() as events:
        ids, on = _serve_once(g, coms, xs)
    spans = [e for e in events if e[0].startswith(("serve.", "grid.",
                                                   "score_grid"))]
    names = [e[0] for e in spans]
    assert {n: names.count(n) for n in set(names)} == {
        "serve.register": 1, "serve.admit": 2, "serve.enqueue": 2,
        "serve.step": 1, "serve.chunk": 2, "serve.assemble": 2,
        "score_grid": 2, "grid.upload": 2, "serve.fetch": 2,
        "serve.finalize": 2}
    for name, a, b, _ in spans:
        if name in SPAN_PARENT:
            assert any(p == SPAN_PARENT[name] and pa <= a and b <= pb
                       for p, pa, pb, _ in spans), name
    chunks = sorted((e for e in spans if e[0] == "serve.chunk"),
                    key=lambda e: e[1])
    assert [(c[3]["bucket"], c[3]["rows"]) for c in chunks] == [(4, 4),
                                                                 (1, 1)]
    # the first chunk carries both queries, the second the rest of one
    carried = [[int(q) for q in str(c[3]["query_ids"]).split()]
               for c in chunks]
    assert carried == [ids, ids[1:]]
    assert [e[3]["query_id"] for e in spans
            if e[0] == "serve.enqueue"] == ids
    S, V = coms.shape[0], coms.shape[1]
    n_ops = xs[0].shape[1]
    # padded placements, dq (S, bucket), beta (bucket,): the pack crossed
    # once, at registration
    want = sorted(4 * (b * n_ops * V + S * b + b) for b in (4, 1))
    assert sorted(e[3]["h2d_bytes"] for e in spans
                  if e[0] == "grid.upload") == want
    assert sorted(e[3]["d2h_bytes"] for e in spans
                  if e[0] == "serve.fetch") == [4 * S * 1, 4 * S * 4]
    obs.disable()
    _, off = _serve_once(g, coms, xs)
    for a, b in zip(on, off):
        np.testing.assert_array_equal(a, b)


def test_upload_counts_only_host_operands(telemetry):
    """A pack already on the device crosses nothing: h2d_bytes is then
    the placements, dq and β alone."""
    g, coms, placements = _setup(n_fleets=2)
    x = placements(4)
    dq = np.full((coms.shape[0], 4), 0.2, np.float32)
    beta = np.full(4, 0.5, np.float32)
    ev = BatchedEvaluator.shared(g)
    ev.score_grid(x, coms, dq=dq, beta=beta)
    on_device = pack_fleets([ExplicitFleet(com_cost=c) for c in coms])
    ev.score_grid(x, on_device, dq=dq, beta=beta)
    got = [e["args"]["h2d_bytes"] for e in obs.trace_events()
           if e["name"] == "grid.upload"]
    assert got == [x.nbytes + coms.nbytes + dq.nbytes + beta.nbytes,
                   x.nbytes + dq.nbytes + beta.nbytes]


def test_dense_pack_crosses_once_at_registration(telemetry):
    """A dense pack crosses to the device when it is registered, once per
    content: a second objective set over the same pack moves nothing, and
    no served upload carries it.  The answers are bitwise a direct
    score_grid on the NumPy pack."""
    g, coms, placements = _setup(n_fleets=2)
    S, V = coms.shape[0], coms.shape[1]
    svc = WhatIfService(g, admission=RELAXED, max_chunk_rows=4)
    fid = svc.register_fleet("a", coms)
    fid_m = svc.register_fleet("a", coms, objectives=OBJ2)
    reg = [e["args"] for e in obs.trace_events()
           if e["name"] == "serve.register"]
    assert [a["device_bytes"] for a in reg] == [S * V * V * 4, 0]
    x = placements(3)
    tk = svc.submit("a", fid, WhatIfQuery(kind="score", placements=x,
                                          dq=0.3, beta=0.5))
    svc.submit("b", fid_m, WhatIfQuery(kind="pareto",
                                       placements=placements(2)))
    svc.drain()
    n_ops = x.shape[1]
    got = sorted(e["args"]["h2d_bytes"] for e in obs.trace_events()
                 if e["name"] == "grid.upload")
    # placements padded to the bucket, dq (S, bucket), beta (bucket,)
    assert got == sorted(4 * (b * n_ops * V + S * b + b) for b in (4, 2))
    res = _result(svc.poll("a"), tk.query_id)
    direct = BatchedEvaluator.shared(g).score_grid(x, coms, dq=0.3,
                                                   beta=0.5)
    np.testing.assert_array_equal(res.scores,
                                  np.asarray(direct, np.float32))


def _live_packs(shape) -> int:
    gc.collect()
    return sum(a.shape == shape for a in jax.live_arrays())


def test_dense_pack_is_held_once_per_content_on_the_device():
    """One device array per content digest and service: the plain and the
    multi-objective fleet of one pack share it, a float64 pack is held as
    float32, and the copy goes with the service."""
    g, coms, placements = _setup(n_dev=5, n_fleets=7)
    before = _live_packs(coms.shape)
    svc = WhatIfService(g, admission=RELAXED)
    fid = svc.register_fleet("a", coms.astype(np.float64))
    fid_m = svc.register_fleet("b", coms, objectives=OBJ2)
    assert fid_m.startswith(fid)
    assert _live_packs(coms.shape) == before + 1
    assert any(a.dtype == np.float32 and a.shape == coms.shape
               and np.array_equal(np.asarray(a), coms)
               for a in jax.live_arrays())
    x = placements(3)
    tk = svc.submit("a", fid, WhatIfQuery(kind="score", placements=x))
    svc.drain()
    np.testing.assert_array_equal(
        _result(svc.poll("a"), tk.query_id).scores,
        np.asarray(BatchedEvaluator.shared(g).score_grid(x, coms)))
    del svc
    assert _live_packs(coms.shape) == before


def test_device_pack_is_registered_in_place(telemetry):
    """A pack already on the device is neither copied nor moved: the
    registration puts no bytes on the device and holds no second array."""
    g, coms, placements = _setup(n_dev=3, n_fleets=6)
    on_device = jax.device_put(coms)
    before = _live_packs(coms.shape)
    svc = WhatIfService(g, admission=RELAXED)
    fid = svc.register_fleet("a", on_device)
    assert fid == fleet_digest(coms)
    assert _live_packs(coms.shape) == before
    (reg,) = [e["args"] for e in obs.trace_events()
              if e["name"] == "serve.register"]
    assert reg["device_bytes"] == 0
    x = placements(2)
    tk = svc.submit("a", fid, WhatIfQuery(kind="score", placements=x))
    svc.drain()
    np.testing.assert_array_equal(
        _result(svc.poll("a"), tk.query_id).scores,
        np.asarray(BatchedEvaluator.shared(g).score_grid(x, coms)))


@pytest.mark.parametrize("status", ["RESOURCE_EXHAUSTED", "INTERNAL"])
def test_full_device_keeps_the_dense_pack_on_the_host(telemetry, monkeypatch,
                                                      status):
    """Where the device has no room for a dense pack, registration still
    succeeds: the float32 host pack is held and every dispatch copies it,
    with answers bitwise a direct score_grid.  Any other device error at
    registration is raised."""
    g, coms, placements = _setup(n_dev=6, n_fleets=5)
    S, V = coms.shape[0], coms.shape[1]
    svc = WhatIfService(g, admission=RELAXED, max_chunk_rows=4)
    real = jax.numpy.asarray

    def full(a, *args, **kw):
        if getattr(a, "shape", None) == coms.shape:
            raise jax.errors.JaxRuntimeError(f"{status}: no room for the pack")
        return real(a, *args, **kw)

    with monkeypatch.context() as m:
        m.setattr(jax.numpy, "asarray", full)
        if status != "RESOURCE_EXHAUSTED":
            with pytest.raises(jax.errors.JaxRuntimeError, match=status):
                svc.register_fleet("a", coms)
            return
        fid = svc.register_fleet("a", coms)
        assert svc.register_fleet("b", coms, objectives=OBJ2) != fid
    assert [e["args"]["device_bytes"] for e in obs.trace_events()
            if e["name"] == "serve.register"] == [0, 0]
    x = placements(3)
    tk = svc.submit("a", fid, WhatIfQuery(kind="score", placements=x))
    svc.drain()
    (up,) = [e["args"]["h2d_bytes"] for e in obs.trace_events()
             if e["name"] == "grid.upload"]
    assert up == 4 * (4 * x.shape[1] * V + S * V * V + S * 4 + 4)
    np.testing.assert_array_equal(
        _result(svc.poll("a"), tk.query_id).scores,
        np.asarray(BatchedEvaluator.shared(g).score_grid(x, coms)))


def test_served_path_builds_nothing_with_telemetry_off(monkeypatch):
    """Telemetry off: no profiler annotation, no query-id string and no
    byte count anywhere on the served path."""
    from repro.serve import service as service_mod
    from repro.sim import batched as batched_mod

    def refuse(*args, **kwargs):
        raise AssertionError("telemetry work with telemetry off")

    monkeypatch.setattr("jax.profiler.TraceAnnotation", refuse)
    monkeypatch.setattr(service_mod, "_query_ids", refuse)
    monkeypatch.setattr(batched_mod, "_host_bytes", refuse)
    assert not obs.enabled()
    g, coms, placements = _setup(n_fleets=2)
    _serve_once(g, coms, [placements(3), placements(2)])
