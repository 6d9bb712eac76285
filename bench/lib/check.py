"""The comparison that decides ``correct``.

Every answer that the window served whole is compared with the float64
reference (``bench.lib.reference``) over the same inputs.  One number is
compared, the widest relative gap of a run, taken over

  * every finished (S, P) score grid, and for pareto queries every
    per-objective grid and the weighted scalarization (the grid evaluator,
    the edge kernels and the on-device dq/beta finish);
  * every host decision, each priced by the reference: how far above the
    reference's own k-th best the served k-th ranked candidate lies, how
    far the served dq choice and min-max pick lie above the best, and how
    far the served Pareto front is from the reference's, in both
    directions (a served point that a reference point dominates, and a
    reference front point that no served point covers).

A decision that differs only where candidates tie to rounding has a gap of
that rounding; a wrong decision has a gap of the candidates' spread.  A
due query that was never answered, or an answer of the wrong shape, fails
on its own.
"""

from __future__ import annotations

import math

import numpy as np

from bench.lib import reference as ref

__all__ = ["query_gaps", "compare"]


def _rel(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return math.inf
    if got.size == 0:
        return 0.0
    err = np.abs(got - want) / np.maximum(np.abs(want), 1e-30)
    return float(np.max(np.where(np.isnan(err), np.inf, err)))


def _above(got_vals, best_vals) -> float:
    """Widest relative amount by which chosen values exceed the best."""
    got_vals = np.asarray(got_vals, np.float64)
    best_vals = np.asarray(best_vals, np.float64)
    if got_vals.shape != best_vals.shape:
        return math.inf
    if got_vals.size == 0:
        return 0.0
    return float(np.max(np.maximum(got_vals - best_vals, 0.0)
                        / np.maximum(np.abs(best_vals), 1e-30)))


def _front_gap(values: np.ndarray, served: np.ndarray) -> float:
    """Two-sided relative distance between a served front (indices) and
    the reference front of ``values`` (P, K)."""
    P = values.shape[0]
    served = np.asarray(served, np.int64)
    if served.size == 0 or served.min() < 0 or served.max() >= P:
        return math.inf
    scale = np.maximum(np.abs(values), 1e-30)
    # a served point p is dominated by q with margin min_k (v_p - v_q)/|v_p|
    d = (values[served][:, None, :] - values[None, :, :]) \
        / scale[served][:, None, :]
    incl = float(np.max(np.maximum(d.min(-1), 0.0)))
    # a reference front point r is covered by the best served p with
    # shortfall max_k (v_p - v_r)/|v_r|
    front = np.flatnonzero(ref.pareto_mask(values))
    c = (values[served][None, :, :] - values[front][:, None, :]) \
        / scale[front][:, None, :]
    excl = float(np.max(np.maximum(c.max(-1).min(-1), 0.0)))
    return max(incl, excl)


def query_gaps(dep, s) -> float:
    """The widest relative gap of one served answer."""
    q, res = s.q, s.result
    g, fleet = dep.graph, dep.fleet
    rows = slice(q.row0, q.row0 + q.rows)
    lat = ref.latency(g, fleet, dep.pool_idx[rows], dep.pool_w[rows])
    S = lat.shape[0]
    if q.kind == "joint":
        j = dep.mix["joint"]
        cube, best, _ = ref.joint(lat, np.asarray(j["dq_values"]), j["beta"])
        dq_idx = np.asarray(res.dq_idx)
        if dq_idx.shape != best.shape or res.best is None:
            return math.inf
        chosen = np.take_along_axis(
            cube, np.clip(dq_idx, 0, cube.shape[2] - 1)[..., None], 2)[..., 0]
        w = ref.worst(best)
        return max(_rel(res.scores, best), _above(chosen, best),
                   _above(w[int(res.best)], w.min()))
    dq = np.broadcast_to(np.asarray(q.dq, np.float64), (S,))[:, None]
    f = ref.objective_f(lat, dq, q.beta)
    if q.kind == "score":
        return _rel(res.scores, f)
    if q.kind == "rank":
        w = ref.worst(f)
        k = min(dep.mix.get("rank_top_k", 1), q.rows)
        top = np.asarray(res.top, np.int64)
        if top.shape != (k,) or top.min() < 0 or top.max() >= q.rows:
            return math.inf
        return max(_rel(res.scores, f), _rel(res.worst, w),
                   _above(w[top], np.sort(w)[:k]))
    # pareto: the objective grids, the scalarization and the front
    names = tuple(dep.mix["pareto_objectives"])
    weights = dep.mix["pareto_objectives"]
    mov = np.broadcast_to(
        ref.network_movement(g, dep.pool_idx[rows], dep.pool_w[rows]),
        f.shape)
    grids = {"latency_f": f, "network_movement": mov}
    if set(names) - set(grids) or res.grids is None:
        return math.inf
    scal = sum(weights[n] * grids[n] for n in names)
    gap = max([_rel(res.scores, scal)]
              + [_rel(res.grids.get(n), grids[n]) for n in names])
    values = np.stack([ref.worst(grids[n]) for n in names], axis=1)
    return max(gap, _front_gap(values, res.front.indices))


def compare(dep, served, limit: float) -> dict:
    """Check every answer served whole; returns the numbers compared,
    each with its limit, the per-query verdicts, and ``correct``."""
    widest, wrong, unanswered = 0.0, set(), 0
    for n, s in enumerate(served):
        if s.verdict in ("rejected", "degraded"):
            continue
        if s.result is None:
            unanswered += 1
            continue
        gap = query_gaps(dep, s)
        widest = max(widest, gap)
        if not gap <= limit:
            wrong.add(n)
    numbers = {"gap": (widest, limit), "unanswered": (unanswered, 0)}
    return {"numbers": numbers, "wrong": wrong,
            "correct": widest <= limit and unanswered == 0}
