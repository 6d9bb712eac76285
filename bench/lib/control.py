"""The control: the reference put in the program's place, in the precision
just below the program's.

The program's edge kernels contract in float32 at ``Precision.HIGHEST``,
so the control contracts in float32 at ``high``: three bfloat16 passes
(``hi*hi + hi*lo + lo*hi``, each product exact in float32, the low-low term
dropped), which is what an XLA float32 matmul at ``Precision.HIGH`` does on
a TPU.  The passes are spelled out, so the control reads the same on any
backend.  Everything else is float32, and its answers are finished and
decided from its own grids by the reference's rules.  ``correct`` has to
come out false on it.
"""

from __future__ import annotations

import types

import ml_dtypes
import numpy as np

from bench.lib import reference as ref
from bench.lib.deploy import Dense

__all__ = ["split_bf16", "latency32", "answer"]

F32 = np.float32


def split_bf16(a: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """float32 ``a`` as ``hi + lo``, both bfloat16 values held in float32."""
    a = np.asarray(a, F32)
    hi = a.astype(ml_dtypes.bfloat16).astype(F32)
    lo = (a - hi).astype(ml_dtypes.bfloat16).astype(F32)
    return hi, lo


def _pair_costs32(fleet, u, v) -> np.ndarray:
    if isinstance(fleet, Dense):
        return np.stack([fleet.com[s][u, v] for s in range(fleet.n_scenarios)])
    d = fleet.degrade.astype(F32)
    inter = fleet.inter.astype(F32)
    out = d[:, u] * d[:, v] * inter[:, fleet.region[u], fleet.region[v]]
    return np.where(u == v, F32(fleet.self_cost), out).astype(F32)


def latency32(g, fleet, idx: np.ndarray, w: np.ndarray) -> np.ndarray:
    """(S, P) float32 latencies with the contraction in three passes."""
    w = np.asarray(w, F32)
    src = np.array([i for i, _ in g.edges])
    dst = np.array([j for _, j in g.edges])
    com = _pair_costs32(fleet, idx[:, src, :, None], idx[:, dst, None, :])
    c_hi, c_lo = split_bf16(com)
    x_hi, x_lo = split_bf16(w[None, :, dst, None, :])
    t = ((c_hi * x_hi).sum(-1, dtype=F32) + (c_hi * x_lo).sum(-1, dtype=F32)
         + (c_lo * x_hi).sum(-1, dtype=F32))
    xi = w[None, :, src, :] * g.selectivity[src].astype(F32)[None, None, :,
                                                               None]
    elat = np.maximum((xi * t).max(-1), F32(0))
    dist = np.zeros(elat.shape[:2] + (g.n_ops,), F32)
    for e, (i, j) in sorted(enumerate(g.edges), key=lambda t: t[1]):
        dist[..., j] = np.maximum(dist[..., j], dist[..., i] + elat[..., e])
    has_out = np.zeros(g.n_ops, bool)
    has_out[src] = True
    return dist[..., ~has_out].max(-1)


def answer(dep, q):
    """The control's answer to one scheduled query, with the fields the
    check reads from a ``QueryResult``."""
    rows = slice(q.row0, q.row0 + q.rows)
    lat = latency32(dep.graph, dep.fleet, dep.pool_idx[rows],
                    dep.pool_w[rows])
    S = lat.shape[0]
    if q.kind == "joint":
        j = dep.mix["joint"]
        _, best, dq_idx = ref.joint(lat.astype(np.float64),
                                    np.asarray(j["dq_values"]), j["beta"])
        return types.SimpleNamespace(
            scores=best, dq_idx=dq_idx, best=int(ref.worst(best).argmin()))
    dq = np.broadcast_to(np.asarray(q.dq, F32), (S,))[:, None]
    f = (lat / (F32(1) + F32(q.beta) * dq)).astype(F32)
    w = ref.worst(f)
    if q.kind in ("score", "rank"):
        k = min(dep.mix.get("rank_top_k", 1), q.rows)
        return types.SimpleNamespace(
            scores=f, worst=w, top=np.argsort(w, kind="stable")[:k])
    obj = dep.mix["pareto_objectives"]
    mov = np.broadcast_to(ref.network_movement(
        dep.graph, dep.pool_idx[rows], dep.pool_w[rows]).astype(F32),
        f.shape)
    grids = {"latency_f": f, "network_movement": mov}
    scal = sum(F32(obj[n]) * grids[n] for n in obj)
    values = np.stack([ref.worst(grids[n]) for n in obj], axis=1)
    front = np.flatnonzero(ref.pareto_mask(values))
    return types.SimpleNamespace(
        scores=scal, grids=grids,
        front=types.SimpleNamespace(indices=front))
