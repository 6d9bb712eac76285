"""The plain float64 reference of the paper's cost model (§3, §3.1).

It follows ``repro.core.costmodel`` (``edge_latency``, ``latency``,
``objective_F``, ``network_movement``) and the decision rules of
``repro.search.decision`` (min-max selection, joint dq expansion, Pareto
mask), and imports nothing of the program:

    edgeLat(i->j) = max_u x_iu * s_i * sum_v com_uv * x_jv     (alpha = 0)
    latency       = max over source->sink paths of summed edgeLat
    F             = latency / (1 + beta * dq)
    movement      = sum_edges rate_i*s_i*bytes_i*(sum x_i * sum x_j - x_i.x_j)

Placements come in sparse form (device ids and fractions per operator),
so each sum runs over the devices that hold a fraction; a device that
holds none adds an exact zero, and the max over the others is 0, which
``max(0, ...)`` keeps.  That is exact, and it is what makes the reference
fast enough to check every answer of a run.
"""

from __future__ import annotations

import numpy as np

from bench.lib.deploy import Dense, Graph, Structured

__all__ = ["pair_costs", "latency", "objective_f", "rates",
           "network_movement", "worst", "joint", "pareto_mask"]


def pair_costs(fleet: Dense | Structured, u: np.ndarray,
               v: np.ndarray) -> np.ndarray:
    """com[s, u, v] in float64 for every scenario: u, v are broadcastable
    integer arrays of device ids; the result has a leading S axis."""
    if isinstance(fleet, Dense):
        return np.stack([fleet.com[s][u, v].astype(np.float64)
                         for s in range(fleet.n_scenarios)])
    r_u, r_v = fleet.region[u], fleet.region[v]
    out = (fleet.degrade[:, u] * fleet.degrade[:, v]
           * fleet.inter[:, r_u, r_v])
    return np.where(u == v, fleet.self_cost, out)


def latency(g: Graph, fleet: Dense | Structured, idx: np.ndarray,
            w: np.ndarray) -> np.ndarray:
    """(S, P) critical-path latencies of P placements given sparse as
    device ids ``idx`` and fractions ``w``, both (P, n_ops, K)."""
    w = np.asarray(w, np.float64)
    src = np.array([i for i, _ in g.edges])
    dst = np.array([j for _, j in g.edges])
    u = idx[:, src, :, None]                         # (P, E, K, 1)
    v = idx[:, dst, None, :]                         # (P, E, 1, K)
    com = pair_costs(fleet, u, v)                    # (S, P, E, K, K)
    t = (com * w[None, :, dst, None, :]).sum(-1)     # (S, P, E, K)
    xi = w[None, :, src, :] * g.selectivity[src][None, None, :, None]
    elat = np.maximum((xi * t).max(-1), 0.0)         # (S, P, E)
    dist = np.zeros(elat.shape[:2] + (g.n_ops,))
    for e, (i, j) in sorted(enumerate(g.edges), key=lambda t: t[1]):
        dist[..., j] = np.maximum(dist[..., j], dist[..., i] + elat[..., e])
    has_out = np.zeros(g.n_ops, bool)
    has_out[src] = True
    return dist[..., ~has_out].max(-1)


def objective_f(lat: np.ndarray, dq: np.ndarray, beta) -> np.ndarray:
    """Paper eq. 8 over an (S, P) grid; dq broadcasts against it."""
    return lat / (1.0 + np.asarray(beta, np.float64) * dq)


def rates(g: Graph) -> np.ndarray:
    """Relative input rate of each operator for unit source rate."""
    rate = np.zeros(g.n_ops)
    has_in = np.zeros(g.n_ops, bool)
    for _, j in g.edges:
        has_in[j] = True
    rate[~has_in] = 1.0
    for i, j in sorted(g.edges, key=lambda e: e[0]):
        rate[j] += rate[i] * g.selectivity[i]
    return rate


def network_movement(g: Graph, idx: np.ndarray, w: np.ndarray) -> np.ndarray:
    """(P,) unweighted data moved between distinct devices (§3.1, [26])."""
    w = np.asarray(w, np.float64)
    rate = rates(g)
    total = np.zeros(idx.shape[0])
    for i, j in g.edges:
        same = idx[:, i, :, None] == idx[:, j, None, :]
        local = (w[:, i, :, None] * w[:, j, None, :] * same).sum((-1, -2))
        pair = w[:, i].sum(-1) * w[:, j].sum(-1) - local
        total += rate[i] * g.selectivity[i] * g.out_bytes[i] * pair
    return total


def worst(grid: np.ndarray) -> np.ndarray:
    """(P,) worst case over the scenarios of an (S, P) grid."""
    return np.asarray(grid, np.float64).max(axis=0)


def joint(lat: np.ndarray, dq_values: np.ndarray, beta: float):
    """(S, P, D) scores of every dq value, the (S, P) best and its index."""
    cube = lat[:, :, None] / (1.0 + beta * np.asarray(dq_values)[None, None])
    best = cube.argmin(axis=2)
    return cube, np.take_along_axis(cube, best[..., None], 2)[..., 0], best


def pareto_mask(values: np.ndarray) -> np.ndarray:
    """(P,) True where no other row dominates (all objectives minimized)."""
    v = np.asarray(values, np.float64)
    le = (v[None, :, :] <= v[:, None, :]).all(-1)    # [p, q]: q <= p
    lt = (v[None, :, :] < v[:, None, :]).any(-1)
    return ~(le & lt).any(axis=1)
