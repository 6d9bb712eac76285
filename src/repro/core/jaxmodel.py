"""Differentiable / vectorized JAX twin of the paper cost model.

Why a twin: the paper's optimization problems are NP-hard ILPs (§2.3.2);
practical instruments are heuristics.  Because the cost model is a chain of
matmuls + maxes, writing it in JAX gives us (a) a *projected-gradient*
placement optimizer via autodiff over a temperature-smoothed latency
(beyond-paper, see optimizers.py), and (b) vectorized batch scoring of
thousands of candidate placements at once (`vmap`) for the SA/greedy search
and the massive-parallelism scaling bench.

Hard mode (``temp=0``) matches :mod:`repro.core.costmodel` to float32
precision — asserted by property tests.

The graph structure is static Python; only ``x`` (and optionally the com
matrix) are traced, so every builder here returns a jit-compatible closure.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.devices import ExplicitFleet, RegionFleet
from repro.core.graph import OpGraph

__all__ = ["SmoothConfig", "make_latency_fn", "make_objective_fn",
           "make_edge_latencies_com_fn", "make_latency_com_fn",
           "make_edge_latencies_region_fn", "make_latency_region_fn",
           "region_a_off", "region_own", "region_terms",
           "region_terms_sparse", "region_times",
           "critical_path_dp"]


@dataclasses.dataclass(frozen=True)
class SmoothConfig:
    """temp=0 ⇒ hard max (paper-exact); temp>0 ⇒ logsumexp smoothing.
    link_eps smooths the enabledLinks indicator: nz(x) ≈ x/(x+eps)."""

    alpha: float = 0.0
    temp: float = 0.0
    link_eps: float = 1e-4


def _smax(v: jnp.ndarray, temp: float, axis=None) -> jnp.ndarray:
    if temp <= 0.0:
        return jnp.max(v, axis=axis)
    return temp * jax.nn.logsumexp(v / temp, axis=axis)


def _soft_nz(x: jnp.ndarray, eps: float, hard: bool) -> jnp.ndarray:
    if hard:
        return (x > 0).astype(x.dtype)
    return x / (x + eps)


def _edge_latency(x_i, x_j, s_i, com_times, cfg: SmoothConfig):
    per_u = x_i * s_i * com_times(x_j)
    base = _smax(per_u, cfg.temp)
    if cfg.alpha:
        nz_i = _soft_nz(x_i, cfg.link_eps, cfg.temp <= 0.0)
        nz_j = _soft_nz(x_j, cfg.link_eps, cfg.temp <= 0.0)
        links = nz_i.sum() * nz_j.sum() - (nz_i * nz_j).sum()
        base = base + cfg.alpha * links
    return base


def make_latency_fn(graph: OpGraph, fleet: ExplicitFleet | RegionFleet,
                    cfg: SmoothConfig = SmoothConfig()):
    """Returns jit'able ``lat(x) -> scalar`` for (n_ops, V) placements.

    The critical-path DP is unrolled over the (static) topo order; with
    temp>0 the max over parents is also smoothed so the whole objective is
    C¹ — suitable for jax.grad.
    """
    sel = [op.selectivity for op in graph.operators]

    if isinstance(fleet, RegionFleet):
        region = np.asarray(fleet.region, dtype=np.int64)
        inter = jnp.asarray(fleet.inter)
        d = jnp.asarray(fleet.degrade_or_ones())
        # factor the scenario BEFORE tracing: a gather of the fleet's
        # constants by region gets constant-folded per edge — minutes of
        # XLA time at 10⁵ devices
        own = region_own(inter, d, region)

        def com_times(x_j):
            mass, w = region_terms(x_j, d, own, region, fleet.n_regions,
                                   fleet.self_cost)
            return region_times(mass, w, inter, d, region)
    else:
        com = jnp.asarray(fleet.com_cost)

        def com_times(x_j):
            return com @ x_j

    def lat(x: jnp.ndarray) -> jnp.ndarray:
        elat = {}
        for e, (i, j) in enumerate(graph.edges):
            elat[e] = _edge_latency(x[i], x[j], sel[i], com_times, cfg)
        dist: dict[int, jnp.ndarray] = {}
        zero = jnp.asarray(0.0, dtype=x.dtype)
        for i in graph.topo_order:
            incoming = [dist[ip] + elat[e] for ip, e in graph.in_edges(i)]
            if incoming:
                dist[i] = _smax(jnp.stack(incoming), cfg.temp, axis=0)
            else:
                dist[i] = zero
        sinks = [dist[s] for s in graph.sinks]
        return _smax(jnp.stack(sinks), cfg.temp, axis=0) if sinks else zero

    return lat


def make_objective_fn(graph: OpGraph, fleet: ExplicitFleet | RegionFleet,
                      beta: float, cfg: SmoothConfig = SmoothConfig()):
    """``obj(x, dq_fraction) -> F`` (paper eq. 8), differentiable in both."""
    lat = make_latency_fn(graph, fleet, cfg)

    def obj(x: jnp.ndarray, dq_fraction: jnp.ndarray) -> jnp.ndarray:
        return lat(x) / (1.0 + beta * dq_fraction)

    return obj


# -- batched what-if APIs (the com matrix itself is traced) -------------------
#
# make_latency_fn closes over ONE fleet; the scenario-simulation subsystem
# (repro.sim) instead scores placements against *families* of fleets, so the
# communication matrix must be an argument: vmap over (x, com) pairs scores a
# (scenario × placement) grid in one dispatch.  Edge math is vectorized over
# E (gather endpoint rows, one einsum, one row-max) rather than unrolled
# per-edge — that is what the Pallas kernel in kernels/edge_latency.py fuses.

def _edge_arrays(graph: OpGraph):
    src = np.array([i for i, _ in graph.edges], dtype=np.int64)
    dst = np.array([j for _, j in graph.edges], dtype=np.int64)
    sel = np.array([graph.operators[i].selectivity for i, _ in graph.edges])
    return src, dst, sel


def make_edge_latencies_com_fn(graph: OpGraph, cfg: SmoothConfig = SmoothConfig(),
                               nz_eps: float = 0.0):
    """Returns ``elat(x, com) -> (E,)`` with both placement AND com traced.

    Hard-max only (this is the what-if scorer, not the gradient path);
    matches :func:`repro.core.costmodel.edge_latencies` on an ExplicitFleet
    with ``com_cost == com``.  ``nz_eps`` mirrors CostConfig.nz_eps for the
    enabledLinks indicator.
    """
    src, dst, sel = _edge_arrays(graph)
    src_j = jnp.asarray(src)
    dst_j = jnp.asarray(dst)
    sel_j = jnp.asarray(sel)
    alpha = cfg.alpha

    def elat(x: jnp.ndarray, com: jnp.ndarray) -> jnp.ndarray:
        x_i = x[src_j] * sel_j[:, None]           # (E, V)
        x_j = x[dst_j]                            # (E, V)
        t = jnp.einsum("uv,ev->eu", com, x_j)     # (E, V)
        out = jnp.max(x_i * t, axis=1)            # (E,)
        if alpha:
            nz = (x > nz_eps).astype(x.dtype)  # hard indicator, paper-exact
            counts = nz.sum(axis=1)               # (n_ops,)
            both = (nz[src_j] * nz[dst_j]).sum(axis=1)
            out = out + alpha * (counts[src_j] * counts[dst_j] - both)
        return out

    return elat


def critical_path_dp(graph: OpGraph, elat: jnp.ndarray) -> jnp.ndarray:
    """(..., E) edge latencies → (...,) critical-path latency.

    The DP unrolls over the static topo order with whatever leading batch
    shape ``elat`` carries — the single implementation shared by the scalar
    com-fn below and the batched evaluator (repro.sim.batched), so the
    oracle-matching max/DP semantics live in exactly one place.
    """
    zero = jnp.zeros(elat.shape[:-1], dtype=elat.dtype)
    dist: dict[int, jnp.ndarray] = {}
    for i in graph.topo_order:
        incoming = [dist[ip] + elat[..., e] for ip, e in graph.in_edges(i)]
        dist[i] = jnp.max(jnp.stack(incoming), axis=0) if incoming else zero
    sinks = graph.sinks
    return jnp.max(jnp.stack([dist[s] for s in sinks]), axis=0) \
        if sinks else zero


def make_latency_com_fn(graph: OpGraph, cfg: SmoothConfig = SmoothConfig(),
                        nz_eps: float = 0.0):
    """Returns ``lat(x, com) -> scalar``: critical-path DP over the traced
    com matrix.  vmap/jit-compatible twin of costmodel.latency for scenario
    batching (repro.sim.batched vmaps it)."""
    elat_fn = make_edge_latencies_com_fn(graph, cfg, nz_eps)

    def lat(x: jnp.ndarray, com: jnp.ndarray) -> jnp.ndarray:
        return critical_path_dp(graph, elat_fn(x, com))

    return lat


# -- structured (RegionFleet) batched APIs ------------------------------------
#
# The dense com-traced twins above need the (V, V) matrix as an operand —
# fine for scenario batches of modest V, hopeless at the 10⁵-device fleets
# the paper targets.  These twins price transfers through region space: the
# *region assignment* is static (a what-if family shares the fleet layout)
# while the (R, R) inter matrix and (V,) per-device degrade multipliers are
# traced — so vmapping over (inter, degrade) pairs scores a whole
# RegionFleetFamily without ever materializing an (S, V, V) tensor.  Per
# destination row x_j, with dj = d·x_j and mass_r = Σ_{v ∈ region r} dj_v,
#
#   t_u = d_u · Σ_{r ≠ r_u} inter[r_u, r] · mass_r          (mass @ a_off)
#       + d_u · inter[r_u, r_u] · loo_u  +  self_cost · x_{j,u}      (w)
#   loo_u = Σ_{v ∈ r_u, v ≠ u} dj_v
#
# i.e. O(V·R + R²) work and O(V) memory per row — linear in V.  A device's
# transfer to itself never enters a float32 sum: ``loo`` is the region's
# mass less u's own term only where that term is at most about half of it
# (the difference then keeps at least half the mass, so its relative error
# stays a few ulps), and otherwise the region's sum over the devices that
# do not dominate it.  A region has at most one dominant device, so that
# second sum leaves out exactly u's term.  Pricing u's self-pair into
# ``mass @ A`` and subtracting it afterwards instead cancels catastrophically
# where one heavily degraded device holds both ends of an edge.

#: a device whose term exceeds this share of its region's mass is priced
#: from the rest of the region; just above ½, so that rounding in a float32
#: region sum can never flag two devices of one region
DOMINANT_SHARE = 0.5 + 2.0 ** -9


def region_own(inter: jnp.ndarray, degrade: jnp.ndarray, region_ix):
    """(V,) ``degrade_u · inter[r_u, r_u]``: what one unit of ``d·x_j`` on
    another device of u's region costs u.  vmap over (inter, degrade)
    pairs for a whole family."""
    return degrade * jnp.diagonal(inter)[region_ix]


def region_a_off(inter: jnp.ndarray, degrade: jnp.ndarray, region_ix):
    """(R, V) ``a_off[r, u] = degrade_u · inter[r_u, r]`` for r ≠ r_u, 0 in
    u's own region: the factor of ``t = mass @ a_off + w`` for a kernel
    that contracts the masses itself."""
    n_regions = inter.shape[0]
    own_col = jnp.arange(n_regions)[:, None] == jnp.asarray(region_ix)[None, :]
    return jnp.where(own_col, 0.0, degrade[None, :] * inter.T[:, region_ix])


def region_terms(x_j: jnp.ndarray, degrade: jnp.ndarray, own: jnp.ndarray,
                 region_ix, n_regions: int, self_cost: float,
                 segment_sum=None, per_device=None):
    """The placement-dependent terms of ``t = mass @ a_off + w`` for rows
    ``x_j`` (..., V) ≥ 0 against one scenario (``own`` from
    :func:`region_own`): the region masses (..., R) and ``w`` (..., V),
    each device's own-region transfer over the OTHER devices of its region
    plus its transfer to itself (module comment above).  The caller may
    give its route's ``segment_sum`` (..., V) → (..., R), the sum over
    each region's devices (default a scatter-add), and ``per_device``
    (..., R) → (..., V), each device's entry of its region (default a
    gather); each must treat every row on its own, whatever rows ride with
    it, and ``per_device`` must be exact."""
    if segment_sum is None:
        def segment_sum(v):
            out = jnp.zeros(v.shape[:-1] + (n_regions,), v.dtype)
            return out.at[..., region_ix].add(v)
    if per_device is None:
        def per_device(m):
            return m[..., region_ix]
    dj = degrade * x_j
    mass = segment_sum(dj)
    mass_u = per_device(mass)
    dom = dj > DOMINANT_SHARE * mass_u
    rest = segment_sum(jnp.where(dom, 0.0, dj))
    loo = jnp.where(dom, per_device(rest), mass_u - dj)
    return mass, own * loo + self_cost * x_j


#: the most slots :func:`region_terms_sparse` takes: each is selected into
#: ``w`` elementwise, O(k) a device; wider rows take :func:`region_terms`
SELECT_SLOTS = 16


def _slot_sum(v: jnp.ndarray, reg: jnp.ndarray, n_regions: int):
    """(..., k) slot entries summed by their region ``reg`` (..., k) into
    (..., R), slot after slot: a row's sums are the same bits whatever
    rows ride with it.  A slot of region ``n_regions`` adds nothing."""
    hit = jnp.where(reg[..., None] == jnp.arange(n_regions), v[..., None],
                    0.0)                                   # (..., k, R)
    return jax.lax.fori_loop(
        0, v.shape[-1], lambda s, acc: acc + hit[..., s, :],
        jnp.zeros(v.shape[:-1] + (n_regions,), v.dtype), unroll=8)


def region_terms_sparse(idx: jnp.ndarray, val: jnp.ndarray,
                        degrade: jnp.ndarray, own: jnp.ndarray, region_ix,
                        n_regions: int, self_cost: float, per_device=None):
    """:func:`region_terms` of rows given as their nonzeros: ``idx``
    (..., k) device indices, ``V`` in an unused slot, and ``val`` (..., k)
    the entries.  The sums run over the k slots, so only ``w`` (..., V) is
    dense: off the support ``loo_u`` is the whole region mass, so
    ``w = own · per_device(mass)`` there, and the support's entries are
    then set by the formula of :func:`region_terms`.  Bitwise the dense
    terms where no two support devices of a row share a region (elsewhere
    a region's sum adds its terms in another order).  ``per_device`` as
    for :func:`region_terms`; at most ``SELECT_SLOTS`` slots."""
    V, k = own.shape[-1], idx.shape[-1]
    if k > SELECT_SLOTS:
        raise ValueError(f"{k} slots: the sparse region terms take at most "
                         f"{SELECT_SLOTS}")
    if per_device is None:
        def per_device(m):
            return m[..., region_ix]
    take = partial(jnp.take, mode="fill")
    reg = take(jnp.asarray(region_ix), idx, fill_value=n_regions)
    dj = take(degrade, idx, fill_value=0) * val
    mass = _slot_sum(dj, reg, n_regions)

    def at_slots(m):
        return jnp.take_along_axis(m, reg, axis=-1, mode="fill",
                                   fill_value=0)

    mass_u = at_slots(mass)
    dom = dj > DOMINANT_SHARE * mass_u
    rest = _slot_sum(jnp.where(dom, 0.0, dj), reg, n_regions)
    loo = jnp.where(dom, at_slots(rest), mass_u - dj)
    support = take(own, idx, fill_value=0) * loo + self_cost * val
    w = own * per_device(mass)
    # each slot's entry selected in by its device index: elementwise, so
    # XLA fuses it into the matmul's one write of w (a scatter relays w
    # out to a linear layout and back on a TPU)
    device = jnp.arange(V, dtype=idx.dtype)
    for s in range(k):
        w = jnp.where(device == idx[..., s, None], support[..., s, None], w)
    return mass, w


def region_times(mass: jnp.ndarray, w: jnp.ndarray, inter: jnp.ndarray,
                 degrade: jnp.ndarray, region_ix) -> jnp.ndarray:
    """``t = mass @ a_off + w`` (..., V) in XLA, each row on its own: the
    other regions' share as an (R, R) sum per row, gathered per device.  A
    row's times are then the same bits whatever batch it rides in, which a
    (rows, R) @ (R, V) matmul, blocked by its row count, does not give."""
    n_regions = inter.shape[0]
    inter_off = inter * (1.0 - jnp.eye(n_regions, dtype=inter.dtype))
    y = (mass[..., None, :] * inter_off).sum(-1)   # y_q = Σ_{r≠q} inter·mass
    return degrade * y[..., region_ix] + w


def make_edge_latencies_region_fn(graph: OpGraph, region: np.ndarray,
                                  n_regions: int, self_cost: float = 0.0,
                                  cfg: SmoothConfig = SmoothConfig(),
                                  nz_eps: float = 0.0):
    """Returns ``elat(x, inter, degrade, sparse=None) -> (E,)`` — the
    structured twin of :func:`make_edge_latencies_com_fn`.

    ``region``/``n_regions``/``self_cost`` are static family structure;
    ``inter`` (R, R) and ``degrade`` (V,) are traced per-scenario state;
    ``sparse``, where given, is x's nonzeros ``(idx, val)`` (n_ops, k),
    and the region terms come from them (:func:`region_terms_sparse`).
    Hard-max only; matches the numpy oracle on the equivalent RegionFleet.
    """
    src, dst, sel = _edge_arrays(graph)
    src_j = jnp.asarray(src)
    dst_j = jnp.asarray(dst)
    sel_j = jnp.asarray(sel)
    region_ix = jnp.asarray(np.asarray(region, dtype=np.int64))
    alpha = cfg.alpha

    def elat(x: jnp.ndarray, inter: jnp.ndarray, degrade: jnp.ndarray,
             sparse=None) -> jnp.ndarray:
        degrade = degrade.astype(x.dtype)
        # every edge into operator j shares j's terms: price per operator
        # (n_ops, V), then gather per edge
        with jax.named_scope("region.terms"):
            own = region_own(inter.astype(x.dtype), degrade, region_ix)
            if sparse is None:
                mass, w = region_terms(x, degrade, own, region_ix,
                                       n_regions, self_cost)
            else:
                mass, w = region_terms_sparse(*sparse, degrade, own,
                                              region_ix, n_regions,
                                              self_cost)
            t = region_times(mass, w, inter.astype(x.dtype), degrade,
                             region_ix)[dst_j]           # (E, V)
        out = jnp.max(x[src_j] * sel_j[:, None] * t, axis=1)   # (E,)
        if alpha:
            nz = (x > nz_eps).astype(x.dtype)
            counts = nz.sum(axis=1)
            both = (nz[src_j] * nz[dst_j]).sum(axis=1)
            out = out + alpha * (counts[src_j] * counts[dst_j] - both)
        return out

    return elat


def make_latency_region_fn(graph: OpGraph, region: np.ndarray,
                           n_regions: int, self_cost: float = 0.0,
                           cfg: SmoothConfig = SmoothConfig(),
                           nz_eps: float = 0.0):
    """Returns ``lat(x, inter, degrade) -> scalar``: critical-path DP over
    the structured edge latencies (vmap/jit twin of costmodel.latency on a
    RegionFleet, with the per-scenario state traced)."""
    elat_fn = make_edge_latencies_region_fn(graph, region, n_regions,
                                            self_cost, cfg, nz_eps)

    def lat(x: jnp.ndarray, inter: jnp.ndarray,
            degrade: jnp.ndarray) -> jnp.ndarray:
        return critical_path_dp(graph, elat_fn(x, inter, degrade))

    return lat


@partial(jax.jit, static_argnames=("n_candidates",))
def _noop(n_candidates: int):  # pragma: no cover - keep jax imported hot
    return n_candidates
