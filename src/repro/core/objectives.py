"""Unified multi-objective cost layer (paper §3.1).

The paper's objectives beyond latency — network movement and device
occupancy — compose "trivially through simple sum functions".  Historically
each objective in this repo was hand-kept in up to three twins (scalar numpy
oracle, dense com-traced jnp, structured segment-sum); this module makes the
triple a *spec*: one :class:`ObjectiveSpec` per objective exposing

  * ``scalar``            — the float64 numpy oracle (tests / exact rescoring),
  * ``build_dense``       — a jnp twin over a traced dense ``(V, V)`` com
    matrix: ``f(x, com, speed) -> raw``,
  * ``build_structured``  — a jnp twin over RegionFleetFamily state:
    ``f(x, inter, degrade, speed) -> raw`` (never materializes ``(V, V)``),
  * ``finish``            — the post-map normalization ``(raw, dq, beta) ->
    value`` (only latency-F uses it: paper eq. 8's ``/(1 + β·dq)``), applied
    OUTSIDE the scenario ``lax.map`` so per-scenario dq broadcasts over the
    whole (S, P) grid.

An :class:`ObjectiveSet` bundles specs with scalarization weights; the
batched evaluator (``repro.sim.batched.BatchedEvaluator.score_grid``)
consumes it to return every objective's (S, P) grid plus the weighted
scalarization in ONE jitted dispatch, and the discrete optimizers
(``PlacementProblem.score``, ``robust_placement``,
``scenario_robust_search``) score the same weighted sum through the scalar
oracles — so min–max robust search can trade worst-case F against WAN bytes
moved or device occupancy with one knob.

Objective registry (weights are the caller's unit exchange rates — the
objectives are NOT normalized to a common scale here;
``repro.search.decision.ObjectiveScales`` fits per-objective scales from a
sampled grid when dimensionless weights are wanted, and
``repro.search.decision.pareto_front`` extracts the non-dominated set the
per-objective grids already hold):

  ``latency_f``             paper eq. 8: critical-path latency / (1 + β·dq)
  ``network_movement``      §3.1 [26]: Σ_edges rate·s·bytes·Σ_{u≠v} x_iu·x_jv
  ``network_movement_cost`` the same sum, each (u, v) pair weighted by
                            comCost_{u,v} (WAN bytes priced by link cost)
  ``occupancy_max``         max_u of §3.1 device occupancy (bottleneck box)
  ``occupancy_imbalance``   max_u − mean_u occupancy (load skew, 0 ⇒ even)

Structured network movement collapses to a degrade-weighted region-mass
quadratic form over pairs of distinct regions — ``mᵀ·inter_off·m`` with
``m_r = Σ_{v∈r} degrade_v·x_v`` — plus the same-region pairs from
``jaxmodel.region_terms``, which never adds the u == v pair: O(R² + V) per
edge.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.costmodel import (CostConfig, device_occupancy, latency,
                                  network_movement, objective_F)
from repro.core.devices import ExplicitFleet, RegionFleet
from repro.core.graph import OpGraph
from repro.core.jaxmodel import (SmoothConfig, _edge_arrays,
                                 make_latency_com_fn, make_latency_region_fn,
                                 region_own, region_terms)

__all__ = [
    "ObjectiveSpec",
    "ObjectiveSet",
    "ObjectiveGrids",
    "OBJECTIVES",
    "as_objective_set",
]

Fleet = ExplicitFleet | RegionFleet


# -- static per-graph vectors shared by the twins -----------------------------

def _edge_movement_weights(graph: OpGraph) -> np.ndarray:
    """(E,) rate_i·s_i·bytes_i for every edge (i → j) — the §3.1 movement
    weight of one unit of (u ≠ v) placement mass product."""
    rates = graph.cumulative_rates()
    return np.array([rates[i] * graph.operators[i].selectivity
                     * graph.operators[i].out_bytes
                     for i, _ in graph.edges], dtype=np.float64)


def _op_loads(graph: OpGraph) -> np.ndarray:
    """(n_ops,) work_i·rate_i — occupancy seconds per unit placement mass
    at unit speed."""
    rates = graph.cumulative_rates()
    return np.array([op.work * rates[i]
                     for i, op in enumerate(graph.operators)],
                    dtype=np.float64)


def _smooth_cfg(cfg: CostConfig) -> SmoothConfig:
    return SmoothConfig(alpha=cfg.alpha)


# -- latency-F ----------------------------------------------------------------

def _scalar_latency_f(graph, fleet, x, dq, beta, cfg):
    return objective_F(latency(graph, fleet, x, cfg), dq, beta)


def _dense_latency_f(graph: OpGraph, cfg: CostConfig):
    lat = make_latency_com_fn(graph, _smooth_cfg(cfg), nz_eps=cfg.nz_eps)

    def f(x, com, speed):
        return lat(x, com)

    return f


def _structured_latency_f(graph, region, n_regions, self_cost, cfg):
    lat = make_latency_region_fn(graph, region, n_regions, self_cost,
                                 _smooth_cfg(cfg), nz_eps=cfg.nz_eps)

    def f(x, inter, degrade, speed):
        return lat(x, inter, degrade)

    return f


def _finish_latency_f(raw, dq, beta):
    """Paper eq. 8 applied grid-wide: dq broadcasts (scalar or (S, 1))."""
    return raw / (1.0 + beta * dq)


# -- network movement ---------------------------------------------------------

def _make_scalar_movement(weighted: bool):
    def scalar(graph, fleet, x, dq, beta, cfg):
        return network_movement(graph, fleet, x, weight_by_cost=weighted)

    return scalar


def _make_dense_movement(weighted: bool):
    def build(graph: OpGraph, cfg: CostConfig):
        src, dst, _ = _edge_arrays(graph)
        src_j, dst_j = jnp.asarray(src), jnp.asarray(dst)
        w = jnp.asarray(_edge_movement_weights(graph))

        def f(x, com, speed):
            if not weighted:
                tot = x.sum(1)                                 # (n_ops,)
                pair = tot[src_j] * tot[dst_j] \
                    - (x[src_j] * x[dst_j]).sum(1)
                return w.astype(x.dtype) @ pair
            # price each OPERATOR's inbound transfer once (n·V² instead of
            # E·V²), then gather per edge
            op_t = x @ com.T.astype(x.dtype)                   # (n_ops, V)
            diag = jnp.diagonal(com).astype(x.dtype)
            x_i = x[src_j]                                     # (E, V)
            pair = (x_i * op_t[dst_j]).sum(1) \
                - (x_i * diag[None, :] * x[dst_j]).sum(1)
            return w.astype(x.dtype) @ pair

        return f

    return build


def _make_structured_movement(weighted: bool):
    def build(graph, region, n_regions, self_cost, cfg):
        src, dst, _ = _edge_arrays(graph)
        src_j, dst_j = jnp.asarray(src), jnp.asarray(dst)
        w = jnp.asarray(_edge_movement_weights(graph))
        region_ix = jnp.asarray(np.asarray(region, dtype=np.int64))

        def f(x, inter, degrade, speed):
            if not weighted:
                tot = x.sum(1)                                 # (n_ops,)
                pair = tot[src_j] * tot[dst_j] \
                    - (x[src_j] * x[dst_j]).sum(1)
                return w.astype(x.dtype) @ pair
            # Σ_{u≠v} d_u·d_v·inter[r_u,r_v]·x_iu·x_jv: the cross-region
            # pairs as a region-mass quadratic form with inter's diagonal
            # zeroed, the same-region pairs as x_i against region_terms' w
            # (at self_cost 0: each device's own-region transfer over the
            # OTHER devices of its region), so no u == v pair is added and
            # subtracted again; O(R² + V) per edge with the (n_ops, ·)
            # terms computed ONCE per placement
            d = degrade.astype(x.dtype)
            inter = inter.astype(x.dtype)
            mass, w_in = region_terms(x, d, region_own(inter, d, region_ix),
                                      region_ix, n_regions, 0.0)
            inter_off = inter * (1.0 - jnp.eye(n_regions, dtype=x.dtype))
            quad = jnp.einsum("er,rq,eq->e", mass[src_j], inter_off,
                              mass[dst_j])
            pair = quad + (x[src_j] * w_in[dst_j]).sum(1)
            return w.astype(x.dtype) @ pair

        return f

    return build


# -- device occupancy ---------------------------------------------------------

def _make_scalar_occupancy(reduce: str):
    def scalar(graph, fleet, x, dq, beta, cfg):
        occ = device_occupancy(graph, fleet, x)
        if reduce == "max":
            return float(occ.max(initial=0.0))
        return float(occ.max(initial=0.0) - (occ.mean() if occ.size else 0.0))

    return scalar


def _occ_reduce(occ: jnp.ndarray, reduce: str) -> jnp.ndarray:
    if reduce == "max":
        return jnp.max(occ)
    return jnp.max(occ) - jnp.mean(occ)


def _make_dense_occupancy(reduce: str):
    def build(graph: OpGraph, cfg: CostConfig):
        wk = jnp.asarray(_op_loads(graph))

        def f(x, com, speed):
            occ = (wk.astype(x.dtype)[:, None] * x).sum(0) \
                / speed.astype(x.dtype)
            return _occ_reduce(occ, reduce)

        return f

    return build


def _make_structured_occupancy(reduce: str):
    def build(graph, region, n_regions, self_cost, cfg):
        wk = jnp.asarray(_op_loads(graph))

        def f(x, inter, degrade, speed):
            # effective speed = speed / degrade (a straggler's compute slows
            # by the same multiplier that prices its links) — degrade is the
            # traced per-scenario operand, speed the nominal vector
            occ = (wk.astype(x.dtype)[:, None] * x).sum(0) \
                * degrade.astype(x.dtype) / speed.astype(x.dtype)
            return _occ_reduce(occ, reduce)

        return f

    return build


# -- the spec and its registry ------------------------------------------------

def _finish_identity(raw, dq, beta):
    return raw


@dataclasses.dataclass(frozen=True)
class ObjectiveSpec:
    """One §3.1 objective, all representations in one place.

    ``scalar(graph, fleet, x, dq, beta, cfg) -> float`` returns the FINISHED
    value (dq/beta applied where relevant); the batched builders return the
    raw per-instance value and ``finish(raw, dq, beta)`` is applied outside
    the scenario map (dq arrives (S, 1), broadcasting over the (S, P) grid).
    """

    name: str
    scalar: Callable
    build_dense: Callable      # (graph, cfg) -> f(x, com, speed) -> raw
    build_structured: Callable  # (graph, region, R, self_cost, cfg) -> f(x, inter, degrade, speed) -> raw
    finish: Callable = _finish_identity


OBJECTIVES: dict[str, ObjectiveSpec] = {
    spec.name: spec
    for spec in (
        ObjectiveSpec(
            name="latency_f",
            scalar=_scalar_latency_f,
            build_dense=_dense_latency_f,
            build_structured=_structured_latency_f,
            finish=_finish_latency_f,
        ),
        ObjectiveSpec(
            name="network_movement",
            scalar=_make_scalar_movement(False),
            build_dense=_make_dense_movement(False),
            build_structured=_make_structured_movement(False),
        ),
        ObjectiveSpec(
            name="network_movement_cost",
            scalar=_make_scalar_movement(True),
            build_dense=_make_dense_movement(True),
            build_structured=_make_structured_movement(True),
        ),
        ObjectiveSpec(
            name="occupancy_max",
            scalar=_make_scalar_occupancy("max"),
            build_dense=_make_dense_occupancy("max"),
            build_structured=_make_structured_occupancy("max"),
        ),
        ObjectiveSpec(
            name="occupancy_imbalance",
            scalar=_make_scalar_occupancy("imbalance"),
            build_dense=_make_dense_occupancy("imbalance"),
            build_structured=_make_structured_occupancy("imbalance"),
        ),
    )
}


@dataclasses.dataclass(frozen=True)
class ObjectiveSet:
    """Objectives plus scalarization weights — the multi-objective knob.

    Hashable (the batched evaluator caches one jitted grid function per
    set).  Weights are exchange rates between objective units, NOT a convex
    combination: ``scalarized = Σ_k w_k · objective_k``.
    """

    specs: tuple[ObjectiveSpec, ...]
    weights: tuple[float, ...]

    def __post_init__(self):
        if len(self.specs) != len(self.weights):
            raise ValueError(
                f"{len(self.specs)} objectives but {len(self.weights)} weights")
        if not self.specs:
            raise ValueError("ObjectiveSet needs at least one objective")
        names = [s.name for s in self.specs]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate objectives: {names}")

    @classmethod
    def of(cls, *objectives: str | ObjectiveSpec,
           weights: Iterable[float] | None = None) -> "ObjectiveSet":
        """``ObjectiveSet.of("latency_f", "network_movement")`` — names
        resolve through :data:`OBJECTIVES`; weights default to all-ones."""
        specs = tuple(o if isinstance(o, ObjectiveSpec) else _lookup(o)
                      for o in objectives)
        w = tuple(1.0 for _ in specs) if weights is None \
            else tuple(float(v) for v in weights)
        return cls(specs=specs, weights=w)

    @classmethod
    def from_weights(cls, **name_weights: float) -> "ObjectiveSet":
        """``ObjectiveSet.from_weights(latency_f=1.0, network_movement=0.01)``."""
        return cls.of(*name_weights.keys(),
                      weights=tuple(name_weights.values()))

    @property
    def names(self) -> tuple[str, ...]:
        return tuple(s.name for s in self.specs)

    # -- scalar (float64 oracle) path ----------------------------------------
    def scalar_values(self, graph: OpGraph, fleet: Fleet, x: np.ndarray,
                      dq: float = 0.0, beta: float = 0.0,
                      cfg: CostConfig = CostConfig()) -> dict[str, float]:
        """Every objective's exact value for one placement on one fleet."""
        return {s.name: float(s.scalar(graph, fleet, x, dq, beta, cfg))
                for s in self.specs}

    def scalar_total(self, graph: OpGraph, fleet: Fleet, x: np.ndarray,
                     dq: float = 0.0, beta: float = 0.0,
                     cfg: CostConfig = CostConfig()) -> float:
        """The weighted scalarization through the exact oracles — what
        ``PlacementProblem.score`` minimizes and min–max robust search
        re-scores winners with."""
        vals = self.scalar_values(graph, fleet, x, dq, beta, cfg)
        return float(sum(w * vals[s.name]
                         for s, w in zip(self.specs, self.weights)))


def _lookup(name: str) -> ObjectiveSpec:
    try:
        return OBJECTIVES[name]
    except KeyError:
        raise ValueError(f"unknown objective {name!r}; "
                         f"choose from {sorted(OBJECTIVES)}") from None


def as_objective_set(objectives) -> ObjectiveSet:
    """Coerce user input — an ObjectiveSet, one name/spec, or a sequence of
    names/specs (unit weights) — into an ObjectiveSet."""
    if isinstance(objectives, ObjectiveSet):
        return objectives
    if isinstance(objectives, (str, ObjectiveSpec)):
        return ObjectiveSet.of(objectives)
    return ObjectiveSet.of(*objectives)


@dataclasses.dataclass
class ObjectiveGrids:
    """score_grid's multi-objective result: per-objective (S, P) grids and
    their weighted scalarization, all from ONE jitted dispatch."""

    names: tuple[str, ...]
    grids: dict[str, jax.Array]
    scalarized: jax.Array
    weights: tuple[float, ...]

    def __getitem__(self, name: str) -> jax.Array:
        return self.grids[name]
