"""Batched what-if evaluation: score (scenario × placement) grids in one
device dispatch.

The scalar path (repro.core.costmodel) walks edges in Python — fine for one
placement on one fleet, hopeless for scoring thousands of candidates over a
scenario family.  This module is the vectorized twin, with TWO scenario
representations behind one API:

  * **dense** — the communication matrix is an *argument* (one (V, V) per
    scenario), so a single jitted function evaluates every
    (fleet, placement) pair of a grid; on the hot path the bilinear-max runs
    in the Pallas kernel ``repro.kernels.edge_latency``.  Memory is
    O(S·V²) — fine to a few thousand devices.
  * **structured** — a :class:`repro.core.devices.RegionFleetFamily`
    (shared region layout, (S, R, R) inter matrices, (S, V) degrade
    multipliers) is scored via the segment-sum formulation
    (``make_edge_latencies_region_fn``): O(S·(R² + V)) scenario state and
    O(P·E·V) working set, never an (S, V, V) tensor — what-if grids reach
    the 10⁵-device fleets the scalar ``make_latency_fn`` already prices.

``BatchedEvaluator`` dispatches on the type of the ``com`` argument:
a stacked array (from :func:`pack_fleets`) takes the dense path, a
``RegionFleetFamily`` (from :func:`pack_region_fleets`) the structured one —
same ``edge_latencies`` / ``latency`` / ``objective`` / ``score_grid``
surface either way.  The critical-path DP is shared: it unrolls over the
static topo order with (B,) vector states, so it vectorizes over the whole
batch for free.

The float64 numpy oracle stays the correctness reference: property tests
assert agreement to ≤1e-5 relative on random graphs/fleets/placements,
including RegionFleet(Family) and ``alpha > 0`` enabledLinks cases.

This module is the scoring backend of the search subsystem: the batched
searchers (``repro.search``) chunk their candidate batches through
``score_grid`` — single-problem searches pack the fleet as a singleton
scenario — and the decision layer consumes the per-objective grids for
Pareto extraction and normalization (see ``src/repro/search/README.md``).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.analysis import sanitize
from repro.core.costmodel import CostConfig
from repro.sim.execache import ExecutableCache, executable_cache, graph_key
from repro.core.devices import ExplicitFleet, RegionFleet, RegionFleetFamily
from repro.core.graph import OpGraph
from repro.core.jaxmodel import (SmoothConfig, _edge_arrays,
                                 critical_path_dp, region_a_off, region_own,
                                 SELECT_SLOTS, region_terms,
                                 region_terms_sparse,
                                 make_edge_latencies_com_fn,
                                 make_edge_latencies_region_fn)
from repro.core.objectives import (ObjectiveGrids, ObjectiveSet,
                                   as_objective_set)
from repro.kernels import dispatch
from repro.kernels.edge_latency import block_geometry, edge_list
from repro.kernels.region_sum import region_sum_pallas

__all__ = ["BatchedEvaluator", "SparsePlacements", "pack_fleets",
           "pack_placements", "pack_region_fleets", "pack_speeds",
           "sparse_placements", "takes_sparse_terms"]

# instance memo behind BatchedEvaluator.shared(): one evaluator per
# (graph content, cfg, dispatch route), so independent consumers (search
# engines, the serving layer, examples) converge on the same instance —
# and therefore the same compiled executables — instead of warming their
# own.  The compiled state itself lives in repro.sim.execache either way;
# this only spares re-deriving the static edge arrays.
_shared_evaluators = ExecutableCache(capacity=64, name="evaluators")

Fleet = ExplicitFleet | RegionFleet


def pack_fleets(fleets: list[Fleet], dtype=jnp.float32) -> jnp.ndarray:
    """(S, V, V) stacked com matrices — the DENSE scenario pack.

    Any fleet (RegionFleets included) is materialized, so this caps out at a
    few thousand devices; families of RegionFleets sharing a region layout
    should use :func:`pack_region_fleets` instead, which keeps the O(R² + V)
    structure all the way through ``score_grid``.
    """
    mats = [np.asarray(f.com_matrix(), dtype=np.float64) for f in fleets]
    shapes = {m.shape for m in mats}
    if len(shapes) != 1:
        raise ValueError(f"fleets disagree on device count: {sorted(shapes)}")
    return jnp.asarray(np.stack(mats), dtype=dtype)


def pack_region_fleets(fleets: list[RegionFleet]) -> RegionFleetFamily:
    """Pack RegionFleets sharing one region layout into the STRUCTURED
    scenario representation (no (S, V, V) materialization anywhere).

    Raises ValueError when the fleets don't stack structurally — fall back
    to :func:`pack_fleets` for heterogeneous-layout families.
    """
    if not all(isinstance(f, RegionFleet) for f in fleets):
        raise ValueError("pack_region_fleets needs RegionFleets; "
                         "use pack_fleets for mixed/dense fleets")
    return RegionFleetFamily.from_fleets(fleets)


def pack_placements(xs: list[np.ndarray], dtype=jnp.float32) -> jnp.ndarray:
    """(P, n_ops, V) stacked candidate placements."""
    return jnp.asarray(np.stack([np.asarray(x) for x in xs]), dtype=dtype)


@dataclasses.dataclass(frozen=True)
class SparsePlacements:
    """(P, n_ops, V) float32 placements held as the nonzero entries of each
    (row, operator): ``idx`` (P, n_ops, k) int32 device indices and ``val``
    (P, n_ops, k) float32 masses, unused slots at index ``n_devices``.  A
    row over a few devices of a 10⁵-device fleet is then ``8·k`` bytes an
    operator instead of ``4·V``; ``score_grid`` rebuilds the dense rows on
    the device, bit for bit, and on a region fleet prices the region terms
    from the nonzeros themselves."""

    idx: np.ndarray
    val: np.ndarray
    n_devices: int

    @property
    def shape(self) -> tuple[int, int, int]:
        return (*self.idx.shape[:2], self.n_devices)

    def rows(self, start: int, stop: int) -> "SparsePlacements":
        return SparsePlacements(self.idx[start:stop], self.val[start:stop],
                                self.n_devices)

    @staticmethod
    def concat(parts: list["SparsePlacements"],
               bucket: int) -> "SparsePlacements":
        """The parts' rows, slots padded to the widest part, and the last
        row repeated up to ``bucket`` rows (as ``serve.bucketing.pad_rows``
        pads dense rows)."""
        k = max(p.idx.shape[2] for p in parts)
        V = parts[0].n_devices
        idx = np.concatenate([np.pad(p.idx, ((0, 0), (0, 0),
                                             (0, k - p.idx.shape[2])),
                                     constant_values=V) for p in parts])
        val = np.concatenate([np.pad(p.val, ((0, 0), (0, 0),
                                             (0, k - p.val.shape[2])))
                              for p in parts])
        pad = bucket - idx.shape[0]
        if pad < 0:
            raise ValueError(f"batch of {idx.shape[0]} rows exceeds "
                             f"bucket {bucket}")
        if pad:
            idx = np.concatenate([idx, np.repeat(idx[-1:], pad, axis=0)])
            val = np.concatenate([val, np.repeat(val[-1:], pad, axis=0)])
        return SparsePlacements(idx, val, V)


_MIN_SLOTS = 8


def sparse_placements(x) -> SparsePlacements | None:
    """``x`` (P, n_ops, V) as :class:`SparsePlacements`, or None where it
    would save too little: more than ``V/16`` nonzeros in some (row,
    operator).  Slots are a power of two, at least ``_MIN_SLOTS``, so a
    traffic of similar rows reuses one compiled shape.  Entries are kept
    by their bits, so ``-0.0`` and NaN payloads come back as they were."""
    x = np.ascontiguousarray(x, np.float32)
    P, n, V = x.shape
    bits = x.view(np.uint32).reshape(-1)
    # scan for nonzero 512-word blocks first: a row over a few devices is
    # almost all zero blocks, and the block maxima run at memory speed
    blk = 512 if V % 512 == 0 else V
    words = bits.reshape(-1, blk)
    blocks = np.flatnonzero(words.max(axis=1))
    b, c = np.nonzero(words[blocks])
    flat = blocks[b] * blk + c
    group, dev = np.divmod(flat, V)
    count = np.bincount(group, minlength=P * n)
    k = max(_MIN_SLOTS, 1 << max(int(count.max(initial=0)) - 1,
                                 0).bit_length())
    if k > V // 16:
        return None
    slot = np.arange(flat.size) - np.repeat(np.cumsum(count) - count, count)
    idx = np.full((P * n, k), V, np.int32)
    val = np.zeros((P * n, k), np.float32)
    idx[group, slot] = dev
    val.view(np.uint32)[group, slot] = bits[flat]
    return SparsePlacements(idx.reshape(P, n, k), val.reshape(P, n, k), V)


def takes_sparse_terms(placements) -> bool:
    """Whether a region fleet's :meth:`BatchedEvaluator.score_grid` of
    ``placements`` prices the region terms from the rows' nonzeros: rows
    given as :class:`SparsePlacements` of at most ``SELECT_SLOTS`` (16)
    slots.  Other rows take the dense terms.  The one rule of every caller,
    the service's super-batches included."""
    return (isinstance(placements, SparsePlacements)
            and placements.idx.shape[2] <= SELECT_SLOTS)


@functools.partial(jax.jit, static_argnames="n_devices")
def _densify(idx: jnp.ndarray, val: jnp.ndarray,
             n_devices: int) -> jnp.ndarray:
    """The dense (P, n_ops, V) rows of a :class:`SparsePlacements` on the
    device; slots at index ``n_devices`` are dropped."""
    P, n, _ = idx.shape
    rows = jnp.arange(P, dtype=jnp.int32)[:, None, None]
    ops = jnp.arange(n, dtype=jnp.int32)[None, :, None]
    return jnp.zeros((P, n, n_devices), val.dtype).at[rows, ops, idx].set(
        val, mode="drop")


def pack_speeds(fleets: list[Fleet], dtype=jnp.float32) -> jnp.ndarray:
    """(S, V) stacked *effective* device speeds — the dense-path companion
    of :func:`pack_fleets` for the occupancy objectives (the com stack
    carries link state only; compute speed rides separately).  Structured
    families don't need this: a RegionFleetFamily carries its own speeds."""
    sp = [np.asarray(f.effective_speed(), dtype=np.float64) for f in fleets]
    shapes = {s.shape for s in sp}
    if len(shapes) != 1:
        raise ValueError(f"fleets disagree on device count: {sorted(shapes)}")
    return jnp.asarray(np.stack(sp), dtype=dtype)


def _region_ops(region_ix, n_regions: int, interpret: bool):
    """The Pallas route's ``segment_sum`` and ``per_device`` for
    :func:`repro.core.jaxmodel.region_terms`: the region sums in the Pallas
    region-sum kernel (a row's bits do not depend on its batch; a
    scatter-add over V costs about as much whatever its rows), the
    per-device gather as a matmul with the layout's one-hot, exact at
    HIGHEST (one product per output, by a one) and faster than the gather
    on a v5e."""
    onehot = (jnp.arange(n_regions)[:, None]
              == region_ix[None, :]).astype(jnp.float32)

    def segment_sum(v):
        return region_sum_pallas(v, onehot, interpret=interpret)

    def per_device(m):
        return jnp.matmul(m, onehot, precision=jax.lax.Precision.HIGHEST)

    return segment_sum, per_device


def _host_bytes(pairs) -> int:
    """Device bytes of the uploaded operands whose source was not already
    on the device: ``pairs`` holds (source, device array); a Python scalar
    left as it was (β) counts nothing."""
    return sum(int(d.nbytes) for s, d in pairs
               if isinstance(d, jax.Array) and not isinstance(s, jax.Array))


@dataclasses.dataclass
class _StructuredFns:
    """Jitted structured-path entry points for one family layout (lat_raw
    is the unjitted latency fn the multi-objective grid composes into its
    own jitted dispatch)."""

    elat: callable
    lat: callable
    obj: callable
    grid: callable
    lat_raw: callable


@dataclasses.dataclass
class BatchedEvaluator:
    """vmap/jit twin of edge_latencies / latency / objective_F for one graph.

    Batch conventions (x and the scenario batch must share the SAME leading
    batch size B, or the scenario batch is a singleton shared across B;
    score_grid forms the cross product itself).  ``com`` is either a dense
    (B, V, V) stack (pack_fleets) or a RegionFleetFamily (pack_region_fleets):

      edge_latencies(x (B,n,V), com)      -> (B, E)
      latency(x, com)                     -> (B,)
      objective(x, com, dq, beta)         -> (B,)
      score_grid(x (P,n,V), com [S scen]) -> (S, P)   — ONE dispatch

    The inner reduction takes the route :func:`repro.kernels.dispatch.route`
    gives at construction: on an accelerator the compiled Pallas kernels
    (dense bilinear-max or structured region-mass matmul), on the CPU the
    ``core/jaxmodel`` twin vmapped — the reference the CPU tests compare
    the interpreted kernels against.
    """

    graph: OpGraph
    cfg: CostConfig = CostConfig()

    def __post_init__(self):
        self._route = dispatch.route()
        src, dst, sel = _edge_arrays(self.graph)
        self._src = jnp.asarray(src)
        self._dst = jnp.asarray(dst)
        self._sel = jnp.asarray(sel, dtype=jnp.float32)
        self._edges = edge_list(src, dst, sel)
        if self.cfg.include_compute:
            raise NotImplementedError(
                "batched evaluator covers the paper-faithful model "
                "(communication dominates); compute extension is scalar-only")
        # single source of truth for the jnp edge math: vmap the com-traced
        # twin from core.jaxmodel (hard max; same alpha/nz_eps semantics)
        self._elat_single = make_edge_latencies_com_fn(
            self.graph, SmoothConfig(alpha=self.cfg.alpha),
            nz_eps=self.cfg.nz_eps)
        # every jitted entry point resolves through the PROCESS-WIDE
        # executable cache (repro.sim.execache), keyed by the evaluator's
        # semantic identity: two evaluators built over identical graphs and
        # configs share ONE jitted function object, so jax's compilation
        # cache hits instead of recompiling per instance.  The builder
        # closures bind this instance, which is safe exactly because the
        # key pins everything they read (graph content, cfg, route).
        ek = self._eval_key = (graph_key(self.graph), self.cfg, self._route)
        cache = executable_cache()
        self._jit_elat = cache.get_or_build(
            ("dense_elat", ek), lambda: jax.jit(self._elat_batched))
        self._jit_lat = cache.get_or_build(
            ("dense_lat", ek), lambda: jax.jit(self._lat_batched))
        self._jit_obj = cache.get_or_build(
            ("dense_obj", ek), lambda: jax.jit(self._obj_batched))
        self._jit_grid = cache.get_or_build(
            ("dense_grid", ek), lambda: jax.jit(self._grid))

    @classmethod
    def shared(cls, graph: OpGraph,
               cfg: CostConfig = CostConfig()) -> "BatchedEvaluator":
        """The process-shared evaluator for this (graph, cfg) on the current
        route — equal-content graphs map to the SAME instance, so every
        consumer (search engines, :mod:`repro.serve`, scripts) reuses one
        set of compiled executables instead of warming its own."""
        key = ("evaluator", graph_key(graph), cfg, dispatch.route())
        return _shared_evaluators.get_or_build(key, lambda: cls(graph, cfg))

    # -- dense batched math (all shapes carry a leading B) -------------------
    def _elat_batched(self, x: jnp.ndarray, com: jnp.ndarray) -> jnp.ndarray:
        """x (B, n, V) against com (B, V, V), or (1, V, V) = one shared
        scenario (the Pallas index map / vmap in_axes share it without
        replicating it in memory)."""
        if not self._route.pallas:
            if com.shape[0] == 1 and x.shape[0] != 1:
                return jax.vmap(self._elat_single, in_axes=(0, None))(
                    x, com[0])                             # (B, E)
            return jax.vmap(self._elat_single)(x, com)     # (B, E)
        x_i = x[:, self._src] * self._sel[None, :, None]   # (B, E, V)
        x_j = x[:, self._dst]                              # (B, E, V)
        out = dispatch.edge_latency(x_i, x_j, com)
        return out + self._links_term(x, out.dtype)

    def _links_term(self, x: jnp.ndarray, dtype) -> jnp.ndarray:
        """α·enabledLinks per edge, (B, E) — zero when alpha is off."""
        if not self.cfg.alpha:
            return jnp.zeros((), dtype)
        nz = (x > self.cfg.nz_eps).astype(dtype)
        counts = nz.sum(axis=-1)                           # (B, n_ops)
        both = (nz[:, self._src] * nz[:, self._dst]).sum(axis=-1)
        links = counts[:, self._src] * counts[:, self._dst] - both
        return self.cfg.alpha * links

    def _lat_batched(self, x: jnp.ndarray, com: jnp.ndarray) -> jnp.ndarray:
        return critical_path_dp(self.graph, self._elat_batched(x, com))

    def _obj_batched(self, x, com, dq, beta):
        return self._lat_batched(x, com) / (1.0 + beta * dq)

    def _grid(self, placements: jnp.ndarray, coms: jnp.ndarray,
              dq, beta) -> jnp.ndarray:
        # cross product WITHOUT materializing S·P operand copies: map over
        # scenarios, each scoring all P placements against one shared com
        # (at the ROADMAP's V=4096 targets a replicated com tensor would be
        # tens of GB).  lax.map keeps one trace; P stays the wide batch dim.
        lat = jax.lax.map(
            lambda com: self._lat_batched(placements, com[None]), coms)
        return self._finish_grid(lat, coms.shape[0], dq, beta)

    @staticmethod
    def _dq_cells(dq, S: int, dtype=jnp.float32) -> jnp.ndarray:
        """dq against an (S, P) grid: a scalar or per-scenario (S,) dq
        becomes an (S, 1) column, a per-cell (S, P) dq is used as is."""
        dq = jnp.asarray(dq, dtype)
        return dq if dq.ndim == 2 else jnp.broadcast_to(dq, (S,))[:, None]

    @staticmethod
    def _finish_grid(lat: jnp.ndarray, S: int, dq, beta) -> jnp.ndarray:
        """(S, P) latencies → objectives; dq scalar, (S,) or (S, P), β
        scalar or per-placement (P,)."""
        return lat / (1.0 + beta
                      * BatchedEvaluator._dq_cells(dq, S, lat.dtype))

    # -- structured batched math (RegionFleetFamily scenarios) ---------------
    @staticmethod
    def _layout_key(fam: RegionFleetFamily) -> tuple:
        return (fam.region.tobytes(), fam.n_regions, float(fam.self_cost))

    def _structured(self, fam: RegionFleetFamily) -> _StructuredFns:
        # structured fns are built lazily per family layout (the region
        # assignment is static structure, like the graph) and cached
        # process-wide: same layout + same evaluator identity ⇒ same
        # compiled executables, whichever instance asked first
        key = ("structured", self._eval_key, self._layout_key(fam))
        return executable_cache().get_or_build(
            key, lambda: self._build_structured(fam.region, fam.n_regions,
                                                fam.self_cost))

    def _build_structured(self, region: np.ndarray, n_regions: int,
                          self_cost: float) -> _StructuredFns:
        elat_single = make_edge_latencies_region_fn(
            self.graph, region, n_regions, self_cost,
            SmoothConfig(alpha=self.cfg.alpha), nz_eps=self.cfg.nz_eps)
        region_ix = jnp.asarray(np.asarray(region, dtype=np.int64))

        def elat_b(x, inter, degrade, sparse=None):
            """x (B, n, V); inter (Sb, R, R), degrade (Sb, V), Sb ∈ {1, B};
            ``sparse``, where given, x's nonzeros ``(idx, val)`` (B, n, k):
            the region terms then come from them, on either route."""
            if not self._route.pallas:
                if inter.shape[0] == 1 and x.shape[0] != 1:
                    return jax.vmap(elat_single,
                                    in_axes=(0, None, None, 0))(
                        x, inter[0], degrade[0], sparse)   # (B, E)
                return jax.vmap(elat_single)(x, inter, degrade, sparse)
            # Pallas route: the region terms per operator, by the same
            # jaxmodel.region_terms(_sparse) as the vmap route, on the MXU
            # (_region_ops); the edge kernel takes the per-operator rows
            # with the edge list and fuses t = mass @ a_off + w, the gather
            # to edges and the row max, so no (B, E, V) copy is written
            with jax.named_scope("region.terms"):
                segment_sum, per_device = _region_ops(
                    region_ix, n_regions, self._route.interpret)

                def terms(x1, sp1, i, d):
                    own = region_own(i, d, region_ix)
                    if sp1 is None:
                        mass, w = region_terms(x1, d, own, region_ix,
                                               n_regions, self_cost,
                                               segment_sum, per_device)
                    else:
                        mass, w = region_terms_sparse(
                            *sp1, d, own, region_ix, n_regions, self_cost,
                            per_device)
                    return region_a_off(i, d, region_ix), mass, w

                if inter.shape[0] == 1:
                    a, mass, w = terms(x, sparse, inter[0], degrade[0])
                    a = a[None]                         # (1, R, V)
                else:
                    a, mass, w = jax.vmap(
                        lambda x1, sp1, i, d: terms(
                            x1[None], jax.tree.map(lambda v: v[None], sp1),
                            i, d))(x, sparse, inter, degrade)
                    mass, w = mass[:, 0], w[:, 0]       # (B, n, ·)
            out = dispatch.edge_latency_structured(
                x.astype(jnp.float32), mass.astype(jnp.float32),
                a.astype(jnp.float32), w.astype(jnp.float32), self._edges)
            return out + self._links_term(x, out.dtype)

        def lat_b(x, inter, degrade, sparse=None):
            return critical_path_dp(self.graph,
                                    elat_b(x, inter, degrade, sparse))

        def obj_b(x, inter, degrade, dq, beta):
            return lat_b(x, inter, degrade) / (1.0 + beta * dq)

        def grid(placements, inters, degrades, dq, beta, sparse=None):
            # same no-replication cross product as the dense path: scenarios
            # stream through lax.map carrying only (R, R) + (V,) state each
            lat = jax.lax.map(
                lambda sc: lat_b(placements, sc[0][None], sc[1][None],
                                 sparse),
                (inters, degrades))
            return self._finish_grid(lat, inters.shape[0], dq, beta)

        return _StructuredFns(elat=jax.jit(elat_b), lat=jax.jit(lat_b),
                              obj=jax.jit(obj_b), grid=jax.jit(grid),
                              lat_raw=lat_b)

    @staticmethod
    def _family_args(fam: RegionFleetFamily) -> tuple[jnp.ndarray, jnp.ndarray]:
        return (jnp.asarray(fam.inter, jnp.float32),
                jnp.asarray(fam.degrade, jnp.float32))

    # -- multi-objective grids (ObjectiveSet, §3.1) --------------------------
    #
    # One jitted dispatch returns EVERY objective's (S, P) grid plus the
    # weighted scalarization, on both scenario representations.  The
    # scenario lax.map carries a pytree of per-objective (P,) rows, so the
    # no-replication cross product is unchanged; dq/beta normalization
    # (spec.finish — only latency-F uses it) and the weighted sum happen
    # after the map, where per-scenario dq broadcasts over the grid.
    #
    # latency_f is carved out by name: it rides the evaluator's own edge
    # machinery (which takes the route and is already built per graph)
    # instead of the spec's reference builders — a test pins the two routes
    # to the same oracle so they can't drift.

    def _finish_multi(self, obj_set: ObjectiveSet, raw: dict, S: int,
                      dq, beta, weights):
        dq_col = self._dq_cells(dq, S)
        grids = {s.name: s.finish(raw[s.name], dq_col, beta)
                 for s in obj_set.specs}
        stacked = jnp.stack([grids[n] for n in obj_set.names])  # (K, S, P)
        return grids, jnp.einsum("k,ksp->sp", weights, stacked)

    def _multi_dense(self, obj_set: ObjectiveSet):
        # multi-objective grid fns cache per (evaluator identity,
        # ObjectiveSet) — ObjectiveSet is hashable for exactly this
        def build():
            builders = {s.name: s.build_dense(self.graph, self.cfg)
                        for s in obj_set.specs if s.name != "latency_f"}
            has_lat = "latency_f" in obj_set.names

            def grid(placements, coms, speeds, dq, beta, weights):
                def per_scenario(op):
                    com, speed = op
                    outs = {}
                    if has_lat:
                        # the evaluator's own edge machinery (Pallas-aware)
                        outs["latency_f"] = self._lat_batched(
                            placements, com[None])
                    for name, f in builders.items():
                        outs[name] = jax.vmap(
                            lambda x: f(x, com, speed))(placements)
                    return outs                       # dict of (P,)
                raw = jax.lax.map(per_scenario, (coms, speeds))
                return self._finish_multi(obj_set, raw, coms.shape[0],
                                          dq, beta, weights)

            return jax.jit(grid)

        return executable_cache().get_or_build(
            ("multi_dense", self._eval_key, obj_set), build)

    def _multi_structured(self, fam: RegionFleetFamily,
                          obj_set: ObjectiveSet):
        def build():
            sf = self._structured(fam)
            builders = {s.name: s.build_structured(
                            self.graph, fam.region, fam.n_regions,
                            fam.self_cost, self.cfg)
                        for s in obj_set.specs if s.name != "latency_f"}
            has_lat = "latency_f" in obj_set.names

            def grid(placements, inters, degrades, speeds, dq, beta,
                     weights, sparse=None):
                def per_scenario(sc):
                    inter, degrade, speed = sc
                    outs = {}
                    if has_lat:
                        outs["latency_f"] = sf.lat_raw(
                            placements, inter[None], degrade[None], sparse)
                    for name, f in builders.items():
                        outs[name] = jax.vmap(
                            lambda x: f(x, inter, degrade, speed))(placements)
                    return outs
                raw = jax.lax.map(per_scenario, (inters, degrades, speeds))
                return self._finish_multi(obj_set, raw, inters.shape[0],
                                          dq, beta, weights)

            return jax.jit(grid)

        key = ("multi_structured", self._eval_key, self._layout_key(fam),
               obj_set)
        return executable_cache().get_or_build(key, build)

    @staticmethod
    def _validate_dq(dq, S: int, P: int | None = None) -> jnp.ndarray:
        """dq must be a scalar, EXACTLY (S,), or — with ``P`` given — one
        entry per cell (S, P).  A wrong-length vector that happens to
        broadcast (e.g. (1,) against S scenarios, or a (P,) slipped in as
        dq) would silently mis-scale the grid."""
        arr = np.asarray(dq, dtype=np.float64)
        if arr.ndim != 0 and arr.shape != (S,) and (
                P is None or arr.shape != (S, P)):
            cells = "" if P is None else f" or ({S}, {P}) per cell"
            raise ValueError(
                f"dq must be a scalar or shape ({S},) — one entry per "
                f"scenario{cells}; got shape {arr.shape} for S={S}")
        return jnp.asarray(arr, jnp.float32)

    @staticmethod
    def _validate_beta(beta, P: int):
        """β is a scalar or one entry per placement (P,)."""
        if np.ndim(beta) == 0:
            return float(beta)
        arr = np.asarray(beta, dtype=np.float64)
        if arr.shape != (P,):
            raise ValueError(f"beta must be a scalar or shape ({P},) — one "
                             f"entry per placement; got shape {arr.shape}")
        return jnp.asarray(arr, jnp.float32)

    def _dense_speeds(self, coms: jnp.ndarray, speed) -> jnp.ndarray:
        """Normalize the dense path's optional speed operand to (S, V):
        None ⇒ unit speeds (the paper-faithful 'communication dominates'
        default), (V,) shared, or (S, V) per-scenario (pack_speeds)."""
        S, V = coms.shape[0], coms.shape[1]
        if speed is None:
            return jnp.ones((S, V), jnp.float32)
        arr = np.asarray(speed, dtype=np.float64)
        if arr.shape == (V,):
            arr = np.broadcast_to(arr, (S, V))
        elif arr.shape != (S, V):
            raise ValueError(f"speed must be (V,) or (S, V) = ({S}, {V}); "
                             f"got shape {arr.shape}")
        return jnp.asarray(arr, jnp.float32)

    # -- public API ----------------------------------------------------------
    def edge_latencies(self, x, com) -> jnp.ndarray:
        """(B, E) edge latencies — batched edge_latencies()."""
        if isinstance(com, RegionFleetFamily):
            return self._structured(com).elat(jnp.asarray(x),
                                              *self._family_args(com))
        return self._jit_elat(jnp.asarray(x), jnp.asarray(com))

    def latency(self, x, com) -> jnp.ndarray:
        """(B,) critical-path latencies — batched latency()."""
        if isinstance(com, RegionFleetFamily):
            return self._structured(com).lat(jnp.asarray(x),
                                             *self._family_args(com))
        return self._jit_lat(jnp.asarray(x), jnp.asarray(com))

    def objective(self, x, com, dq=0.0, beta: float = 0.0) -> jnp.ndarray:
        """(B,) paper eq. (8) objectives — batched objective_F()."""
        if isinstance(com, RegionFleetFamily):
            return self._structured(com).obj(
                jnp.asarray(x), *self._family_args(com),
                jnp.asarray(dq, jnp.float32), float(beta))
        return self._jit_obj(jnp.asarray(x), jnp.asarray(com),
                             jnp.asarray(dq, jnp.float32), float(beta))

    def score_grid(self, placements, coms, dq=0.0, beta: float = 0.0,
                   objectives: ObjectiveSet | None = None, speed=None,
                   guard_output: bool = True):
        """Score every (scenario, placement) pair in one jitted dispatch.

        ``coms`` is a dense (S, V, V) stack or a RegionFleetFamily; ``dq``
        must be a scalar, exactly per-scenario (S,), or per-cell (S, P), and
        ``beta`` a scalar or per-placement (P,) — the per-cell forms let
        rows with different dq/β share one dispatch (:mod:`repro.serve`).

        ``objectives=None`` (default) returns the (S, P) latency-F grid —
        the single-objective fast path.  With an :class:`ObjectiveSet` (or
        anything ``as_objective_set`` accepts) the SAME dispatch computes
        every objective's (S, P) grid plus the weighted scalarization,
        returned as an :class:`ObjectiveGrids`; the structured path still
        never materializes an (S, V, V) array.  ``speed`` feeds the
        occupancy objectives on the dense path ((V,) or (S, V), see
        :func:`pack_speeds`; default unit speeds); structured families
        carry their own speeds, so ``speed`` must stay None there.

        On the structured path the region terms come from each row's
        nonzeros wherever the rows reach here as :class:`SparsePlacements`
        of at most 16 slots (:func:`takes_sparse_terms`); host rows are
        turned into them where :func:`sparse_placements` accepts them, so a
        direct call and a served call of the same rows take the same route.
        Device arrays and wider rows keep the dense terms.  The route is
        the whole call's: one row that ``sparse_placements`` refuses sends
        every row of the call to the dense terms, whose sums over two or
        more support devices of one region may differ in the last bits.
        """
        structured = isinstance(coms, RegionFleetFamily)
        if structured and not isinstance(placements,
                                         (SparsePlacements, jax.Array)):
            sparse = sparse_placements(placements)
            placements = placements if sparse is None else sparse
        S = coms.n_scenarios if structured else int(np.shape(coms)[0])
        shape = (placements.shape if isinstance(placements, SparsePlacements)
                 else np.shape(placements))
        P, V = int(shape[0]), int(shape[-1])
        path = "structured" if structured else "dense"
        multi = objectives is not None
        terms = "sparse" if takes_sparse_terms(placements) else "dense"
        reg = obs.registry()
        if reg.enabled:
            reg.counter("eval.score_grid.dispatches", path=path).add(1)
            reg.histogram("eval.score_grid.cells", lo=1.0).observe(S * P)
            if structured:
                reg.counter("eval.region_terms", route=terms).add(1)
        regions = {"R": coms.n_regions, "terms": terms} if structured else {}
        with obs.span("score_grid", S=S, P=P, path=path, multi=multi,
                      **regions) as sp:
            if reg.enabled and self._route.pallas:
                sp.set(kernel_rows=self._kernel_rows(structured, P, V))
            x, sparse, pack, dq_arr, beta = self._upload(
                placements, coms, dq, beta, S, P)
            san = sanitize.state()
            if san.enabled and san.domain_check:
                sanitize.check_dq(dq)  # host-side operand: no round-trip
            out = self._dispatch_grid(
                x, sparse if terms == "sparse" else None, coms, pack,
                dq_arr, beta, objectives, speed, structured)
            sp.sync(out.scalarized if isinstance(out, ObjectiveGrids)
                    else out)
        if guard_output and san.enabled and san.nan_check:
            # jax.Array caches its host copy, so downstream np conversions
            # don't pay this device→host transfer twice.  Callers that run
            # their own output guard on the host copy they already make
            # (BatchedProblem) pass guard_output=False — one guard per
            # value, at the layer that owns the transfer
            sanitize.check_finite(
                "score_grid",
                out.scalarized if isinstance(out, ObjectiveGrids) else out)
        return out

    def _kernel_rows(self, structured: bool, P: int, V: int) -> int:
        """The V-sized rows per placement row that the edge kernel reads:
        x and w per operator on the structured route, the two padded
        per-edge endpoint rows on the dense one."""
        if structured:
            return 2 * self.graph.n_ops
        E = self.graph.n_edges
        if not E:
            return 0
        from repro.kernels import autotune
        cfg = autotune.get_config("dense", P, E, V)
        return 2 * block_geometry("dense", E, V, None, cfg.block_edges,
                                  cfg.block_v).e_pad

    def _upload(self, placements, coms, dq, beta, S: int, P: int):
        """The grid's operands as device arrays: placements (rebuilt dense
        on the device from :class:`SparsePlacements`, whose ``(idx, val)``
        come back too, else None), the pack (a dense stack, or a family's
        ``(inter, degrade)``), dq and β.  In a
        ``grid.upload`` span that, with telemetry on, waits for the copies
        and counts ``h2d_bytes``: the device bytes of every operand that
        was not already a ``jax.Array``."""
        with obs.span("grid.upload") as up:
            structured = isinstance(coms, RegionFleetFamily)
            if isinstance(placements, SparsePlacements):
                idx = jnp.asarray(placements.idx)
                val = jnp.asarray(placements.val)
                x = _densify(idx, val, n_devices=placements.n_devices)
                sent = [(placements.idx, idx), (placements.val, val)]
                sparse = (idx, val)
            else:
                x = jnp.asarray(placements)
                sent = [(placements, x)]
                sparse = None
            if structured:
                pairs = list(zip((coms.inter, coms.degrade),
                                 self._family_args(coms)))
            else:
                pairs = [(coms, jnp.asarray(coms))]
            pairs += [(dq, self._validate_dq(dq, S, P)),
                      (beta, self._validate_beta(beta, P))]
            out = [d for _, d in pairs]
            if obs.enabled():
                up.sync([x, *out])
                up.set(h2d_bytes=_host_bytes(sent + pairs))
        pack = tuple(out[:2]) if structured else out[0]
        return x, sparse, pack, out[-2], out[-1]

    def _dispatch_grid(self, placements, sparse, coms, pack, dq_arr, beta,
                       objectives, speed, structured: bool):
        """``pack`` is ``coms`` on the device: the dense stack, or the
        family's ``(inter, degrade)``; ``sparse`` the placements' ``(idx,
        val)`` or None, read on the structured path alone."""
        if objectives is None:
            if speed is not None:
                raise ValueError("speed only feeds the occupancy objectives "
                                 "— pass objectives= to use it")
            if structured:
                return self._structured(coms).grid(placements, *pack,
                                                   dq_arr, beta, sparse)
            return self._jit_grid(placements, pack, dq_arr, beta)
        obj_set = as_objective_set(objectives)
        weights = jnp.asarray(obj_set.weights, jnp.float32)
        if structured:
            if speed is not None:
                raise ValueError("structured families carry their own "
                                 "speeds; leave speed=None")
            # nominal speeds: the structured occupancy twin applies the
            # traced degrade itself (effective = speed / degrade)
            speeds = jnp.asarray(coms.speed_or_ones(), jnp.float32)
            grids, scal = self._multi_structured(coms, obj_set)(
                placements, *pack, speeds, dq_arr, beta, weights, sparse)
        else:
            grids, scal = self._multi_dense(obj_set)(
                placements, pack, self._dense_speeds(pack, speed), dq_arr,
                beta, weights)
        # jit returns dict pytrees in sorted-key order; present the grids
        # in the set's declared objective order
        return ObjectiveGrids(names=obj_set.names,
                              grids={n: grids[n] for n in obj_set.names},
                              scalarized=scal, weights=obj_set.weights)
