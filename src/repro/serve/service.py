"""`WhatIfService` — the single-host, multi-tenant what-if serving loop.

Query lifecycle (see serve/README.md for the diagram)::

    submit ── normalize ── bucket ── admit ─┬─ Rejected (typed, priced)
                                            └─ queue[CoalesceKey]
    step ──── coalesce queues ── pad to 2^k ── ONE dispatch per chunk,
                 per-column dq/β ── stream ResultChunks ── final QueryResult

Tenants :meth:`~WhatIfService.register_fleet` scenario packs once (content
digest → equal fleets coalesce across tenants), then
:meth:`~WhatIfService.submit` heterogeneous queries — score a placement
batch, rank candidates (weighted or ε-constraint), extract a Pareto front,
co-optimize placement × dq.  The service normalizes each query to its
:class:`~repro.serve.bucketing.CoalesceKey`, prices it against the p99
budget (:mod:`repro.serve.admission`), and merges admitted rows across
tenants into power-of-two-padded super-batches so the whole mixed stream
runs through a handful of compiled executables — resolved via the
process-wide :mod:`repro.sim.execache`, with recompiles attributed per
dispatch through :func:`repro.obs.jaxhooks.snapshot`.

Each chunk is ONE ``score_grid`` dispatch whose dq is per cell (S, rows)
and β per row, so every query's own dq/β — scalar or per-scenario —
finishes on the device with the ops a direct ``score_grid`` call runs:
served scores and per-objective grids are bitwise that call's on every
backend.  Joint rows ride the same dispatch raw (dq = 0, β = 0); their dq
grid is closed-form (:func:`repro.search.decision.split_dq_term`) and
expands on the host.  Results stream back per tenant
(:meth:`~WhatIfService.poll`) as chunks complete: long queries yield
:class:`ResultChunk` partials before the final :class:`QueryResult`.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.costmodel import CostConfig
from repro.core.devices import RegionFleetFamily
from repro.core.graph import OpGraph
from repro.core.objectives import ObjectiveGrids, ObjectiveSet, \
    as_objective_set
from repro.obs import jaxhooks
from repro.search.decision import (dq_caps_mask, epsilon_constraint,
                                   joint_dq_scores, pareto_front,
                                   robust_select, split_dq_term)
from repro.serve.admission import (AdmissionConfig, Admitted, Degraded,
                                   DispatchPricer, Rejected, decide)
from repro.serve.bucketing import (CoalesceKey, fleet_digest, next_pow2,
                                   pad_rows)
from repro.serve.cache import ServeStats
from repro.sim.batched import (BatchedEvaluator, SparsePlacements,
                               sparse_placements, takes_sparse_terms)

__all__ = ["WhatIfQuery", "QueryTicket", "ResultChunk", "QueryResult",
           "WhatIfService"]

_KINDS = ("score", "rank", "pareto", "joint")


def _query_ids(live) -> str:
    """The chunk's query ids joined by spaces: the profiler keeps a string
    argument only up to its first comma."""
    return " ".join(str(p.query_id) for p, _ in live)


@dataclasses.dataclass(frozen=True)
class WhatIfQuery:
    """One tenant question over a batch of candidate placements.

    ``kind`` picks the post-processing applied to the (scenario, candidate)
    grids the shared dispatch produces — the dispatch itself is identical:

    * ``"score"``  — the finished (S, P) score grid(s), dq/β applied;
    * ``"rank"``   — top-``top_k`` candidates by worst-case score; with
      ``eps_caps`` the ranking is ε-constraint (minimize one objective
      subject to caps on the others) instead of the weighted sum;
    * ``"pareto"`` — the non-dominated front over the key's objectives
      (requires the fleet to be registered with an ObjectiveSet);
    * ``"joint"``  — placement × dq co-optimization over ``dq_values``
      (optionally DQCoupling-masked), min–max selected.

    ``dq`` may be a scalar or per-scenario (S,) column; dq/β never affect
    which super-batch the query coalesces into.
    """

    kind: str
    placements: np.ndarray
    dq: float | np.ndarray = 0.0
    beta: float = 0.0
    # rank
    top_k: int = 1
    minimize: str | None = None
    eps_caps: dict | None = None
    # pareto / rank reduction across scenarios
    scenario: int | str = "worst"
    # joint
    dq_values: np.ndarray | None = None
    coupling: object | None = None

    def __post_init__(self):
        if self.kind not in _KINDS:
            raise ValueError(f"kind must be one of {_KINDS}, "
                             f"got {self.kind!r}")
        x = np.asarray(self.placements, dtype=np.float32)
        if x.ndim != 3:
            raise ValueError(f"placements must be (P, n_ops, V), "
                             f"got {x.shape}")
        object.__setattr__(self, "placements", x)
        if self.kind == "joint" and self.dq_values is None:
            raise ValueError("joint queries need dq_values")
        if self.eps_caps and self.minimize is None:
            raise ValueError("eps_caps needs minimize=<objective name>")


@dataclasses.dataclass(frozen=True)
class QueryTicket:
    """submit()'s receipt: the query id results will carry, plus the typed
    admission verdict (Admitted or Degraded — Rejected never queues)."""

    query_id: int
    tenant: str
    admission: Admitted | Degraded
    rows: int            # candidate rows actually queued (post-degrade)
    dq_steps: int | None


@dataclasses.dataclass(frozen=True)
class ResultChunk:
    """A streamed partial: finished scores for ``rows`` candidates starting
    at ``offset`` within the (possibly degraded) query batch."""

    query_id: int
    tenant: str
    offset: int
    scores: np.ndarray   # (S, rows) finished scalar scores

    @property
    def rows(self) -> int:
        return int(self.scores.shape[1])


@dataclasses.dataclass(frozen=True)
class QueryResult:
    """The final answer for one query (follows its ResultChunks).

    ``scores`` is always the finished (S, P) scalar grid over the rows the
    query actually dispatched.  Kind-specific extras: ``top``/``worst``/
    ``best`` (rank), ``front`` (pareto), ``best``/``dq_idx`` (joint),
    ``infeasible`` (ε-constraint with no candidate under the caps).
    ``grids`` carries the finished per-objective (S, P) grids when the
    fleet was registered with an ObjectiveSet."""

    query_id: int
    tenant: str
    kind: str
    scores: np.ndarray
    grids: dict | None = None
    degraded: Degraded | None = None
    top: np.ndarray | None = None
    worst: np.ndarray | None = None
    front: object | None = None
    best: int | None = None
    dq_idx: np.ndarray | None = None
    infeasible: bool = False


@dataclasses.dataclass
class _Fleet:
    pack: object                    # (S, V, V) array or RegionFleetFamily
    key: CoalesceKey
    n_scenarios: int
    n_devices: int
    objectives: ObjectiveSet | None
    pricer: DispatchPricer


@dataclasses.dataclass
class _Pending:
    """An admitted query waiting in (or mid-flight through) its key's
    queue, accumulating its host-side grid columns chunk by chunk (finished,
    or raw for joint queries)."""

    query_id: int
    tenant: str
    query: WhatIfQuery
    placements: np.ndarray          # post-degrade (P, n_ops, V)
    dq_values: np.ndarray | None    # post-degrade
    predicted_s: float
    degraded: Degraded | None
    sparse: SparsePlacements | None = None   # region fleets' upload form
    done_rows: int = 0
    score_cols: list = dataclasses.field(default_factory=list)
    grid_cols: dict = dataclasses.field(default_factory=dict)

    @property
    def rows(self) -> int:
        return int(self.placements.shape[0])


class WhatIfService:
    """Single-host what-if serving for one operator graph.

    One service instance per :class:`~repro.core.graph.OpGraph` /
    :class:`~repro.core.costmodel.CostConfig`; any number of logical
    tenants and registered scenario fleets.  ``max_chunk_rows`` bounds a
    single dispatch (super-batches larger than it stream in chunks, which
    is what makes results *streamable* and keeps the compiled-shape set
    small); admission is configured via :class:`AdmissionConfig`.
    """

    def __init__(self, graph: OpGraph, cfg: CostConfig = CostConfig(),
                 admission: AdmissionConfig = AdmissionConfig(),
                 max_chunk_rows: int = 1024):
        if max_chunk_rows < 1 or max_chunk_rows & (max_chunk_rows - 1):
            raise ValueError(f"max_chunk_rows must be a power of two, "
                             f"got {max_chunk_rows}")
        self.graph = graph
        self.cfg = cfg
        self.admission = admission
        self.max_chunk_rows = max_chunk_rows
        # evaluator resolves through the process-wide executable cache:
        # services, search engines and scripts over equal graphs share one
        self._ev = BatchedEvaluator.shared(graph, cfg)
        self._fleets: dict[str, _Fleet] = {}
        self._packs: dict[str, object] = {}    # dense, by content digest
        self._queues: dict[CoalesceKey, list[_Pending]] = {}
        self._mail: dict[str, list] = {}
        self._next_id = 0
        self.stats = ServeStats()

    # -- registration --------------------------------------------------------
    def register_fleet(self, tenant: str, pack,
                       objectives: ObjectiveSet | None = None) -> str:
        """Register a scenario pack (dense (S, V, V) stack or
        RegionFleetFamily) and get back its fleet id — a content digest,
        so two tenants registering equal fleets receive the SAME id and
        their queries coalesce into one dispatch stream.  ``objectives``
        fixes the multi-objective set for queries against this fleet
        (None = single-objective latency-F).

        A dense pack is held on the device as float32, one copy per
        content digest however many objective sets register it, so no
        dispatch copies it again; the copy lands here, not in the first
        dispatch.  A ``jax.Array`` pack stays where it is.  Where the
        device has no room left, the float32 host pack is held instead
        and every dispatch copies it."""
        obj_set = as_objective_set(objectives) if objectives is not None \
            else None
        with obs.span("serve.register") as sp:
            structured = isinstance(pack, RegionFleetFamily)
            if not structured and not isinstance(pack, jax.Array):
                pack = np.asarray(pack, dtype=np.float32)
            digest = fleet_digest(pack)
            fid = digest if obj_set is None \
                else f"{digest}:{abs(hash(obj_set)):x}"
            moved = 0
            if fid not in self._fleets:
                if structured:
                    S, V = pack.n_scenarios, int(pack.degrade.shape[1])
                    R = pack.n_regions
                else:
                    pack, moved = self._resident(digest, pack)
                    S, V = int(pack.shape[0]), int(pack.shape[1])
                    R = None
                key = CoalesceKey(fleet=fid, objectives=obj_set)
                self._fleets[fid] = _Fleet(
                    pack=pack, key=key, n_scenarios=S, n_devices=V,
                    objectives=obj_set,
                    pricer=DispatchPricer(len(self.graph.edges), V, R,
                                          cfg=self.admission))
            if obs.enabled():
                sp.set(device_bytes=moved)
        return fid

    def _resident(self, digest: str, pack) -> tuple[object, int]:
        """The pack held for this content digest, made on first sight, and
        the bytes this call put on the device: a float32 device copy,
        waited for, or the float32 host pack where the device is out of
        memory."""
        held = self._packs.get(digest)
        if held is not None:
            return held, 0
        try:
            held = jnp.asarray(pack, jnp.float32).block_until_ready()
        except jax.errors.JaxRuntimeError as err:
            if "RESOURCE_EXHAUSTED" not in str(err):
                raise
            held = np.asarray(pack, np.float32)
        self._packs[digest] = held
        on_device = isinstance(held, jax.Array) and held is not pack
        return held, held.nbytes if on_device else 0

    # -- submission (normalize → bucket → admit → queue) ---------------------
    def submit(self, tenant: str, fleet_id: str,
               query: WhatIfQuery) -> QueryTicket | Rejected:
        """Price the query and either queue it (returning a
        :class:`QueryTicket` whose ``admission`` says what, if anything,
        was degraded) or refuse it with a typed :class:`Rejected` —
        nothing is dispatched here; call :meth:`step` / :meth:`drain`."""
        fleet = self._fleets[fleet_id]
        q = query
        if q.kind == "pareto" and fleet.objectives is None:
            raise ValueError("pareto queries need the fleet registered "
                             "with an ObjectiveSet")
        if (q.eps_caps or q.minimize is not None) \
                and fleet.objectives is None:
            raise ValueError("ε-constraint ranking (minimize/eps_caps) "
                             "needs the fleet registered with an "
                             "ObjectiveSet")
        if q.placements.shape[2] != fleet.n_devices:
            raise ValueError(
                f"placements have V={q.placements.shape[2]} devices; "
                f"fleet {fleet_id} has V={fleet.n_devices}")
        dq_steps = None if q.dq_values is None else len(
            np.atleast_1d(q.dq_values))
        rows = q.placements.shape[0]
        with obs.span("serve.admit", rows=rows):
            verdict = decide(
                fleet.pricer, fleet.n_scenarios, next_pow2(rows),
                backlog_s=self._backlog_s(), cfg=self.admission,
                dq_steps=dq_steps,
                bucket_stats=self.stats.peek_bucket(next_pow2(rows)))
            if isinstance(verdict, Rejected):
                self.stats.rejected += 1
                return verdict
            placements, dq_vals, degraded = q.placements, q.dq_values, None
            if isinstance(verdict, Degraded):
                degraded = verdict
                self.stats.degraded += 1
                placements = placements[:verdict.keep_rows]
                if verdict.dq_steps is not None and dq_steps is not None \
                        and verdict.dq_steps < dq_steps:
                    grid = np.atleast_1d(
                        np.asarray(q.dq_values, dtype=np.float64))
                    pick = np.linspace(0, len(grid) - 1,
                                       verdict.dq_steps).round().astype(int)
                    dq_vals = grid[np.unique(pick)]
            else:
                self.stats.admitted += 1
        qid = self._next_id
        self._next_id += 1
        # a region fleet's rows are dense in V ~ 1e5 with a few devices
        # each: they cross to the device as their nonzeros.  A dense
        # fleet's V is bounded by its S·V² pack, so its rows stay dense
        sparse = (sparse_placements(placements)
                  if isinstance(fleet.pack, RegionFleetFamily) else None)
        with obs.span("serve.enqueue", query_id=qid):
            self._queues.setdefault(fleet.key, []).append(_Pending(
                query_id=qid, tenant=tenant, query=q, placements=placements,
                dq_values=dq_vals, predicted_s=verdict.predicted_s,
                degraded=degraded, sparse=sparse))
        return QueryTicket(query_id=qid, tenant=tenant, admission=verdict,
                           rows=placements.shape[0],
                           dq_steps=None if dq_vals is None
                           else len(np.atleast_1d(dq_vals)))

    def _backlog_s(self) -> float:
        return sum(p.predicted_s for queue in self._queues.values()
                   for p in queue)

    # -- the serving loop (coalesce → pad → dispatch → stream) ---------------
    def step(self) -> int:
        """Serve the oldest non-empty coalesce queue: merge its pending
        queries into a super-batch, dispatch it in ≤max_chunk_rows
        power-of-two chunks, stream each chunk's finished scores to tenant
        mailboxes, finalize completed queries.  Returns the number of
        queries completed (0 = nothing pending)."""
        key = next((k for k, queue in self._queues.items() if queue), None)
        if key is None:
            return 0
        queue = self._queues.pop(key)
        fleet = next(f for f in self._fleets.values() if f.key == key)
        # a region fleet's rows that take the sparse region terms and rows
        # that take the dense ones (takes_sparse_terms) are super-batches
        # of their own, so a query's chunks take the route a direct
        # score_grid of its rows takes
        return sum(self._serve_batch(fleet, batch) for batch in (
            [p for p in queue if takes_sparse_terms(p.sparse)],
            [p for p in queue if not takes_sparse_terms(p.sparse)])
            if batch)

    def _serve_batch(self, fleet: _Fleet, queue: list[_Pending]) -> int:
        """``step`` for one super-batch of one queue's queries."""
        # (query, rows) spans inside the super-batch, in queue order
        spans, off = [], 0
        for p in queue:
            spans.append((p, off, off + p.rows))
            off += p.rows
        done = 0
        with obs.span("serve.step", queries=len(queue), rows=off):
            for start in range(0, off, self.max_chunk_rows):
                end = min(start + self.max_chunk_rows, off)
                bucket = next_pow2(end - start)
                # each live query's columns within the chunk
                live = [(p, slice(max(a, start) - start,
                                  min(b, end) - start))
                        for p, a, b in spans if a < end and b > start]
                with obs.span("serve.chunk", bucket=bucket, rows=end - start,
                              query_ids=(_query_ids(live) if obs.enabled()
                                         else "")):
                    scores, grids = self._dispatch(fleet, bucket, live)
                    with obs.span("serve.finalize", queries=len(live)):
                        done += self._deliver(fleet, scores, grids, live)
        return done

    def _deliver(self, fleet: _Fleet, scores: np.ndarray, grids: dict,
                 live: list[tuple[_Pending, slice]]) -> int:
        """Hand each live query its chunk columns: a ResultChunk to its
        tenant's mailbox, then the final QueryResult once its last rows
        are in.  Returns the number of queries completed."""
        done = 0
        for p, sl in live:
            p.score_cols.append(scores[:, sl])
            for name, g in grids.items():
                p.grid_cols.setdefault(name, []).append(g[:, sl])
            if p.query.kind != "joint":
                self._mail.setdefault(p.tenant, []).append(ResultChunk(
                    query_id=p.query_id, tenant=p.tenant,
                    offset=p.done_rows, scores=scores[:, sl]))
            p.done_rows += sl.stop - sl.start
            if p.done_rows == p.rows:
                self._mail.setdefault(p.tenant, []).append(
                    self._finalize(fleet, p))
                done += 1
        return done

    def drain(self) -> int:
        """step() until every queue is empty; returns queries completed."""
        total = 0
        while True:
            n = self.step()
            if n == 0 and not any(self._queues.values()):
                return total
            total += n

    def poll(self, tenant: str) -> list:
        """Drain the tenant's mailbox: ResultChunk / QueryResult, in
        completion order."""
        return self._mail.pop(tenant, [])

    # -- dispatch + accounting ----------------------------------------------
    def _assemble(self, fleet: _Fleet, bucket: int,
                  live: list[tuple[_Pending, slice]]):
        """The chunk's rows padded to ``bucket`` (as SparsePlacements
        where every live query has them), each query's columns carrying
        its own dq/β (joint and padding columns 0).  A region fleet's
        dense rows go to the device here: score_grid then keeps the dense
        region terms its queries were batched for, and does not scan a
        chunk of them for nonzeros again."""
        with obs.span("serve.assemble", bucket=bucket):
            if all(p.sparse is not None for p, _ in live):
                padded = SparsePlacements.concat(
                    [p.sparse.rows(p.done_rows,
                                   p.done_rows + sl.stop - sl.start)
                     for p, sl in live], bucket)
            else:
                padded = pad_rows(np.concatenate(
                    [p.placements[p.done_rows:
                                  p.done_rows + sl.stop - sl.start]
                     for p, sl in live]), bucket)
                if isinstance(fleet.pack, RegionFleetFamily):
                    padded = jnp.asarray(padded)
            dq = np.zeros((fleet.n_scenarios, bucket), np.float32)
            beta = np.zeros(bucket, np.float32)
            for p, sl in live:
                if p.query.kind != "joint":
                    dq[:, sl] = np.broadcast_to(np.asarray(
                        p.query.dq, np.float32),
                        (fleet.n_scenarios,))[:, None]
                    beta[sl] = p.query.beta
        return padded, dq, beta

    def _dispatch(self, fleet: _Fleet, bucket: int,
                  live: list[tuple[_Pending, slice]]):
        """ONE score_grid call over the chunk's padded rows, each query's
        columns finished with its own dq/β; returns host-side float32
        (scalar scores, per-objective grids), both (S, bucket)."""
        padded, dq, beta = self._assemble(fleet, bucket, live)
        n_rows = sum(sl.stop - sl.start for _, sl in live)
        snap = jaxhooks.snapshot()
        t0 = time.perf_counter()
        out = self._ev.score_grid(padded, fleet.pack, dq=dq, beta=beta,
                                  objectives=fleet.objectives)
        # one host transfer for the whole chunk
        with obs.span("serve.fetch") as sp:
            if isinstance(out, ObjectiveGrids):
                scores, grids = jax.device_get((out.scalarized,
                                                dict(out.grids)))
            else:
                scores, grids = jax.device_get(out), {}
            if obs.enabled():
                sp.set(d2h_bytes=sum(a.nbytes for a in
                                     jax.tree.leaves((scores, grids))))
        seconds = time.perf_counter() - t0
        recompiles, compile_s = snap.delta()
        self.stats.bucket(bucket).observe(
            seconds, n_rows=n_rows, n_padded=bucket, n_queries=len(live),
            n_recompiles=recompiles, compile_s=compile_s)
        # calibrate the pricer on warm execution time only — compile cost
        # is a one-off the executable cache amortizes away, not a per-
        # dispatch price
        fleet.pricer.observe(fleet.n_scenarios, bucket,
                             max(seconds - compile_s, 0.0))
        return np.asarray(scores, np.float32), {
            n: np.asarray(g, np.float32) for n, g in grids.items()}

    # -- per-kind finalization ----------------------------------------------
    def _finalize(self, fleet: _Fleet, p: _Pending) -> QueryResult:
        q = p.query
        scores = np.concatenate(p.score_cols, axis=1)     # (S, P) float32
        grids = {n: np.concatenate(cols, axis=1)
                 for n, cols in p.grid_cols.items()} or None
        if q.kind == "joint":
            raw = scores if grids is None else ObjectiveGrids(
                names=fleet.objectives.names, grids=grids,
                scalarized=scores, weights=fleet.objectives.weights)
            lat, rest, w_lat = split_dq_term(raw)
            feas = dq_caps_mask(p.placements, p.dq_values, q.coupling)
            scores, dq_idx = joint_dq_scores(
                lat.astype(np.float32), np.atleast_1d(p.dq_values), q.beta,
                rest=rest.astype(np.float32),
                w_lat=w_lat, feasible=feas)
            best, worst = robust_select(scores)
            return QueryResult(
                query_id=p.query_id, tenant=p.tenant, kind=q.kind,
                scores=scores, grids=grids, degraded=p.degraded,
                best=best, dq_idx=dq_idx, worst=worst,
                infeasible=bool(np.isinf(worst[best])))
        if q.kind == "score":
            return QueryResult(query_id=p.query_id, tenant=p.tenant,
                               kind=q.kind, scores=scores, grids=grids,
                               degraded=p.degraded)
        if q.kind == "pareto":
            og = ObjectiveGrids(names=fleet.objectives.names, grids=grids,
                                scalarized=scores,
                                weights=fleet.objectives.weights)
            front = pareto_front(og, scenario=q.scenario)
            return QueryResult(query_id=p.query_id, tenant=p.tenant,
                               kind=q.kind, scores=scores, grids=grids,
                               degraded=p.degraded, front=front)
        # rank
        if q.eps_caps or q.minimize is not None:
            og = ObjectiveGrids(names=fleet.objectives.names, grids=grids,
                                scalarized=scores,
                                weights=fleet.objectives.weights)
            best, masked = epsilon_constraint(
                og, q.minimize, q.eps_caps, scenario=q.scenario)
            order = np.argsort(masked, kind="stable")[:q.top_k]
            return QueryResult(
                query_id=p.query_id, tenant=p.tenant, kind=q.kind,
                scores=scores, grids=grids, degraded=p.degraded,
                top=order, worst=masked, best=int(best),
                infeasible=bool(np.isinf(masked[best])))
        best, worst = robust_select(scores)
        order = np.argsort(worst, kind="stable")[:q.top_k]
        return QueryResult(query_id=p.query_id, tenant=p.tenant,
                           kind=q.kind, scores=scores, grids=grids,
                           degraded=p.degraded, top=order, worst=worst,
                           best=int(best))
