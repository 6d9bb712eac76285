"""Layer 2 — batched scoring: every candidate batch goes through
``BatchedEvaluator.score_grid`` in O(dispatches), not O(candidates).

:class:`BatchedProblem` wraps one :class:`repro.core.optimizers.
PlacementProblem` and exposes ``score_batch(placements, dqs) -> (P, D)``
— the exact quantity ``prob.score`` returns, for a whole candidate batch
crossed with a whole DQ grid, from ONE jitted dispatch per chunk:

  * the fleet is packed once — an ExplicitFleet as a (1, V, V) dense com
    stack, a RegionFleet as an S=1 :class:`RegionFleetFamily` so 10⁵-device
    problems never materialize V×V;
  * the evaluator scores the batch at dq = 0 (raw latency / raw objective
    grids); DQ only enters through the analytic ``/(1 + β·dq)`` factor on
    the latency-F term, so the (P, D) joint grid is expanded AFTER the
    dispatch at numpy cost — ``dq_fraction`` becomes a free search
    dimension;
  * DQCoupling feasibility (caps(dq) = cap0 − dq·load ≥ column mass) is a
    vectorized (P, D) mask applied as +inf, mirroring ``prob.score``'s
    infeasible-⇒-inf convention;
  * multi-objective problems split the scalarization into the latency-F
    term (dq-dependent) and the rest (dq-independent), both from the same
    fused ``ObjectiveSet`` dispatch.

Scoring is float32 on the batched path (the evaluator's precision); the
searchers re-score their winners through the float64 oracle before
reporting, so returned objectives match the scalar loop to ≤1e-5 relative.

Problems with ``cfg.include_compute`` fall back to a scalar ``prob.score``
loop — the batched evaluator covers the paper-faithful model only — so
every searcher keeps working on compute-extension problems (e.g. the
StreamingEngine's re-optimization path), just without the batching win.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.analysis import sanitize
from repro.core.devices import ExplicitFleet, RegionFleet, RegionFleetFamily
from repro.core.optimizers import PlacementProblem
from repro.search.decision import dq_caps_mask, split_dq_term
from repro.sim.batched import BatchedEvaluator, pack_placements

__all__ = ["BatchedProblem"]


def _bucket(n: int) -> int:
    """Next power of two — candidate batches are padded up to buckets so
    varying neighborhood sizes don't retrace the jitted grid per shape."""
    return 1 << max(n - 1, 0).bit_length()


@dataclasses.dataclass
class BatchedProblem:
    """Batched twin of ``PlacementProblem.score`` for candidate batches.

    ``evals`` counts logical candidate evaluations (what the seed's scalar
    loops counted); ``dispatches`` counts jitted device dispatches — the
    O(candidates) → O(dispatches) collapse the search layer exists for.
    """

    prob: PlacementProblem
    chunk: int = 4096
    # an already-built evaluator to reuse (same graph/cfg): callers that
    # re-solve the same problem shape against CHANGING fleets — the
    # closed-loop controller re-optimizing after every recalibration — keep
    # one evaluator so its jitted grid functions compile once, not per
    # reconfiguration (the fleet pack is data, not part of the trace).
    # None ⇒ BatchedEvaluator.shared(): equal-content problems across
    # BatchedProblem instances resolve to ONE evaluator through the
    # process-wide executable cache (repro.sim.execache), so a second
    # engine over an identically-specified problem never recompiles
    evaluator: BatchedEvaluator | None = None

    def __post_init__(self):
        self.evals = 0
        self.dispatches = 0
        # shape buckets this instance has dispatched (telemetry: the first
        # dispatch of an unseen padded size is a compilation-cache miss —
        # a silent retrace unless the evaluator was warmed on that bucket)
        self._seen_buckets: set[int] = set()
        self.scalar_fallback = self.prob.cost_cfg.include_compute
        if self.scalar_fallback:
            return
        self._ev = self.evaluator if self.evaluator is not None else \
            BatchedEvaluator.shared(self.prob.graph, self.prob.cost_cfg)
        fleet = self.prob.fleet
        if isinstance(fleet, RegionFleet):
            self._pack = RegionFleetFamily.from_fleets([fleet])
            self._speed = None  # structured families carry their own speeds
        elif isinstance(fleet, ExplicitFleet):
            self._pack = jnp.asarray(fleet.com_matrix(),
                                     jnp.float32)[None, :, :]
            self._speed = fleet.effective_speed()
        else:
            raise TypeError(f"unsupported fleet type {type(fleet).__name__}")
        obj = self.prob.objectives
        self._w_lat = 1.0
        if obj is not None:
            self._w_lat = dict(zip(obj.names, obj.weights)).get(
                "latency_f", 0.0)

    # -- raw batched values ---------------------------------------------------
    def _raw_chunk(self, xs: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """One padded chunk through score_grid at dq = 0: (latency (B,),
        dq-independent scalarization remainder (B,))."""
        b = xs.shape[0]
        bucket = _bucket(b)
        pad = bucket - b
        if pad:
            xs = np.concatenate([xs, np.repeat(xs[:1], pad, axis=0)])
        placements = pack_placements(list(xs))
        obj = self.prob.objectives
        self.dispatches += 1
        first = bucket not in self._seen_buckets
        reg = obs.registry()
        if reg.enabled:
            reg.counter("search.dispatches").add(1)
            reg.counter("search.candidates").add(b)
            reg.histogram("search.candidates_per_dispatch", lo=1.0).observe(b)
            if first:
                # a fresh padded shape: this dispatch retraces/compiles
                # (visible as jax.compiles too, but this names the bucket)
                reg.counter("search.bucket_first_dispatch",
                            bucket=str(bucket)).add(1)
        self._seen_buckets.add(bucket)
        if first and sanitize.state().enabled:
            # same event the telemetry meters — trips the retrace budget
            sanitize.note_first_dispatch(bucket)
        if obj is None:
            raw = self._ev.score_grid(placements, self._pack,
                                      dq=0.0, beta=0.0, guard_output=False)
        else:
            speed = None if self._speed is None or \
                isinstance(self._pack, RegionFleetFamily) else self._speed
            raw = self._ev.score_grid(placements, self._pack, dq=0.0,
                                      beta=0.0, objectives=obj, speed=speed,
                                      guard_output=False)
        lat, rest, _ = split_dq_term(raw)       # (1, B) grids, S == 1
        return lat[0, :b], rest[0, :b]

    def raw_values(self, placements: np.ndarray
                   ) -> tuple[np.ndarray, np.ndarray]:
        """(latency (P,), dq-independent remainder (P,)) over chunked
        dispatches.  ``score = rest + w_lat · lat / (1 + β·dq)``."""
        xs = np.asarray(placements, dtype=np.float64)
        lats, rests = [], []
        for lo in range(0, xs.shape[0], self.chunk):
            lat, rest = self._raw_chunk(xs[lo:lo + self.chunk])
            lats.append(lat)
            rests.append(rest)
        lat_all, rest_all = np.concatenate(lats), np.concatenate(rests)
        san = sanitize.state()
        if san.enabled and san.nan_check:
            # guard AFTER the host transfer concatenate already forces —
            # checking per chunk inside _raw_chunk would sync the device
            # early and forfeit async-dispatch overlap (measurably slower
            # than the check itself)
            self._guard_outputs(lat_all, rest_all)
        return lat_all, rest_all

    def _guard_outputs(self, lat: np.ndarray, rest: np.ndarray) -> None:
        """NaN guard on the assembled raw values; the offending chunk's
        shape bucket is recovered from the first NaN index (error path
        only — the clean path is two ``isnan().any()`` host scans)."""
        for name, arr in (("score_batch.latency", lat),
                          ("score_batch.rest", rest)):
            s = float(arr.sum()) if arr.size else 0.0
            if s == s:          # NaN anywhere poisons the sum
                continue
            if np.isnan(arr).any():
                idx = int(np.isnan(arr).argmax())
                lo = (idx // self.chunk) * self.chunk
                bucket = _bucket(min(arr.shape[0] - lo, self.chunk))
                sanitize.check_finite(name, arr, bucket=bucket)

    # -- feasibility ----------------------------------------------------------
    def feasible_mask(self, placements: np.ndarray,
                      dqs: np.ndarray) -> np.ndarray:
        """(P, D) DQCoupling feasibility — the vectorized twin of
        ``prob.feasible`` (:func:`repro.search.decision.dq_caps_mask`)."""
        mask = dq_caps_mask(placements, dqs, self.prob.dq)
        if mask is None:
            return np.ones((placements.shape[0], dqs.shape[0]), dtype=bool)
        return mask

    # -- the joint (placement × dq) score grid --------------------------------
    def score_batch(self, placements, dqs) -> np.ndarray:
        """(P, D) problem scores (∞ where infeasible) — ``prob.score`` for
        every (candidate, dq) pair of the cross product.

        The candidate batch is validated UP FRONT: a bad dtype or shape
        would otherwise dispatch into a fresh shape bucket and surface as
        an opaque retrace (or an XLA error); instead a typed
        :class:`repro.analysis.AnalysisError` names the offending bucket.
        """
        xs = np.asarray(placements)
        san = sanitize.state()
        # NaN placement mass is caught by the (cheaper) output nan-guard
        # in _raw_chunk when the sanitizer is armed
        sanitize.check_placements(
            xs, self.prob.graph.n_ops, self.prob.fleet.n_devices,
            bucket=_bucket(min(xs.shape[0] if xs.ndim >= 3 else 1,
                               self.chunk)))
        xs = xs.astype(np.float64, copy=False)
        if xs.ndim == 2:
            xs = xs[None]
        dq_arr = np.atleast_1d(np.asarray(dqs, dtype=np.float64))
        if san.enabled and san.domain_check:
            sanitize.check_dq(dq_arr)
        P, D = xs.shape[0], dq_arr.shape[0]
        self.evals += P * D
        if self.scalar_fallback:
            return np.array([[self.prob.score(x, float(d)) for d in dq_arr]
                             for x in xs])
        with obs.span("search.score_batch", P=P, D=D):
            lat, rest = self.raw_values(xs)
        denom = 1.0 + self.prob.beta * dq_arr                      # (D,)
        scores = rest[:, None] + self._w_lat * lat[:, None] / denom[None, :]
        return np.where(self.feasible_mask(xs, dq_arr), scores, np.inf)

    def score_pairs(self, placements, dqs) -> np.ndarray:
        """(P,) problem scores for PAIRED (candidate_i, dq_i) inputs — one
        dq per candidate (e.g. an annealing path whose quality knob moves
        along the walk), so ``evals`` counts P, not a P×D cross product."""
        xs = np.asarray(placements)
        san = sanitize.state()
        sanitize.check_placements(
            xs, self.prob.graph.n_ops, self.prob.fleet.n_devices,
            bucket=_bucket(min(xs.shape[0] if xs.ndim >= 3 else 1,
                               self.chunk)))
        xs = xs.astype(np.float64, copy=False)
        dq_arr = np.broadcast_to(
            np.asarray(dqs, dtype=np.float64), (xs.shape[0],))
        if san.enabled and san.domain_check:
            sanitize.check_dq(dq_arr)
        self.evals += xs.shape[0]
        if self.scalar_fallback:
            return np.array([self.prob.score(x, float(d))
                             for x, d in zip(xs, dq_arr)])
        lat, rest = self.raw_values(xs)
        scores = rest + self._w_lat * lat / (1.0 + self.prob.beta * dq_arr)
        if self.prob.dq is None:
            return scores
        col = xs.sum(axis=1)                                       # (P, V)
        caps = (np.asarray(self.prob.dq.cap0, dtype=np.float64)[None, :]
                - dq_arr[:, None] * np.asarray(self.prob.dq.load,
                                               dtype=np.float64)[None, :])
        feas = (col <= caps + 1e-7).all(axis=-1)                   # (P,)
        return np.where(feas, scores, np.inf)

    def best(self, placements, dqs) -> tuple[int, int, float]:
        """First-occurrence argmin over the (P, D) grid in candidate-major
        order — the seed loops' scan order — as (cand_idx, dq_idx, score)."""
        scores = self.score_batch(placements, dqs)
        k = int(np.argmin(scores))
        i, d = divmod(k, scores.shape[1])
        return i, d, float(scores[i, d])
