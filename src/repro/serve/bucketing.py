"""Query normalization and shape bucketing for the what-if serving layer.

Heterogeneous tenant queries coalesce only when they can share a dispatch.
Two facts make that sharing wide instead of narrow:

  * **dq/β are operands, not shapes.**  Only latency-F depends on dq,
    through the closed-form ``/(1 + β·dq)`` factor, and ``score_grid``
    takes dq per cell and β per row, so queries with *different* dq
    values and β coexist in one super-batch and each row is finished with
    its own.  Joint queries ride along raw (dq = 0, β = 0, exactly like
    ``repro.search.engine``) and expand their dq grid on the host.
  * **rows are independent.**  ``score_grid`` vmaps over the placement
    axis, so concatenating tenants' candidate rows — and padding with
    repeated rows up to a power-of-two bucket — changes nothing about any
    individual row's result (bitwise; gated in ``bench_serve`` and
    ``tests/test_serve.py``).

What remains in the coalescing key is exactly what separates one
service's queues: the scenario pack (content digest — two tenants
registering equal fleets coalesce) and the objective set.  The graph and
CostConfig need no place in it, since each service owns one of each and
its own queues.  The padded row count is
the *shape bucket*: the unit of executable-cache identity, admission
pricing, and per-bucket telemetry.
"""

from __future__ import annotations

import dataclasses
import hashlib

import numpy as np

from repro.core.devices import RegionFleetFamily
from repro.core.objectives import ObjectiveSet

__all__ = ["CoalesceKey", "fleet_digest", "next_pow2", "pad_rows"]


def next_pow2(n: int) -> int:
    """Next power of two ≥ n — the bucketing rule shared with
    ``repro.search.engine``: a handful of padded shapes instead of one
    compiled executable per row count."""
    return 1 << max(int(n) - 1, 0).bit_length()


def fleet_digest(pack) -> str:
    """Content digest of a packed scenario family (dense (S, V, V) stack or
    :class:`RegionFleetFamily`).  Computed ONCE at fleet registration —
    queries then carry the fleet id — so coalescing across tenants keys on
    what the dispatch actually consumes, not on object identity."""
    h = hashlib.sha256()
    if isinstance(pack, RegionFleetFamily):
        h.update(b"structured")
        h.update(np.ascontiguousarray(pack.region).tobytes())
        h.update(np.ascontiguousarray(pack.inter).tobytes())
        h.update(np.ascontiguousarray(pack.degrade).tobytes())
        h.update(np.float64(pack.self_cost).tobytes())
        h.update(np.ascontiguousarray(pack.speed_or_ones()).tobytes())
    else:
        arr = np.asarray(pack, dtype=np.float32)
        if arr.ndim != 3 or arr.shape[1] != arr.shape[2]:
            raise ValueError(f"dense pack must be (S, V, V), "
                             f"got {arr.shape}")
        h.update(b"dense")
        h.update(np.ascontiguousarray(arr).tobytes())
    return h.hexdigest()[:16]


@dataclasses.dataclass(frozen=True)
class CoalesceKey:
    """Everything two queries of one service must agree on to share one
    raw dispatch.

    ``fleet`` (the registration-time content digest) pins the scenario
    operands, ``objectives`` pins the multi-objective executable (None =
    the single-objective latency grid).  dq/β are deliberately ABSENT —
    they are per-row operands of the shared dispatch."""

    fleet: str
    objectives: ObjectiveSet | None


def pad_rows(xs: np.ndarray, bucket: int) -> np.ndarray:
    """Pad a (P, n_ops, V) super-batch to ``bucket`` rows by repeating the
    last row.  Padding rows are real (valid simplex placements), score
    normally, and are SLICED OFF before any tenant sees results — the
    non-leak property ``tests/test_serve.py`` pins."""
    pad = bucket - xs.shape[0]
    if pad < 0:
        raise ValueError(f"batch of {xs.shape[0]} rows exceeds "
                         f"bucket {bucket}")
    if pad == 0:
        return xs
    return np.concatenate([xs, np.repeat(xs[-1:], pad, axis=0)])

