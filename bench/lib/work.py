"""The algorithm's operations and bytes for one grid dispatch, whatever
implements it, for the real (unpadded) rows of the chunk.

Dense, per scenario: ``t = com @ x_j`` for every edge is ``2*P*E*V^2``
operations; the scenario's (V, V) link costs are read once, and the
placements (P, n_ops, V) once per dispatch.  Structured, per scenario:
``t = mass @ A`` is ``2*P*E*R*V`` operations; ``A`` (R, V) is read once per
scenario and the placements once per dispatch.  The gathered per-edge
copies of the placements and repeated reads of ``com`` are the
implementation's, not the algorithm's, so they are not counted: removing
them shows as a gain.  Every value is float32.
"""

from __future__ import annotations

__all__ = ["grid_work", "least_seconds"]

F32 = 4


def grid_work(kind: str, rows: int, S: int, E: int, V: int, n_ops: int,
              R: int | None = None) -> tuple[float, float]:
    """(operations, bytes) of one dispatch scoring ``rows`` placements
    against ``S`` scenarios of a graph with ``E`` edges."""
    x_bytes = F32 * rows * n_ops * V
    if kind == "dense":
        return 2.0 * S * rows * E * V * V, F32 * S * V * V + x_bytes
    if kind == "structured":
        return 2.0 * S * rows * E * R * V, F32 * S * R * V + x_bytes
    raise ValueError(f"unknown fleet kind {kind!r}")


def least_seconds(ops: float, nbytes: float, peak) -> tuple[float, str]:
    """The roofline's least time and which bound sets it."""
    t_ops, t_mem = ops / peak.flops, nbytes / peak.hbm_bw
    return (t_ops, "flops") if t_ops >= t_mem else (t_mem, "hbm")
