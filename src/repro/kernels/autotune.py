"""VMEM-aware block-shape autotuning for the edge-latency kernels.

Picking ``(block_edges, block_v)`` is a real tradeoff the kernels cannot
resolve locally: larger edge blocks re-stream the com matrix fewer times
(dense HBM traffic carries a ``n_e · V²`` term), larger V blocks re-stream
the endpoint rows fewer times (a ``n_u`` factor on x_j) — but both inflate
the per-step VMEM footprint, and a block pair that spills VMEM doesn't
lower at all.  This module ranks candidate pairs with two analytic models
that price EXACTLY what the kernels run (both sides share
:func:`repro.kernels.edge_latency.block_geometry`):

  * :func:`vmem_bytes` — the per-grid-step VMEM footprint: every streamed
    input tile double-buffered, plus the scratch accumulator and output;
  * :func:`predict_seconds` — a roofline estimate (the device kind's
    entry in ``repro.perf.roofline.CHIP_PEAKS``): max(compute term,
    HBM-traffic term) + per-grid-step overhead.
    HBM traffic counts tile *revisits* (the dense kernel re-reads com once
    per edge block and x_j once per u block), which is what makes the
    ranking non-trivial.

Decisions persist in a process-wide table keyed by
``(backend, kind, V, E, R, B-bucket, n_ops)`` — B buckets to powers of
two, the same rule the serving layer uses, so one warm entry covers the
whole bucket.  ``get_config`` consults the table first; a miss ranks candidates
analytically and (optionally, when the caller supplies a ``timer`` — real
accelerators only; interpret-mode timings rank Python overhead, not
hardware) races the top candidates empirically.  The table round-trips to
JSON via :func:`save_table` / :func:`load_table` (format in
kernels/README.md).

The table is consulted at TRACE time by the dispatch layer: a decision
returns a config, and the (already-jitted, static-block-arg) kernel
wrapper is reused — autotuning never constructs a ``pallas_call`` per
iteration, so the no-silent-retrace discipline holds (lint-enforced).
Decisions and chosen block shapes are exported through ``repro.obs``.
"""

from __future__ import annotations

import dataclasses
import json
import threading

import jax

from repro import obs
from repro.kernels.edge_latency import block_geometry, structured_row_block
from repro.perf.roofline import peaks_for

__all__ = ["KernelConfig", "ShapeKey", "DEFAULT_CONFIG", "VMEM_BUDGET_BYTES",
           "candidate_configs", "vmem_bytes", "predict_seconds", "rank",
           "local_device_kind", "get_config", "table_rows", "save_table", "load_table",
           "clear_table"]

BYTES_F32 = 4
VMEM_BYTES_TOTAL = 16 * 2 ** 20   # ~16 MiB of VMEM per TPU core
VMEM_FRACTION = 0.75              # headroom for compiler temporaries
VMEM_BUDGET_BYTES = int(VMEM_BYTES_TOTAL * VMEM_FRACTION)

# per-grid-step dispatch overhead in the analytic model: compiled TPU grids
# cost ~a microsecond of sequencing per step; interpret mode (CPU) runs the
# kernel body in Python, where per-step overhead dominates everything —
# which is exactly why the model must price it, or it would happily pick
# tiny blocks on the backend the container actually runs
STEP_OVERHEAD_S = {"cpu": 100e-6}
STEP_OVERHEAD_DEFAULT_S = 1.5e-6

BLOCK_EDGES_CANDIDATES = (32, 64, 128, 256, 512)
BLOCK_V_CANDIDATES = (128, 256, 512, 1024, 2048)
EMPIRICAL_TOP_K = 3


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """One block-shape decision for the edge-latency kernels."""

    block_edges: int = 128
    block_v: int = 512


DEFAULT_CONFIG = KernelConfig()


@dataclasses.dataclass(frozen=True)
class ShapeKey:
    """Decision-table key: everything the choice may depend on.  B buckets
    to the next power of two (one entry per serving-layer shape bucket)."""

    backend: str
    kind: str          # "dense" | "structured"
    V: int
    E: int
    R: int | None
    b_bucket: int
    n_ops: int | None = None   # structured: operators whose rows it reads

    @classmethod
    def of(cls, backend: str, kind: str, B: int, E: int, V: int,
           R: int | None, n_ops: int | None = None) -> "ShapeKey":
        return cls(backend=backend, kind=kind, V=int(V), E=int(E),
                   R=None if R is None else int(R),
                   b_bucket=1 << max(int(B) - 1, 0).bit_length(),
                   n_ops=_n_ops(kind, E, n_ops))


_lock = threading.Lock()
_table: dict[ShapeKey, tuple[KernelConfig, str]] = {}


def _n_ops(kind: str, E: int, n_ops: int | None) -> int | None:
    """The structured kernel's operator rows; by default one per edge
    (rows already gathered to edges).  None for the dense kernel."""
    if kind != "structured":
        return None
    return int(E if n_ops is None else n_ops)


def vmem_bytes(kind: str, E: int, V: int, R: int | None,
               config: KernelConfig, n_ops: int | None = None,
               B: int = 1) -> int:
    """Per-grid-step VMEM footprint of the blocked kernel under ``config``:
    streamed input tiles double-buffered (the compiler overlaps the next
    tile's DMA with compute), scratch and output single-buffered.  The
    structured kernel's tiles hold a row block of ``B`` rows (at most 8)
    of every operator, ``n_ops`` of them (default: one per edge)."""
    g = block_geometry(kind, E, V, R, config.block_edges, config.block_v)
    if kind == "dense":
        inputs = g.be * g.bv + g.be * g.bv + g.bv * g.bv  # xi, xj, com
        scratch = g.be * g.bv                             # t accumulator
        return BYTES_F32 * (2 * inputs + scratch + 2 * g.be)
    rows = structured_row_block(B) * _n_ops(kind, E, n_ops)
    # x, w, mass, a tiles; x and T parked by lane chunk
    inputs = 2 * rows * g.bv + rows * g.r_pad + g.r_pad * g.bv
    temps = 2 * rows * g.bv
    out = E * structured_row_block(B) * g.out_lanes
    return BYTES_F32 * (2 * inputs + temps + 2 * out)


def predict_seconds(kind: str, B: int, E: int, V: int, R: int | None,
                    config: KernelConfig, com_batch: int = 1,
                    backend: str = "tpu",
                    device_kind: str | None = None,
                    n_ops: int | None = None) -> float:
    """Analytic time estimate for one kernel launch: roofline terms over
    the PADDED shape (so over-padding from a too-coarse block is priced),
    with HBM traffic counting every tile revisit the index maps imply.
    Off the CPU the peaks are ``device_kind``'s; an unknown kind raises."""
    peaks = peaks_for(backend, device_kind)
    g = block_geometry(kind, E, V, R, config.block_edges, config.block_v)
    if kind == "dense":
        steps = B * g.n_e * g.n_u * g.n_v
        flops = 2.0 * B * g.e_pad * g.v_pad * g.v_pad \
            + 3.0 * B * g.e_pad * g.v_pad
        traffic = (B * g.e_pad * g.v_pad            # xi: once per (e, u)
                   + B * g.e_pad * g.v_pad * g.n_u  # xj: re-read per u block
                   + com_batch * g.n_e * g.v_pad * g.v_pad  # com: per e blk
                   + B * g.e_pad)                   # output
    else:
        n = _n_ops(kind, E, n_ops)
        rb = structured_row_block(B)
        b_pad = -(-B // rb) * rb
        steps = b_pad // rb * g.n_u
        flops = (2.0 * b_pad * n * g.r_pad * g.v_pad  # T = mass @ a
                 + 3.0 * b_pad * E * g.v_pad)         # x·s, ·T, max
        traffic = (2 * b_pad * n * g.v_pad            # x, w: read once
                   + b_pad * n * g.r_pad              # mass: once
                   + (b_pad // rb if com_batch == 1 else B)
                   * g.r_pad * g.v_pad                # a: per row block
                   + E * b_pad * g.out_lanes)         # output
    overhead = STEP_OVERHEAD_S.get(backend, STEP_OVERHEAD_DEFAULT_S)
    return max(flops / peaks.flops, BYTES_F32 * traffic / peaks.hbm_bw) \
        + steps * overhead


def candidate_configs(kind: str, E: int, V: int, R: int | None,
                      n_ops: int | None = None,
                      B: int = 1) -> list[KernelConfig]:
    """VMEM-feasible (block_edges, block_v) pairs, deduplicated by the
    geometry they actually clamp to (a 512-wide block over V = 300 is the
    same kernel as a 384-wide one).  Raises ValueError when not even the
    smallest tile fits the budget (a huge R): no kernel lowers there."""
    out, seen = [], set()
    for be in BLOCK_EDGES_CANDIDATES:
        for bv in BLOCK_V_CANDIDATES:
            cfg = KernelConfig(block_edges=be, block_v=bv)
            g = block_geometry(kind, E, V, R, be, bv)
            if (g.be, g.bv) in seen:
                continue
            if vmem_bytes(kind, E, V, R, cfg, n_ops, B) > VMEM_BUDGET_BYTES:
                continue
            seen.add((g.be, g.bv))
            out.append(cfg)
    if not out:
        raise ValueError(
            f"no {kind} block shape fits the {VMEM_BUDGET_BYTES}-byte VMEM "
            f"budget at E={E}, V={V}, R={R}")
    return out


def rank(kind: str, B: int, E: int, V: int, R: int | None = None,
         com_batch: int = 1, backend: str = "tpu",
         device_kind: str | None = None,
         n_ops: int | None = None) -> list[KernelConfig]:
    """Feasible candidates, best predicted first (deterministic: ties break
    toward the larger blocks, which also minimize grid-sequencing steps)."""
    cands = candidate_configs(kind, E, V, R, n_ops, B)
    return sorted(
        cands,
        key=lambda c: (predict_seconds(kind, B, E, V, R, c,
                                       com_batch=com_batch, backend=backend,
                                       device_kind=device_kind,
                                       n_ops=n_ops),
                       -c.block_v, -c.block_edges))


def local_device_kind(backend: str) -> str:
    """``device_kind`` of ``backend``'s first device (e.g. "TPU v5 lite"):
    the key the tuner looks its peaks up by."""
    return jax.devices(backend)[0].device_kind


def get_config(kind: str, B: int, E: int, V: int, R: int | None = None,
               com_batch: int = 1, backend: str | None = None,
               device_kind: str | None = None, timer=None,
               n_ops: int | None = None) -> KernelConfig:
    """The block config for one shape: decision-table hit, else analytic
    ranking (plus an empirical race over the top candidates when ``timer``
    — a ``callable(KernelConfig) -> seconds`` — is supplied), stored.
    ``backend`` defaults to JAX's, ``device_kind`` (which picks the peaks
    off the CPU) to that of the backend's first device.

    Safe to call at trace time: pure host work, deterministic per key."""
    if backend is None:
        backend = jax.default_backend()
    key = ShapeKey.of(backend, kind, B, E, V, R, n_ops)
    with _lock:
        hit = _table.get(key)
    reg = obs.registry()
    if hit is not None:
        if reg.enabled:
            reg.counter("kernels.autotune.decisions", kind=kind,
                        source="table", backend=backend).add(1)
        return hit[0]
    if backend != "cpu" and device_kind is None:
        device_kind = local_device_kind(backend)
    ranked = rank(kind, key.b_bucket, E, V, R, com_batch=com_batch,
                  backend=backend, device_kind=device_kind,
                  n_ops=key.n_ops)
    best, source = ranked[0], "analytic"
    if timer is not None:
        timed = [(timer(c), c) for c in ranked[:EMPIRICAL_TOP_K]]
        best, source = min(timed, key=lambda t: t[0])[1], "empirical"
    with _lock:
        _table[key] = (best, source)
    if reg.enabled:
        reg.counter("kernels.autotune.decisions", kind=kind, source=source,
                    backend=backend).add(1)
        reg.gauge("kernels.autotune.block_edges", kind=kind,
                  V=str(V)).set(best.block_edges)
        reg.gauge("kernels.autotune.block_v", kind=kind,
                  V=str(V)).set(best.block_v)
    return best


# -- decision-table persistence ----------------------------------------------

def table_rows() -> list[dict]:
    """The decision table as JSON-ready rows (format: kernels/README.md)."""
    with _lock:
        items = sorted(_table.items(),
                       key=lambda kv: (kv[0].backend, kv[0].kind, kv[0].V,
                                       kv[0].E, kv[0].b_bucket))
    return [{"backend": k.backend, "kind": k.kind, "V": k.V, "E": k.E,
             "R": k.R, "b_bucket": k.b_bucket, "n_ops": k.n_ops,
             "block_edges": cfg.block_edges, "block_v": cfg.block_v,
             "source": source}
            for k, (cfg, source) in items]


def save_table(path) -> None:
    rows = table_rows()
    with open(path, "w") as f:
        json.dump({"version": 1, "entries": rows}, f, indent=2)


def load_table(path) -> int:
    """Merge a saved decision table into the process table (existing
    entries win — a live decision is never clobbered by a stale file).
    Returns the number of entries loaded."""
    with open(path) as f:
        doc = json.load(f)
    if doc.get("version") != 1:
        raise ValueError(f"unknown autotune table version "
                         f"{doc.get('version')!r}")
    loaded = 0
    with _lock:
        for row in doc["entries"]:
            key = ShapeKey(backend=row["backend"], kind=row["kind"],
                           V=int(row["V"]), E=int(row["E"]),
                           R=None if row["R"] is None else int(row["R"]),
                           b_bucket=int(row["b_bucket"]),
                           n_ops=_n_ops(row["kind"], int(row["E"]),
                                        row.get("n_ops")))
            if key in _table:
                continue
            _table[key] = (KernelConfig(block_edges=int(row["block_edges"]),
                                        block_v=int(row["block_v"])),
                           row.get("source", "table"))
            loaded += 1
    return loaded


def clear_table() -> None:
    with _lock:
        _table.clear()
