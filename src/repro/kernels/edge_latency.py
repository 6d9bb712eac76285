"""Pallas TPU kernels for the cost model's hot edge-latency reduction.

The paper's edge latency (§3) is, per edge ``i→j`` with placement rows
``x_i``/``x_j`` and communication matrix ``com``:

    edgeLat = max_u  x_{i,u} · s_i · Σ_v com_{u,v} · x_{j,v}

The batched what-if evaluator (repro.sim.batched) scores (scenario ×
placement) grids, so the reduction runs over a (B, E, V) tensor of gathered
edge endpoint rows against a (B, V, V) tensor of per-scenario com matrices —
a fused matvec + row-max that dominates evaluation time once B·E·V² grows.
Selectivity is folded into ``x_i`` by the caller, keeping the kernels pure
bilinear-maxes.

Compiled-ready blocking scheme (see kernels/README.md for the full story):

  * every V-sized axis is padded to the f32 lane width (128) inside the
    wrapper, and E to the sublane width (8), so arbitrary fleet sizes lower
    cleanly — padded u-columns are masked to -inf before the row max,
    padded v-columns contribute exact zeros to the contraction;
  * the DENSE kernel runs a (B, E/be, V/bv, V/bv) grid: the innermost v
    axis accumulates the ``com @ x_j`` matvec into a VMEM scratch tile, the
    u axis folds per-block row maxima into the output with a running max —
    so the (E, V) endpoint rows and the (V, V) com matrix stream through
    VMEM in (be, bv) / (bv, bv) tiles instead of requiring residency;
  * the STRUCTURED kernel (RegionFleetFamily: ``t = mass @ A + w`` with
    R ≪ V) runs a (B, E/be, V/bv) grid, V-blocking its (be, R)@(R, bv)
    product and the own-region term ``w`` with the same running max over
    u-tiles;
  * both kernels write a lane-dense (B, e_pad, LANE) output in (1, be, LANE)
    blocks, every lane holding the edge's running max: Mosaic requires an
    output block's last two dims to be (8, 128)-aligned or whole, which a
    (1, be) block over a (B, e_pad) array is not.  The wrapper keeps lane 0.

Block shapes come from :mod:`repro.kernels.autotune` via the dispatch layer
(:mod:`repro.kernels.dispatch`); the single-tile kernels the blocked ones
replaced are kept as ``*_single_tile`` parity references — at small V the
blocked kernels reproduce them bitwise (gated in tests/test_kernel_blocking).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["LANE", "SUBLANE", "BlockGeometry", "block_geometry",
           "edge_latency_pallas", "edge_latency_structured_pallas",
           "edge_latency_pallas_single_tile",
           "edge_latency_structured_pallas_single_tile"]

LANE = 128     # f32 minor-dim tile width on TPU
SUBLANE = 8    # f32 second-minor tile width
# the f32 contraction at f32 precision on the MXU (Mosaic's contract
# precision fp32), not one bf16 pass: scores answer to a float64 oracle
F32_DOT = jax.lax.Precision.HIGHEST


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


@dataclasses.dataclass(frozen=True)
class BlockGeometry:
    """Concrete padded dims + clamped block shapes for one problem shape.

    This is THE single source of truth for how a (E, V[, R]) shape lowers:
    the kernel wrappers pad/grid exactly by it and the autotune VMEM/time
    models price exactly it, so the model can never drift from the kernel.
    """

    be: int           # edge-block rows (≤ padded E, multiple of SUBLANE)
    bv: int           # V-block width (≤ padded V, multiple of LANE)
    e_pad: int        # E padded to a multiple of be
    v_pad: int        # V padded to a multiple of bv
    r_pad: int | None  # R padded to a multiple of LANE (structured only)
    n_e: int          # edge-block grid steps
    n_u: int          # u-axis (row-max) grid steps
    n_v: int          # v-axis (contraction) grid steps; 1 for structured
    out_lanes: int    # minor dim of the (B, e_pad, out_lanes) kernel output


def block_geometry(kind: str, E: int, V: int, R: int | None,
                   block_edges: int, block_v: int) -> BlockGeometry:
    """Clamp a requested (block_edges, block_v) to a legal geometry for the
    shape: blocks are rounded to hardware tile multiples, then the axes pad
    up to block multiples (never the other way round — a requested block
    larger than the padded axis shrinks to it)."""
    if kind not in ("dense", "structured"):
        raise ValueError(f"kind must be dense|structured, got {kind!r}")
    if E < 1 or V < 1:
        raise ValueError(f"need E >= 1 and V >= 1, got E={E}, V={V}")
    bv = _round_up(max(1, block_v), LANE)
    bv = min(bv, _round_up(V, LANE))
    v_pad = _round_up(V, bv)
    be = _round_up(max(1, block_edges), SUBLANE)
    be = min(be, _round_up(E, SUBLANE))
    e_pad = _round_up(E, be)
    n_v = v_pad // bv if kind == "dense" else 1
    r_pad = None
    if kind == "structured":
        if R is None or R < 1:
            raise ValueError(f"structured geometry needs R >= 1, got {R}")
        r_pad = _round_up(R, LANE)
    return BlockGeometry(be=be, bv=bv, e_pad=e_pad, v_pad=v_pad,
                         r_pad=r_pad, n_e=e_pad // be, n_u=v_pad // bv,
                         n_v=n_v, out_lanes=LANE)


def _fold_row_max(u, o_ref, vals):
    """Fold the (be, bv) tile's row max into the lane-dense (be, LANE)
    output block: initialise on the first u-tile, running max after."""
    part = jnp.broadcast_to(jnp.max(vals, axis=1, keepdims=True),
                            o_ref.shape[1:])

    @pl.when(u == 0)
    def _init():
        o_ref[0] = part

    @pl.when(u > 0)
    def _running():
        o_ref[0] = jnp.maximum(o_ref[0], part)


def _pad_axis(x: jnp.ndarray, axis: int, target: int) -> jnp.ndarray:
    pad = target - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# -- dense V-blocked kernel ---------------------------------------------------
#
# grid = (B, n_e, n_u, n_v); iteration is row-major, so for one (b, e, u)
# the v axis runs innermost: the scratch tile accumulates the partial
# matvec t[e, u_blk] += com[u_blk, v_blk] @ x_j[e, v_blk] across v-tiles,
# and on the last v-tile the block's row max folds into the output under a
# running max across u-tiles.  Padded u-columns are masked to -inf so the
# max over real columns is exact for operands of any sign.


def _edge_latency_blocked_kernel(n_v: int, v_real: int, xi_ref, xj_ref,
                                 com_ref, o_ref, t_acc):
    u = pl.program_id(2)
    v = pl.program_id(3)

    @pl.when(v == 0)
    def _zero():
        t_acc[...] = jnp.zeros_like(t_acc)

    xj = xj_ref[0].astype(jnp.float32)    # (be, bv) — v-tile of x_j
    com = com_ref[0].astype(jnp.float32)  # (bu=bv, bv) — (u, v) com tile
    # t_acc[e, u'] += Σ_{v'} com[u', v'] · xj[e, v']
    t_acc[...] += jax.lax.dot_general(xj, com, (((1,), (1,)), ((), ())),
                                      precision=F32_DOT,
                                      preferred_element_type=jnp.float32)

    @pl.when(v == n_v - 1)
    def _fold_max():
        xi = xi_ref[0].astype(jnp.float32)  # (be, bu) — pre-scaled by s_i
        u_ix = u * xi.shape[1] + jax.lax.broadcasted_iota(
            jnp.int32, xi.shape, 1)
        _fold_row_max(u, o_ref,
                      jnp.where(u_ix < v_real, xi * t_acc[...], -jnp.inf))


@functools.partial(jax.jit,
                   static_argnames=("block_edges", "block_v", "interpret"))
def edge_latency_pallas(x_i, x_j, com, block_edges: int = 128,
                        block_v: int = 512, interpret: bool = False):
    """x_i, x_j: (B, E, V) with selectivity folded into x_i; com: (B, V, V)
    or (1, V, V) → (B, E) latencies ``max_u x_i[b,e,u]·(com[b] @ x_j[b,e])_u``.

    V-blocked: (E, V) tiles and (bv, bv) com tiles stream through VMEM (see
    module docstring), so V needs neither lane alignment nor VMEM residency.
    A singleton com batch dim is shared across B via the index map (no
    replication in HBM) — the score-grid path scores every placement of one
    scenario against a single resident com matrix."""
    B, E, V = x_i.shape
    if E == 0:
        return jnp.zeros((B, 0), jnp.float32)
    if com.shape[0] not in (1, B):
        raise ValueError(f"com batch dim {com.shape[0]} must be 1 or {B}")
    shared_com = com.shape[0] == 1
    g = block_geometry("dense", E, V, None, block_edges, block_v)
    x_i = _pad_axis(_pad_axis(x_i, 2, g.v_pad), 1, g.e_pad)
    x_j = _pad_axis(_pad_axis(x_j, 2, g.v_pad), 1, g.e_pad)
    com = _pad_axis(_pad_axis(com, 2, g.v_pad), 1, g.v_pad)
    com_ix = (lambda b, e, u, v: (0, u, v)) if shared_com \
        else (lambda b, e, u, v: (b, u, v))
    out = pl.pallas_call(
        functools.partial(_edge_latency_blocked_kernel, g.n_v, V),
        grid=(B, g.n_e, g.n_u, g.n_v),
        in_specs=[
            pl.BlockSpec((1, g.be, g.bv), lambda b, e, u, v: (b, e, u)),
            pl.BlockSpec((1, g.be, g.bv), lambda b, e, u, v: (b, e, v)),
            pl.BlockSpec((1, g.bv, g.bv), com_ix),
        ],
        out_specs=pl.BlockSpec((1, g.be, g.out_lanes),
                               lambda b, e, u, v: (b, e, 0)),
        out_shape=jax.ShapeDtypeStruct((B, g.e_pad, g.out_lanes),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((g.be, g.bv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary",
                                 "arbitrary")),
        interpret=interpret,
    )(x_i, x_j, com)
    return out[:, :E, 0]


# -- structured (RegionFleet) V-blocked kernel --------------------------------
#
# At 10⁵ devices the (V, V) com matrix no longer exists; the structured path
# factors the per-edge matvec through region space
# (``repro.core.jaxmodel.region_a_off`` / ``region_terms``):
#
#   t[e, u] = Σ_r A[r, u] · mass[e, r]  +  w[e, u]
#   A[r, u] = degrade_u · inter[region_u, r], 0 in u's own region (R, V)
#   mass[e, r] = Σ_{v ∈ region r} degrade_v · x_j[e, v]          (E, R)
#   w[e, u] = u's own-region transfer over the region's other devices,
#             plus its transfer to itself                          (E, V)
#
# so the kernel's inner product is (be, R) @ (R, bv) — R ≪ V — and the only
# V-sized operands are two (E, V) rows, as many as the dense kernel
# streams.  The caller precomputes ``mass``/``A``/``w`` (XLA gathers and
# segment sums, no V² anywhere); here the u axis is V-blocked with the
# same running max as the dense kernel, so A/w/x tiles stream through
# VMEM in (R, bv)/(be, bv) slices and V = 131 072 fleets never need a
# V-resident row.  R pads to the lane width (zero rows of mass/A add
# exact zeros to the product).


def _check_structured(x_i, a, w):
    B = x_i.shape[0]
    if a.shape[0] not in (1, B):
        raise ValueError(f"scenario batch dim {a.shape[0]} must be 1 or {B}")
    if w.shape != x_i.shape:
        raise ValueError(f"w has shape {w.shape}, want {x_i.shape}")


def _edge_latency_structured_blocked_kernel(v_real: int, xi_ref, mass_ref,
                                            a_ref, w_ref, o_ref):
    u = pl.program_id(2)
    xi = xi_ref[0].astype(jnp.float32)      # (be, bv) — pre-scaled by s_i
    mass = mass_ref[0].astype(jnp.float32)  # (be, Rp)
    a = a_ref[0].astype(jnp.float32)        # (Rp, bv)
    w = w_ref[0].astype(jnp.float32)        # (be, bv)
    t = jax.lax.dot_general(mass, a, (((1,), (0,)), ((), ())),
                            precision=F32_DOT,
                            preferred_element_type=jnp.float32)
    u_ix = u * xi.shape[1] + jax.lax.broadcasted_iota(jnp.int32, xi.shape, 1)
    _fold_row_max(u, o_ref,
                  jnp.where(u_ix < v_real, xi * (t + w), -jnp.inf))


@functools.partial(jax.jit,
                   static_argnames=("block_edges", "block_v", "interpret"))
def edge_latency_structured_pallas(x_i, mass, a, w,
                                   block_edges: int = 128,
                                   block_v: int = 512,
                                   interpret: bool = False):
    """x_i, w: (B, E, V); mass: (B, E, R); a: (Bc, R, V) with Bc ∈ {1, B}
    → (B, E) latencies ``max_u x_i·(mass @ a + w)``.

    V-blocked over the u axis with a running max (module docstring); R pads
    to the lane width with exact-zero rows.  A singleton scenario batch
    (Bc == 1) is shared across all B placement rows via the index map,
    mirroring the dense kernel's shared-com path."""
    B, E, V = x_i.shape
    R = mass.shape[-1]
    if E == 0:
        return jnp.zeros((B, 0), jnp.float32)
    _check_structured(x_i, a, w)
    shared = a.shape[0] == 1
    g = block_geometry("structured", E, V, R, block_edges, block_v)
    x_i = _pad_axis(_pad_axis(x_i, 2, g.v_pad), 1, g.e_pad)
    w = _pad_axis(_pad_axis(w, 2, g.v_pad), 1, g.e_pad)
    mass = _pad_axis(_pad_axis(mass, 2, g.r_pad), 1, g.e_pad)
    a = _pad_axis(_pad_axis(a, 2, g.v_pad), 1, g.r_pad)
    scen_ix = (lambda b, e, u: (0, 0, u)) if shared \
        else (lambda b, e, u: (b, 0, u))
    rows = pl.BlockSpec((1, g.be, g.bv), lambda b, e, u: (b, e, u))
    out = pl.pallas_call(
        functools.partial(_edge_latency_structured_blocked_kernel, V),
        grid=(B, g.n_e, g.n_u),
        in_specs=[
            rows,
            pl.BlockSpec((1, g.be, g.r_pad), lambda b, e, u: (b, e, 0)),
            pl.BlockSpec((1, g.r_pad, g.bv), scen_ix),
            rows,
        ],
        out_specs=pl.BlockSpec((1, g.be, g.out_lanes),
                               lambda b, e, u: (b, e, 0)),
        out_shape=jax.ShapeDtypeStruct((B, g.e_pad, g.out_lanes),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(x_i, mass, a, w)
    return out[:, :E, 0]


# -- single-tile parity references --------------------------------------------
#
# The pre-blocking kernels: whole-V tiles resident in VMEM, no lane padding.
# Kept verbatim as the exact-parity targets the blocked kernels are gated
# against at small V (tests/test_kernel_blocking.py) — at one (u, v) tile
# the blocked kernels reduce to precisely this computation.


def _edge_latency_single_tile_kernel(xi_ref, xj_ref, com_ref, o_ref):
    xi = xi_ref[0].astype(jnp.float32)    # (be, V) — pre-scaled by s_i
    xj = xj_ref[0].astype(jnp.float32)    # (be, V)
    com = com_ref[0].astype(jnp.float32)  # (V, V)
    t = jax.lax.dot_general(xj, com, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o_ref[0] = jnp.max(xi * t, axis=1)


@functools.partial(jax.jit, static_argnames=("block_edges", "interpret"))
def edge_latency_pallas_single_tile(x_i, x_j, com, block_edges: int = 128,
                                    interpret: bool = False):
    """The original whole-V dense kernel (parity reference; assumes the
    (V, V) com tile fits VMEM — do not use for large V)."""
    B, E, V = x_i.shape
    if E == 0:
        return jnp.zeros((B, 0), jnp.float32)
    if com.shape[0] not in (1, B):
        raise ValueError(f"com batch dim {com.shape[0]} must be 1 or {B}")
    shared_com = com.shape[0] == 1
    be = min(block_edges, E)
    x_i = _pad_axis(x_i, 1, _round_up(E, be))
    x_j = _pad_axis(x_j, 1, _round_up(E, be))
    n_blocks = x_i.shape[1] // be
    com_index = (lambda b, e: (0, 0, 0)) if shared_com \
        else (lambda b, e: (b, 0, 0))
    out = pl.pallas_call(
        _edge_latency_single_tile_kernel,
        grid=(B, n_blocks),
        in_specs=[
            pl.BlockSpec((1, be, V), lambda b, e: (b, e, 0)),
            pl.BlockSpec((1, be, V), lambda b, e: (b, e, 0)),
            pl.BlockSpec((1, V, V), com_index),
        ],
        out_specs=pl.BlockSpec((1, be), lambda b, e: (b, e)),
        out_shape=jax.ShapeDtypeStruct((B, x_i.shape[1]), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x_i, x_j, com)
    return out[:, :E]


def _edge_latency_structured_single_tile_kernel(xi_ref, mass_ref, a_ref,
                                                w_ref, o_ref):
    xi = xi_ref[0].astype(jnp.float32)      # (be, V) — pre-scaled by s_i
    mass = mass_ref[0].astype(jnp.float32)  # (be, R)
    a = a_ref[0].astype(jnp.float32)        # (R, V)
    w = w_ref[0].astype(jnp.float32)        # (be, V)
    t = jax.lax.dot_general(mass, a, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o_ref[0] = jnp.max(xi * (t + w), axis=1)


@functools.partial(jax.jit, static_argnames=("block_edges", "interpret"))
def edge_latency_structured_pallas_single_tile(x_i, mass, a, w,
                                               block_edges: int = 128,
                                               interpret: bool = False):
    """The original whole-V structured kernel (parity reference; (R, V) and
    (be, V) tiles resident — do not use for large V)."""
    B, E, V = x_i.shape
    R = mass.shape[-1]
    if E == 0:
        return jnp.zeros((B, 0), jnp.float32)
    _check_structured(x_i, a, w)
    shared = a.shape[0] == 1
    be = min(block_edges, E)
    e_pad = _round_up(E, be)
    x_i = _pad_axis(x_i, 1, e_pad)
    w = _pad_axis(w, 1, e_pad)
    mass = _pad_axis(mass, 1, e_pad)
    n_blocks = x_i.shape[1] // be
    rows = pl.BlockSpec((1, be, V), lambda b, e: (b, e, 0))
    out = pl.pallas_call(
        _edge_latency_structured_single_tile_kernel,
        grid=(B, n_blocks),
        in_specs=[
            rows,
            pl.BlockSpec((1, be, R), lambda b, e: (b, e, 0)),
            pl.BlockSpec((1, R, V), (lambda b, e: (0, 0, 0)) if shared
                         else (lambda b, e: (b, 0, 0))),
            rows,
        ],
        out_specs=pl.BlockSpec((1, be), lambda b, e: (b, e)),
        out_shape=jax.ShapeDtypeStruct((B, x_i.shape[1]), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x_i, mass, a, w)
    return out[:, :E]
