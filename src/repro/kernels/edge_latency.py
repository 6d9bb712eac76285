"""Pallas TPU kernels for the cost model's hot edge-latency reduction.

The paper's edge latency (§3) is, per edge ``i→j`` with placement rows
``x_i``/``x_j`` and communication matrix ``com``:

    edgeLat = max_u  x_{i,u} · s_i · Σ_v com_{u,v} · x_{j,v}

The batched what-if evaluator (repro.sim.batched) scores (scenario ×
placement) grids.  The DENSE reduction runs over a (B, E, V) tensor of
gathered edge endpoint rows against a (B, V, V) tensor of per-scenario com
matrices — a fused matvec + row-max that dominates evaluation time once
B·E·V² grows; selectivity is folded into ``x_i`` by the caller.  The
STRUCTURED reduction takes per-operator rows and the graph's static edge
list, and gathers rows to edges itself.

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
  * the STRUCTURED kernel (RegionFleetFamily: ``T = mass @ A + w`` per
    operator, R ≪ V) runs a (B/rb, V/bv) grid over (rb·n_ops, bv) tiles
    of the rows ``x`` and ``w`` of rb ≤ 8 placement rows: each step forms
    T for every (row, operator), reads each edge's two operators for all
    rb rows by sublane-strided loads, and folds the row max over u-tiles,
    so no per-edge copy of a V-sized row exists;
  * both kernels write lane-dense outputs: Mosaic requires an output
    block's last two dims to be (8, 128)-aligned or whole, which a
    (1, be) block over a (B, e_pad) array is not.  The dense kernel's
    (B, e_pad, LANE) holds the edge's running max in every lane, and the
    wrapper keeps lane 0; the structured kernel's (E, B, LANE) holds a
    running max per lane, and the wrapper takes the max over lanes.

The dispatch layer (:mod:`repro.kernels.dispatch`) calls these kernels,
compiled on an accelerator and interpreted on the CPU, with block shapes
from :mod:`repro.kernels.autotune`; the single-tile kernels the blocked ones
replaced are kept as ``*_single_tile`` parity references — at small V the
blocked kernels reproduce them bitwise (gated in tests/test_kernel_blocking).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["LANE", "SUBLANE", "BlockGeometry", "block_geometry",
           "edge_list", "structured_row_block",
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

    be: int           # edge-block rows (dense: ≤ padded E, multiple of
                      # SUBLANE; structured: E)
    bv: int           # V-block width (≤ padded V, multiple of LANE;
                      # structured: of SUBLANE·LANE, or all of V)
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
    larger than the padded axis shrinks to it).  ``block_edges`` shapes
    the dense kernel only: the structured one holds every edge in one
    block and pads no edge axis."""
    if kind not in ("dense", "structured"):
        raise ValueError(f"kind must be dense|structured, got {kind!r}")
    if E < 1 or V < 1:
        raise ValueError(f"need E >= 1 and V >= 1, got E={E}, V={V}")
    # the structured kernel views a V tile as (bv/LANE, LANE): a whole
    # number of sublane tiles, or all of V
    v_tile = LANE if kind == "dense" else SUBLANE * LANE
    bv = min(_round_up(max(1, block_v), v_tile), _round_up(V, LANE))
    v_pad = _round_up(V, bv)
    if kind == "dense":
        be = min(_round_up(max(1, block_edges), SUBLANE),
                 _round_up(E, SUBLANE))
    else:  # every edge in one unpadded block, applied in VMEM
        be = E
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
#   T[o, u] = Σ_r A[r, u] · mass[o, r]  +  w[o, u]
#   A[r, u] = degrade_u · inter[region_u, r], 0 in u's own region (R, V)
#   mass[o, r] = Σ_{v ∈ region r} degrade_v · x[o, v]              (n_ops, R)
#   w[o, u] = u's own-region transfer over the region's other devices,
#             plus its transfer to itself                          (n_ops, V)
#
# per destination OPERATOR o, and edge i→j scores max_u (x[i]·s)·T[j].
# The kernel takes the per-operator rows and the DAG's static edge list and
# applies the edges itself, so no per-edge (E, V) copy is ever written.
#
# grid = (B/rb, V/bv) over blocks of rb ≤ 8 placement rows.  The rows of a
# block arrive as (rb·n_ops, bv) tiles in (row, operator) order.  Each step
# parks x and T = mass @ a + w for every (row, operator) in VMEM by lane
# chunk — one (rb·n_ops, Rp) @ (Rp, LANE) product a chunk, R ≪ V — and
# then, per edge and lane chunk, reads operator i's x and operator j's T
# for all rb rows with one sublane-strided load each: an (rb, LANE) tile,
# every sublane a placement row, where a (1, bv) row slice would fill one
# sublane of eight.  The running max over u-tiles folds into a lane-dense
# (E, rb, LANE) output block; the wrapper takes the max over lanes.  R
# pads to the lane width (zero rows of mass/A add exact zeros); a shared
# (Bc = 1) A tile is read once per row block.


def edge_list(src, dst, sel) -> tuple:
    """A graph's static edge list as the structured kernels take it:
    ``(src, dst, sel)`` tuples of operator indices and float32
    selectivities (hashable, so it can be a static jit argument)."""
    return (tuple(int(i) for i in src), tuple(int(j) for j in dst),
            tuple(float(s) for s in np.asarray(sel, np.float32)))


def structured_row_block(B: int) -> int:
    """rb, the placement rows of one structured row block: one sublane
    tile of rows, or all B when fewer.  B pads up to a multiple of it."""
    return min(B, SUBLANE)


def _check_structured(x, mass, a, w, edges):
    B, n = x.shape[:2]
    if a.shape[0] not in (1, B):
        raise ValueError(f"scenario batch dim {a.shape[0]} must be 1 or {B}")
    if w.shape != x.shape:
        raise ValueError(f"w has shape {w.shape}, want {x.shape}")
    if mass.shape[:2] != (B, n):
        raise ValueError(f"mass has shape {mass.shape}, want ({B}, {n}, R)")
    src, dst, sel = edges
    if not len(src) == len(dst) == len(sel):
        raise ValueError("edge list: src, dst and sel differ in length")
    if any(not 0 <= o < n for o in src + dst):
        raise ValueError(f"edge list names an operator outside 0..{n - 1}")


def _edge_latency_structured_blocked_kernel(v_real: int, n_ops: int, edges,
                                            x_ref, mass_ref, a_ref, w_ref,
                                            o_ref, x_k, t_k):
    u = pl.program_id(1)
    n_k, rb = t_k.shape[0], o_ref.shape[1]
    mass = mass_ref[...].astype(jnp.float32)   # (rb·n_ops, Rp)
    # x and T = mass @ a + w by lane chunk: a strided load needs a
    # LANE-wide ref
    for k in range(n_k):
        lanes = slice(k * LANE, (k + 1) * LANE)
        x_k[k] = x_ref[:, lanes].astype(jnp.float32)
        t_k[k] = jax.lax.dot_general(
            mass, a_ref[:, lanes].astype(jnp.float32),
            (((1,), (0,)), ((), ())), precision=F32_DOT,
            preferred_element_type=jnp.float32) \
            + w_ref[:, lanes].astype(jnp.float32)
    masked = v_real % (n_k * LANE) != 0

    @pl.when(u == 0)
    def _init():
        o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, jnp.float32)

    def chunk(k, carry):
        if masked:
            lane = (u * n_k + k) * LANE + jax.lax.broadcasted_iota(
                jnp.int32, o_ref.shape[1:], 1)
        for e, (i, j, s) in enumerate(zip(*edges)):
            # x·s first, then x_i·(t + w): the gathered route's order
            x_i = x_k[k, pl.ds(i, rb, stride=n_ops), :] * s
            vals = x_i * t_k[k, pl.ds(j, rb, stride=n_ops), :]
            if masked:
                vals = jnp.where(lane < v_real, vals, -jnp.inf)
            o_ref[e] = jnp.maximum(o_ref[e], vals)
        return carry

    # rolled over the lane chunks: the edges' unrolled body is traced and
    # lowered once per program, not once per chunk
    jax.lax.fori_loop(0, n_k, chunk, 0)


def _structured_shared(x, mass, a, w, edges, block_v, interpret):
    """The kernel for one scenario shared by all B rows: a (1, R, V)."""
    B, n_ops, V = x.shape
    R = mass.shape[-1]
    E = len(edges[0])
    g = block_geometry("structured", E, V, R, E, block_v)
    rb = structured_row_block(B)
    b_pad = -(-B // rb) * rb
    x = _pad_axis(_pad_axis(x, 2, g.v_pad), 0, b_pad)
    w = _pad_axis(_pad_axis(w, 2, g.v_pad), 0, b_pad)
    mass = _pad_axis(_pad_axis(mass, 2, g.r_pad), 0, b_pad)
    a = _pad_axis(_pad_axis(a[0], 1, g.v_pad), 0, g.r_pad)
    rows = rb * n_ops
    tile = pl.BlockSpec((rows, g.bv), lambda i, u: (i, u))
    chunks = pltpu.VMEM((g.bv // LANE, rows, LANE), jnp.float32)
    out = pl.pallas_call(
        functools.partial(_edge_latency_structured_blocked_kernel, V, n_ops,
                          edges),
        grid=(b_pad // rb, g.n_u),
        in_specs=[
            tile,
            pl.BlockSpec((rows, g.r_pad), lambda i, u: (i, 0)),
            pl.BlockSpec((g.r_pad, g.bv), lambda i, u: (0, u)),
            tile,
        ],
        out_specs=pl.BlockSpec((E, rb, LANE), lambda i, u: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((E, b_pad, LANE), jnp.float32),
        scratch_shapes=[chunks, chunks],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x.reshape(b_pad * n_ops, g.v_pad), mass.reshape(b_pad * n_ops, g.r_pad),
      a, w.reshape(b_pad * n_ops, g.v_pad))
    return jnp.max(out[:, :B], axis=-1).T


@functools.partial(jax.jit, static_argnames=("edges", "block_v",
                                             "interpret"))
def edge_latency_structured_pallas(x, mass, a, w, edges=None,
                                   block_v: int = 2048,
                                   interpret: bool = False):
    """x, w: (B, n_ops, V); mass: (B, n_ops, R); a: (Bc, R, V) with
    Bc ∈ {1, B}; ``edges`` the static :func:`edge_list` ``(src, dst, sel)``
    → (B, E) latencies ``max_u (x[src]·sel)·(mass @ a + w)[dst]``.

    ``edges=None`` is the per-edge special case ``src = dst = range(E)``,
    ``sel = 1``: rows already gathered to edges (multiplying by 1.0 is
    exact).  V-blocked over the u axis with a running max (module
    docstring); R pads to the lane width with exact-zero rows.  A
    singleton scenario batch (Bc == 1) is shared by all B placement rows
    via the index map; a per-row one (Bc == B) maps the kernel over rows."""
    B, n_ops, V = x.shape
    if edges is None:
        edges = edge_list(range(n_ops), range(n_ops), [1.0] * n_ops)
    if len(edges[0]) == 0:
        return jnp.zeros((B, 0), jnp.float32)
    _check_structured(x, mass, a, w, edges)
    if a.shape[0] == 1:
        return _structured_shared(x, mass, a, w, edges, block_v, interpret)
    return jax.lax.map(
        lambda r: _structured_shared(r[0][None], r[1][None], r[2][None],
                                     r[3][None], edges, block_v,
                                     interpret)[0],
        (x, mass, a, w))


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
    _check_structured(x_i, mass, a, w,
                      edge_list(range(E), range(E), [1.0] * E))
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
