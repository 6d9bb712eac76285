"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

Grid: (batch, head_block, n_chunks) — batch and head blocks are parallel;
the chunk axis is the innermost *arbitrary* (sequential) dimension so the
inter-chunk SSM state (Hb, N, P) persists in VMEM scratch between steps,
exactly the TPU analogue of the paper's chunked state-passing algorithm
(DESIGN.md: HBM→VMEM streaming replaces the GPU SRAM tiling of the official
Triton kernel).

Per chunk the quadratic intra-chunk form runs on the MXU:
  CB (Q×Q) ← C·Bᵀ; masked/decayed; Y ← M·X  — all f32 accumulation.
VMEM per step ≈ (3·Q·N + Q·Hb·(2P+2) + Q² + Hb·N·P)·4B; at Q=128, N=128,
Hb=8, P=64 that is ≈ 0.9 MB — comfortably inside the ~16 MB v5e VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["ssd_scan_pallas"]


def _ssd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, d_ref, y_ref, state_ref,
                *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)    # (Q, Hb, P)
    B = b_ref[0, 0].astype(jnp.float32)    # (Q, N)
    C = c_ref[0, 0].astype(jnp.float32)    # (Q, N)
    dt = dt_ref[0, 0].astype(jnp.float32)  # (Q, Hb)
    A = a_ref[0].astype(jnp.float32)       # (Hb,)
    D = d_ref[0].astype(jnp.float32)       # (Hb,)
    Q = x.shape[0]

    dtA = dt * A[None, :]                        # (Q, Hb)
    cum = jnp.cumsum(dtA, axis=0)                # (Q, Hb)
    total = cum[-1, :]                           # (Hb,)
    CB = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, Q)
    decay = jnp.exp(cum[:, None, :] - cum[None, :, :])  # (i, j, Hb)
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    mask = (jj <= ii)[:, :, None]
    M = CB[:, :, None] * jnp.where(mask, decay, 0.0) * dt[None, :, :]  # (i,j,Hb)
    y = jnp.einsum("ijh,jhp->ihp", M, x)         # intra-chunk
    # inter-chunk: contribution of carried state
    S = state_ref[...]                            # (Hb, N, P)
    y += jnp.einsum("iN,hNp->ihp", C, S) * jnp.exp(cum)[..., None]
    y += D[None, :, None] * x
    # state update
    w = jnp.exp(total[None, :] - cum) * dt        # (Q, Hb)
    state_ref[...] = jnp.exp(total)[:, None, None] * S + jnp.einsum(
        "jN,jh,jhp->hNp", B, w, x)
    y_ref[0, 0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "head_block", "interpret"))
def ssd_scan_pallas(x, B, C, dt, A, D, chunk: int = 128,
                    head_block: int = 8, interpret: bool = False):
    """x: (b, L, H, P); B, C: (b, L, N); dt: (b, L, H); A, D: (H,).

    Returns y (b, L, H, P).  L must divide by ``chunk``, H by ``head_block``.
    """
    b, L, H, Pd = x.shape
    N = B.shape[-1]
    chunk = min(chunk, L)
    head_block = min(head_block, H)
    if L % chunk or H % head_block:
        raise ValueError(f"L={L} % chunk={chunk} or H={H} % hb={head_block}")
    n = L // chunk
    nh = H // head_block
    # (b, n, Q, …) chunked layouts
    xc = x.reshape(b, n, chunk, H, Pd)
    Bc = B.reshape(b, n, chunk, N)
    Cc = C.reshape(b, n, chunk, N)
    dtc = dt.reshape(b, n, chunk, H)
    Ab = jnp.broadcast_to(A[None], (1, H))
    Db = jnp.broadcast_to(D[None], (1, H))
    grid = (b, nh, n)
    out = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, head_block, Pd),
                         lambda bi, hi, ci: (bi, ci, 0, hi, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda bi, hi, ci: (bi, ci, 0, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda bi, hi, ci: (bi, ci, 0, 0)),
            pl.BlockSpec((1, 1, chunk, head_block),
                         lambda bi, hi, ci: (bi, ci, 0, hi)),
            pl.BlockSpec((1, head_block), lambda bi, hi, ci: (0, hi)),
            pl.BlockSpec((1, head_block), lambda bi, hi, ci: (0, hi)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, head_block, Pd),
                               lambda bi, hi, ci: (bi, ci, 0, hi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n, chunk, H, Pd), x.dtype),
        scratch_shapes=[pltpu.VMEM((head_block, N, Pd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xc, Bc, Cc, dtc, Ab, Db)
    return out.reshape(b, L, H, Pd)
