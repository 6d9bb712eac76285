"""Pallas TPU fused RMSNorm: one HBM read, one write per row block.

Grid over row blocks; each step loads a (rows_block, D) tile into VMEM,
reduces in f32 and writes the normalized+scaled tile.  D is kept whole in
the block (lane-dim aligned; all model widths here are multiples of 128
except none — the kernel pads rows only)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["rmsnorm_pallas"]


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    o_ref[...] = (x * inv * w[None, :]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm_pallas(x, w, eps: float = 1e-6, block_rows: int = 256,
                   interpret: bool = False):
    """x: (..., D); w: (D,)."""
    orig_shape = x.shape
    D = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, D)
    br = min(block_rows, rows)
    pad = (-rows) % br
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    n = x2.shape[0] // br
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(n,),
        in_specs=[
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x2, w)
    return out[:rows].reshape(orig_shape)
