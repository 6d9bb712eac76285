"""Pallas TPU kernel for the structured path's region sums.

``repro.core.jaxmodel.region_terms`` sums each placement row's
degrade-weighted mass over every region twice per scenario.  On a TPU a
scatter-add over V = 131 072 devices costs about as much per call
whatever the number of rows, and an XLA matmul with the layout's one-hot
is fast but blocks its sum over V by the number of rows, so a row's bits
would depend on the rows batched with it (the served path's answers must
equal direct scoring bit for bit).  This kernel contracts fixed
(128 rows × ``BLOCK_V`` devices) tiles of the rows with the (R, V)
one-hot at ``Precision.HIGHEST`` and accumulates over V in one fixed
order, so every row is summed the same way whatever batch it rides in.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.edge_latency import F32_DOT, LANE, SUBLANE, _pad_axis

__all__ = ["BLOCK_ROWS", "BLOCK_V", "region_sum_pallas"]

BLOCK_ROWS = LANE   # rows per tile, whatever the batch: fixed for the bits
BLOCK_V = 2048      # devices per tile of the contraction


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _region_sum_kernel(onehot_ref, rows_ref, o_ref):
    k = pl.program_id(1)
    # (R, bv) · (bm, bv)ᵀ → (R, bm): one column per row
    part = jax.lax.dot_general(onehot_ref[...], rows_ref[...],
                               (((1,), (1,)), ((), ())), precision=F32_DOT,
                               preferred_element_type=jnp.float32)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = part

    @pl.when(k > 0)
    def _accumulate():
        o_ref[...] += part


@functools.partial(jax.jit, static_argnames=("interpret",))
def region_sum_pallas(v, onehot_t, interpret: bool = False):
    """v: (..., V); onehot_t: (R, V), 1 where device v is in region r →
    (..., R) float32 region sums of every row of ``v``."""
    lead, V = v.shape[:-1], v.shape[-1]
    R = onehot_t.shape[0]
    rows = v.reshape(-1, V).astype(jnp.float32)
    M = rows.shape[0]
    bv = min(BLOCK_V, _round_up(V, LANE))
    m_pad, v_pad = _round_up(max(M, 1), BLOCK_ROWS), _round_up(V, bv)
    r_pad = _round_up(R, SUBLANE)
    rows = _pad_axis(_pad_axis(rows, 0, m_pad), 1, v_pad)
    onehot_t = _pad_axis(_pad_axis(onehot_t.astype(jnp.float32), 0, r_pad),
                         1, v_pad)
    out = pl.pallas_call(
        _region_sum_kernel,
        grid=(m_pad // BLOCK_ROWS, v_pad // bv),
        in_specs=[pl.BlockSpec((r_pad, bv), lambda m, k: (0, k)),
                  pl.BlockSpec((BLOCK_ROWS, bv), lambda m, k: (m, k))],
        out_specs=pl.BlockSpec((r_pad, BLOCK_ROWS), lambda m, k: (0, m)),
        out_shape=jax.ShapeDtypeStruct((r_pad, m_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(onehot_t, rows)
    return out[:R, :M].T.reshape(lead + (R,))
