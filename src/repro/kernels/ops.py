"""jit'd dispatch wrappers for the Pallas kernels.

``interpret=True`` executes the kernel body in Python on CPU (correctness
validation in this container); ``interpret=False`` lowers for real TPUs.
The model layer passes ``attention_impl``/``ssm_impl`` through to here.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import dispatch
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas

__all__ = ["flash_attention", "ssd_scan", "rmsnorm", "edge_latency_max",
           "edge_latency_structured_max"]


def flash_attention(q, k, v, causal: bool = True, interpret: bool = False,
                    bq: int = 128, bk: int = 128):
    """(B, S, H, D) attention; kv repeated to H (GQA handled by caller)."""
    Sq = q.shape[1]
    bq = _largest_divisor_block(Sq, bq)
    bk = _largest_divisor_block(k.shape[1], bk)
    return flash_attention_pallas(q, k, v, causal=causal, bq=bq, bk=bk,
                                  interpret=interpret)


def ssd_scan(x, B, C, dt, A, D, chunk: int = 128, head_block: int = 8,
             interpret: bool = False):
    chunk = _largest_divisor_block(x.shape[1], chunk)
    head_block = _largest_divisor_block(x.shape[2], head_block)
    return ssd_scan_pallas(x, B, C, dt, A, D, chunk=chunk,
                           head_block=head_block, interpret=interpret)


def rmsnorm(x, w, eps: float = 1e-6, interpret: bool = False):
    return rmsnorm_pallas(x, w, eps=eps, interpret=interpret)


def edge_latency_max(x_i, x_j, com, interpret: bool | None = None,
                     block_edges: int | None = None,
                     block_v: int | None = None):
    """(B, E) fused ``max_u x_i·(com @ x_j)`` on the Pallas route — see
    kernels/edge_latency.py.  ``interpret=None`` resolves per backend via
    :mod:`repro.kernels.dispatch`; block shapes come from the autotune
    table unless pinned.  No divisor shrinking: the kernel pads E up to the
    block size, so a prime E still runs full tiles."""
    return dispatch.edge_latency(x_i, x_j, com, use_pallas=True,
                                 interpret=interpret,
                                 block_edges=block_edges, block_v=block_v)


def edge_latency_structured_max(x_i, mass, a, w,
                                interpret: bool | None = None,
                                block_v: int | None = None):
    """(B, E) structured edge-latency max over precomputed region masses,
    one edge per row — the RegionFleetFamily hot path
    (kernels/edge_latency.py), dispatched like :func:`edge_latency_max`."""
    return dispatch.edge_latency_structured(
        x_i, mass, a, w, use_pallas=True, interpret=interpret,
        block_v=block_v)


def _largest_divisor_block(n: int, target: int) -> int:
    b = min(target, n)
    while n % b:
        b -= 1
    return max(b, 1)
