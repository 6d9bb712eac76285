"""jit'd dispatch wrappers for the Pallas kernels.

``interpret=True`` executes the kernel body in Python on CPU (correctness
validation in this container); ``interpret=False`` lowers for real TPUs.
The model layer passes ``attention_impl``/``ssm_impl`` through to here.
"""

from __future__ import annotations

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas

__all__ = ["flash_attention", "ssd_scan", "rmsnorm"]


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


def _largest_divisor_block(n: int, target: int) -> int:
    b = min(target, n)
    while n % b:
        b -= 1
    return max(b, 1)
