"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

These are deliberately naive (full materialization / sequential scans) —
clarity over speed.  tests/test_kernels.py sweeps shapes & dtypes asserting
kernel(interpret=True) ≈ oracle.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["flash_attention_ref", "ssd_ref", "rmsnorm_ref",
           "edge_latency_ref", "edge_latency_structured_ref"]


def flash_attention_ref(q, k, v, causal: bool = True):
    """q,k,v: (B, S, H, D) (kv already repeated to H).  Full softmax."""
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * (D ** -0.5)
    if causal:
        mask = jnp.tril(jnp.ones((Sq, Skv), dtype=bool), k=Skv - Sq)
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def ssd_ref(x, B, C, dt, A, D):
    """Sequential (per-token) SSD recurrence — the definitional oracle.

    x: (b, L, H, P); B, C: (b, L, N); dt: (b, L, H); A, D: (H,).
    h_t = exp(A·dt_t)·h_{t-1} + dt_t·B_t⊗x_t ;  y_t = C_t·h_t + D·x_t.
    Returns (y (b,L,H,P), final_state (b,H,N,P)).
    """
    b, L, H, Pd = x.shape
    N = B.shape[-1]
    xf = x.astype(jnp.float32)
    Bf = B.astype(jnp.float32)
    Cf = C.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)

    def step(S, t):
        decay = jnp.exp(dtf[:, t] * A[None, :])  # (b,H)
        S = decay[..., None, None] * S + jnp.einsum(
            "bN,bh,bhp->bhNp", Bf[:, t], dtf[:, t], xf[:, t])
        y = jnp.einsum("bN,bhNp->bhp", Cf[:, t], S) \
            + D[None, :, None] * xf[:, t]
        return S, y

    S0 = jnp.zeros((b, H, N, Pd), jnp.float32)
    S, ys = jax.lax.scan(step, S0, jnp.arange(L))
    return ys.swapaxes(0, 1).astype(x.dtype), S


def rmsnorm_ref(x, w, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def edge_latency_ref(x_i, x_j, com):
    """x_i, x_j: (B, E, V) (selectivity folded into x_i); com: (B, V, V).

    out[b, e] = max_u x_i[b,e,u] · Σ_v com[b,u,v] · x_j[b,e,v] — the paper's
    per-edge bilinear-max, fully materialized."""
    t = jnp.einsum("buv,bev->beu", com.astype(jnp.float32),
                   x_j.astype(jnp.float32))
    return jnp.max(x_i.astype(jnp.float32) * t, axis=-1)


def edge_latency_structured_ref(x, mass, a, w, edges=None):
    """x, w: (B, n, V) per-operator rows; mass: (B, n, R); a: (B|1, R, V);
    ``edges`` the static ``(src, dst, sel)`` edge list (None: one edge per
    row, ``src = dst``, ``sel = 1``).

    out[b, e] = max_u x[b,src,u]·sel · (mass[b,dst] @ a[b] + w[b,dst])_u —
    the structured kernel's contract, with ``t`` materialized per operator."""
    if edges is None:
        n = x.shape[1]
        edges = (np.arange(n), np.arange(n), np.ones(n))
    src, dst, sel = (np.asarray(e) for e in edges)
    t = jnp.einsum("bnr,bru->bnu", mass.astype(jnp.float32),
                   a.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST) \
        + w.astype(jnp.float32)
    x_i = x.astype(jnp.float32)[:, src] * jnp.asarray(
        sel, jnp.float32)[None, :, None]
    return jnp.max(x_i * t[:, dst], axis=-1)
