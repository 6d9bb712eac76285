"""Backend dispatch for the edge-latency hot path: one place that decides
XLA-einsum vs Pallas, interpret vs compiled, and which block shapes.

Before this module, ``use_pallas``/``interpret`` flags were scattered across
``sim/batched.py``, ``serve/service.py``, ``search/``, and the kernel
wrappers — with DIVERGENT defaults (the serving layer defaulted
``interpret=True`` while the kernels defaulted ``interpret=False``), so a
caller could silently run interpreted kernels on an accelerator or try to
compile Pallas on CPU.  Every edge-latency consumer now routes through:

  * :func:`resolve_flags` — turns ``None`` (= "auto") flags into concrete
    booleans for the active backend: CPU → XLA einsum + interpret=True;
    accelerators → Pallas + compiled.  An EXPLICIT ``interpret=False`` on
    CPU is coerced back to True (compiled Pallas cannot lower there) and
    counted in ``repro.obs`` rather than left to crash at trace time.
  * :func:`edge_latency` / :func:`edge_latency_structured` — functional
    entry points that resolve flags, fetch a block config from
    :mod:`repro.kernels.autotune` (unless the caller pins one), and call
    either the XLA reference einsum or the blocked Pallas kernel.  The
    Pallas wrappers are module-level jits with static block args, so a
    table-stable config means zero warm recompiles.

``plan_edge_kernel`` exposes the decision itself (impl, interpret, config)
for callers that want to introspect or log it; plans are exported as
``kernels.dispatch.plans`` counter samples.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels import autotune
from repro.kernels.edge_latency import (F32_DOT, edge_latency_pallas,
                                        edge_latency_structured_pallas,
                                        edge_list)

__all__ = ["backend_name", "resolve_flags", "KernelPlan", "plan_edge_kernel",
           "edge_latency", "edge_latency_structured"]


def backend_name() -> str:
    """The active JAX backend ("cpu", "tpu", "gpu"); the dispatch policy
    keys off this, never off caller-supplied booleans alone."""
    return jax.default_backend()


def resolve_flags(use_pallas: bool | None = None,
                  interpret: bool | None = None,
                  backend: str | None = None) -> tuple[bool, bool]:
    """(use_pallas, interpret) with ``None`` meaning "auto for the backend".

    Policy: on CPU the fast path is the XLA einsum (interpreted Pallas is a
    correctness tool, not a fast path) and compiled Pallas cannot lower, so
    auto resolves to (False, True) and an explicit ``interpret=False`` is
    coerced to True.  On accelerators auto resolves to (True, False); an
    explicit ``interpret=True`` is honored (debugging) but counted."""
    if backend is None:
        backend = backend_name()
    on_cpu = backend == "cpu"
    if use_pallas is None:
        use_pallas = not on_cpu
    if interpret is None:
        interpret = on_cpu
    reg = obs.registry()
    if on_cpu and not interpret:
        if reg.enabled:
            reg.counter("kernels.dispatch.coerced", flag="interpret",
                        backend=backend).add(1)
        interpret = True
    elif not on_cpu and interpret and use_pallas:
        if reg.enabled:
            reg.counter("kernels.dispatch.interpret_on_accelerator",
                        backend=backend).add(1)
    return bool(use_pallas), bool(interpret)


@dataclasses.dataclass(frozen=True)
class KernelPlan:
    """One resolved dispatch decision for an edge-latency shape."""

    impl: str                             # "pallas" | "xla"
    interpret: bool
    config: autotune.KernelConfig | None  # None for the XLA route


def plan_edge_kernel(kind: str, B: int, E: int, V: int, R: int | None = None,
                     *, use_pallas: bool | None = None,
                     interpret: bool | None = None,
                     backend: str | None = None, com_batch: int = 1,
                     block_edges: int | None = None,
                     block_v: int | None = None,
                     n_ops: int | None = None) -> KernelPlan:
    """Resolve flags and block shapes for one shape.  Caller-pinned blocks
    bypass the autotuner; otherwise the decision table supplies them,
    ranked with the peaks of this process's device kind."""
    if backend is None:
        backend = backend_name()
    use_pallas_r, interpret_r = resolve_flags(use_pallas, interpret, backend)
    if not use_pallas_r:
        plan = KernelPlan(impl="xla", interpret=interpret_r, config=None)
    elif block_edges is not None or block_v is not None:
        dflt = autotune.DEFAULT_CONFIG
        cfg = autotune.KernelConfig(
            block_edges=block_edges if block_edges is not None
            else dflt.block_edges,
            block_v=block_v if block_v is not None else dflt.block_v)
        plan = KernelPlan(impl="pallas", interpret=interpret_r, config=cfg)
    else:
        cfg = autotune.get_config(kind, B, E, V, R, com_batch=com_batch,
                                  backend=backend, n_ops=n_ops)
        plan = KernelPlan(impl="pallas", interpret=interpret_r, config=cfg)
    reg = obs.registry()
    if reg.enabled:
        reg.counter("kernels.dispatch.plans", kind=kind, impl=plan.impl,
                    interpret=str(plan.interpret)).add(1)
    return plan


def _edge_latency_xla(x_i, x_j, com):
    # com may be (1, V, V) shared across the B placement rows — einsum
    # broadcasting handles both batch layouts without materializing copies
    t = jnp.einsum("buv,bev->beu", com.astype(jnp.float32),
                   x_j.astype(jnp.float32))
    return jnp.max(x_i.astype(jnp.float32) * t, axis=-1)


def _edge_latency_structured_xla(x, mass, a, w, edges):
    src, dst, sel = (np.asarray(e) for e in edges)
    t = jnp.einsum("bnr,bru->bnu", mass.astype(jnp.float32),
                   a.astype(jnp.float32), precision=F32_DOT) \
        + w.astype(jnp.float32)
    x_i = x.astype(jnp.float32)[:, src] * jnp.asarray(
        sel, jnp.float32)[None, :, None]
    return jnp.max(x_i * t[:, dst], axis=-1)


def edge_latency(x_i, x_j, com, *, use_pallas: bool | None = None,
                 interpret: bool | None = None, backend: str | None = None,
                 block_edges: int | None = None, block_v: int | None = None):
    """Dense edge-latency max through the dispatch policy: (B, E, V) rows ×
    (B|1, V, V) com → (B, E).  Auto flags pick the backend-appropriate
    route; block shapes come from the autotune table unless pinned."""
    B, E, V = x_i.shape
    if E == 0:
        return jnp.zeros((B, 0), jnp.float32)
    plan = plan_edge_kernel("dense", B, E, V, use_pallas=use_pallas,
                            interpret=interpret, backend=backend,
                            com_batch=com.shape[0], block_edges=block_edges,
                            block_v=block_v)
    if plan.impl == "xla":
        return _edge_latency_xla(x_i, x_j, com)
    return edge_latency_pallas(x_i, x_j, com,
                               block_edges=plan.config.block_edges,
                               block_v=plan.config.block_v,
                               interpret=plan.interpret)


def edge_latency_structured(x, mass, a, w, edges=None, *,
                            use_pallas: bool | None = None,
                            interpret: bool | None = None,
                            backend: str | None = None,
                            block_v: int | None = None):
    """Structured (RegionFleet) edge-latency max through the dispatch
    policy: per-operator rows x, w (B, n_ops, V) and masses (B, n_ops, R),
    ``a`` (B|1, R, V), and the graph's static :func:`edge_list` → (B, E)
    ``max_u (x[src]·sel)·(mass @ a + w)[dst]`` (see kernels/edge_latency);
    ``edges=None`` is one edge per row, ``src = dst``, ``sel = 1``."""
    B, n_ops, V = x.shape
    if edges is None:
        edges = edge_list(range(n_ops), range(n_ops), [1.0] * n_ops)
    E = len(edges[0])
    if E == 0:
        return jnp.zeros((B, 0), jnp.float32)
    plan = plan_edge_kernel("structured", B, E, V, mass.shape[-1],
                            use_pallas=use_pallas, interpret=interpret,
                            backend=backend, com_batch=a.shape[0],
                            block_v=block_v, n_ops=n_ops)
    if plan.impl == "xla":
        return _edge_latency_structured_xla(x, mass, a, w, edges)
    return edge_latency_structured_pallas(
        x, mass, a, w, edges, block_v=plan.config.block_v,
        interpret=plan.interpret)
