"""Backend dispatch for the edge-latency hot path: the one place that
decides the route, from the backend it observes, and the block shapes.

  * :func:`route` — what the backend gives.  On the CPU the evaluator
    scores with its ``core/jaxmodel`` twin and any Pallas call runs
    interpreted; on an accelerator the edge kernels run compiled.  No
    caller chooses: tests that want interpreted Pallas on the CPU
    substitute this function (``tests/conftest.py``).
  * :func:`edge_latency` / :func:`edge_latency_structured` — call the
    blocked Pallas kernels, interpreted as the route says, with a block
    config from :mod:`repro.kernels.autotune`.  The Pallas wrappers are
    module-level jits with static block args, so a table-stable config
    means zero warm recompiles.

``plan_edge_kernel`` exposes the decision itself (interpret, config) for
callers that want to introspect or log it; plans are exported as
``kernels.dispatch.plans`` counter samples.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import autotune
from repro.kernels.edge_latency import (edge_latency_pallas,
                                        edge_latency_structured_pallas,
                                        edge_list)

__all__ = ["backend_name", "Route", "route", "KernelPlan",
           "plan_edge_kernel", "edge_latency", "edge_latency_structured"]


def backend_name() -> str:
    """The active JAX backend ("cpu", "tpu", "gpu"); the route keys off
    this alone."""
    return jax.default_backend()


@dataclasses.dataclass(frozen=True)
class Route:
    """How the edge latencies are scored in this process."""

    pallas: bool     # the evaluator scores through the Pallas kernels
    interpret: bool  # Pallas calls run in interpret mode


def route() -> Route:
    """The backend's route: on the CPU the jaxmodel twin, with any Pallas
    call interpreted (compiled Pallas cannot lower there); on an
    accelerator compiled Pallas."""
    on_cpu = backend_name() == "cpu"
    return Route(pallas=not on_cpu, interpret=on_cpu)


@dataclasses.dataclass(frozen=True)
class KernelPlan:
    """One resolved dispatch decision for an edge-latency shape."""

    interpret: bool
    config: autotune.KernelConfig


def plan_edge_kernel(kind: str, B: int, E: int, V: int, R: int | None = None,
                     *, com_batch: int = 1,
                     n_ops: int | None = None) -> KernelPlan:
    """The route's interpret flag and the decision table's block shapes
    for one shape, ranked with the peaks of this process's device kind."""
    cfg = autotune.get_config(kind, B, E, V, R, com_batch=com_batch,
                              backend=backend_name(), n_ops=n_ops)
    plan = KernelPlan(interpret=route().interpret, config=cfg)
    reg = obs.registry()
    if reg.enabled:
        reg.counter("kernels.dispatch.plans", kind=kind,
                    interpret=str(plan.interpret)).add(1)
    return plan


def edge_latency(x_i, x_j, com):
    """Dense edge-latency max on the blocked Pallas kernel: (B, E, V) rows
    × (B|1, V, V) com → (B, E)."""
    B, E, V = x_i.shape
    if E == 0:
        return jnp.zeros((B, 0), jnp.float32)
    plan = plan_edge_kernel("dense", B, E, V, com_batch=com.shape[0])
    return edge_latency_pallas(x_i, x_j, com,
                               block_edges=plan.config.block_edges,
                               block_v=plan.config.block_v,
                               interpret=plan.interpret)


def edge_latency_structured(x, mass, a, w, edges=None):
    """Structured (RegionFleet) edge-latency max on the blocked Pallas
    kernel: per-operator rows x, w (B, n_ops, V) and masses (B, n_ops, R),
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
                            com_batch=a.shape[0], n_ops=n_ops)
    return edge_latency_structured_pallas(
        x, mass, a, w, edges, block_v=plan.config.block_v,
        interpret=plan.interpret)
