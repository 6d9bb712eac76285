"""Pallas TPU kernels for the compute hot-spots, each with a pure-jnp
oracle in ref.py; validated with interpret=True on CPU.

The edge-latency hot path is V-blocked for compiled execution and routed
through :mod:`repro.kernels.dispatch`, which decides from the backend
whether the kernels run interpreted or compiled and picks autotuned block
shapes — see kernels/README.md.
"""

from repro.kernels.autotune import (DEFAULT_CONFIG, KernelConfig, ShapeKey,
                                    get_config)
from repro.kernels.dispatch import (KernelPlan, backend_name, edge_latency,
                                    edge_latency_structured, plan_edge_kernel)
from repro.kernels.edge_latency import (LANE, SUBLANE, BlockGeometry,
                                        block_geometry)

__all__ = [
    "LANE", "SUBLANE", "BlockGeometry", "block_geometry",
    "KernelConfig", "ShapeKey", "DEFAULT_CONFIG", "get_config",
    "KernelPlan", "backend_name", "plan_edge_kernel",
    "edge_latency", "edge_latency_structured",
]
