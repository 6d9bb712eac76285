"""Logical-axis sharding: model code names axes, the launcher maps them.

Model code annotates params/activations with *logical* axes ("batch",
"vocab", "heads", "ff", …).  An :class:`AxisRules` maps logical → mesh axes
and is swappable per experiment — this is the lever the §Perf hillclimbs
turn (e.g. "shard vocab over model" vs "replicate", sequence parallelism on
or off) without touching model code.

Outside a mesh context everything degrades to a no-op so the same model code
runs single-device in smoke tests.
"""

from __future__ import annotations

import dataclasses
import threading

import jax
from jax.sharding import PartitionSpec as P

__all__ = ["AxisRules", "DEFAULT_RULES", "axis_rules", "set_axis_rules",
           "logical_spec", "shard", "param_spec", "constrain_tree",
           "fsdp_leaf_spec"]

MeshAxes = tuple[str, ...] | str | None


@dataclasses.dataclass(frozen=True)
class AxisRules:
    """logical axis name → mesh axis (or tuple, or None=replicate)."""

    rules: dict[str, MeshAxes]

    def resolve(self, *logical: str | None, mesh: jax.sharding.Mesh | None = None) -> P:
        """PartitionSpec for the given logical axes, dropping mesh axes that
        don't exist on the active mesh (so ('pod','data') batch rules work on
        single-pod meshes too)."""
        mesh = mesh or _active_mesh()
        present = set(mesh.axis_names) if mesh is not None else set()
        out = []
        for name in logical:
            target = self.rules.get(name) if name else None
            if target is None:
                out.append(None)
                continue
            if isinstance(target, str):
                target = (target,)
            kept = tuple(a for a in target if a in present)
            out.append(kept if len(kept) > 1 else (kept[0] if kept else None))
        return P(*out)


DEFAULT_RULES = AxisRules({
    # activations
    "batch": ("pod", "data"),
    "seq": None,          # flip to "model" for sequence parallelism
    "embed": None,
    # params
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",   # replicated automatically when not divisible
    "ff": "model",
    "experts": "model",
    "inner": "model",      # mamba2 d_inner / conv channels
    "state": None,
    "layers": None,
})

_local = threading.local()


def set_axis_rules(rules: AxisRules):
    _local.rules = rules


def axis_rules() -> AxisRules:
    return getattr(_local, "rules", DEFAULT_RULES)


def _active_mesh() -> jax.sharding.AbstractMesh | None:
    """The context mesh ``use_mesh`` installed, or None outside one."""
    m = jax.sharding.get_abstract_mesh()
    if m is None or m.empty:
        return None
    return m


def logical_spec(*logical: str | None) -> P:
    return axis_rules().resolve(*logical)


def shard(x, *logical: str | None):
    """with_sharding_constraint by logical axes; no-op without a mesh."""
    mesh = _active_mesh()
    if mesh is None:
        return x
    spec = axis_rules().resolve(*logical, mesh=mesh)
    return jax.lax.with_sharding_constraint(x, spec)


def shard_div(x, logical: tuple[str | None, ...]):
    """Like :func:`shard` but SKIPS the whole constraint if any requested
    axis doesn't divide its dimension.  Pinning a non-divisible dim would
    constrain it to *replicated* — for 56-head attention that forces 16×
    redundant compute; leaving it unconstrained lets GSPMD pick a padded
    sharding instead (§Perf iteration 5)."""
    mesh = _active_mesh()
    if mesh is None:
        return x
    requested = axis_rules().resolve(*logical, mesh=mesh)
    achieved = param_spec(logical, tuple(x.shape), mesh=mesh)
    if tuple(requested) != tuple(achieved):
        return x
    return jax.lax.with_sharding_constraint(x, achieved)


FSDP_AXIS = "data"
FSDP_MIN_ELEMS = 1 << 20


def fsdp_leaf_spec(spec: P, shape: tuple[int, ...],
                   mesh=None, axis: str = FSDP_AXIS,
                   min_elems: int = FSDP_MIN_ELEMS) -> P:
    """ZeRO-3 via GSPMD: add `axis` to the largest replicated, divisible dim
    of a big leaf (shared by launch.shardings.fsdp_specs and the in-body
    constraint below)."""
    mesh = mesh or _active_mesh()
    if mesh is None or axis not in mesh.axis_names:
        return spec
    ways = dict(mesh.shape)[axis]
    n = 1
    for s in shape:
        n *= s
    if n < min_elems:
        return spec
    entries = list(spec) + [None] * (len(shape) - len(spec))
    used = {a for e in entries if e is not None
            for a in ((e,) if isinstance(e, str) else e)}
    if axis in used:
        return P(*entries)
    best, best_dim = -1, -1
    for d, e in enumerate(entries):
        if e is None and shape[d] % ways == 0 and shape[d] > best:
            best, best_dim = shape[d], d
    if best_dim < 0:
        return P(*entries)
    entries[best_dim] = axis
    return P(*entries)


def constrain_tree(params, spec_tree, fsdp: bool = True):
    """with_sharding_constraint over a params subtree (no-op without mesh).

    Applied at the TOP of every scanned block body: it pins the per-layer
    slice to its intended (FSDP) sharding so GSPMD's propagation cannot pull
    the body's gathered layout out onto the full stacked (L, …) tensor —
    without this, a 35-layer MoE stack all-gathers 3×19.5 GB per device
    (EXPERIMENTS.md §Dry-run notes)."""
    mesh = _active_mesh()
    if mesh is None:
        return params

    def leaf(x, spec):
        if not isinstance(spec, P) or not hasattr(x, "ndim"):
            return x
        if fsdp:
            spec = fsdp_leaf_spec(spec, x.shape, mesh)
        return jax.lax.with_sharding_constraint(x, spec)

    return jax.tree.map(leaf, params, spec_tree,
                        is_leaf=lambda s: isinstance(s, P))


def param_spec(shape_logical: tuple[str | None, ...],
               divisibility: tuple[int, ...] | None = None,
               mesh: jax.sharding.Mesh | None = None) -> P:
    """Spec for a parameter; if ``divisibility`` is given, axes whose size
    does not divide by the mesh-axis size are replicated instead (e.g. 56
    query heads on model=16 still shard — GSPMD pads — but 8 kv heads on
    model=16 replicate, the Megatron kv-replication scheme)."""
    rules = axis_rules()
    mesh = mesh or _active_mesh()
    spec = list(rules.resolve(*shape_logical, mesh=mesh))
    if divisibility is not None and mesh is not None:
        sizes = dict(mesh.shape)
        for k, (target, dim) in enumerate(zip(spec, divisibility)):
            if target is None or dim <= 0:
                continue
            axes = (target,) if isinstance(target, str) else target
            total = 1
            for a in axes:
                total *= sizes.get(a, 1)
            if dim % total != 0:
                spec[k] = None
    return P(*spec)
