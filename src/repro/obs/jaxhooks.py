"""Recompile and compile-time accounting via ``jax.monitoring``.

JAX fires a ``/jax/core/compile/backend_compile_duration`` event for
every executable it obtains from the backend — including the silent
retraces a shape-bucket miss triggers in chunked ``score_batch``, and
including executables loaded from the persistent compilation cache, which
fire ``/jax/compilation_cache/cache_hits`` first — and nothing at all for
in-memory cache hits.  Module-level listeners (installed lazily, at most
once, and kept registered behind an armed flag) turn those events into:

  * module-level totals (``compile_count`` / ``compile_seconds``), always
    updated while armed — the bench harness snapshots them around timed
    regions to report ``n_recompiles`` per benchmark record;
  * the default registry's ``jax.compiles`` counter and
    ``jax.compile_seconds`` total (when the registry is enabled);
  * compile-time attribution on the innermost active span
    (:mod:`repro.obs.spans`), which is how a span splits its wall time
    into compile vs execute.

``compile_count`` counts *backend compilations*, persistent-cache loads
among them (``cache_load_count`` counts those loads alone): the first
compilation of a callable and every subsequent recompile look identical to
XLA, so "recompiles" in steady-state accounting means snapshotting after
warmup (what :func:`repro.obs.bench.measure` does).
"""

from __future__ import annotations

import threading

__all__ = ["install", "installed", "snapshot", "CompileSnapshot",
           "compile_count", "compile_seconds", "cache_load_count"]

# total-duration events of the three compile phases; backend_compile is the
# one that fires exactly once per XLA compilation, so it carries the count
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_COMPILE_PHASES = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    _BACKEND_COMPILE,
)
_CACHE_HIT = "/jax/compilation_cache/cache_hits"

_lock = threading.Lock()
_installed = False
_armed = False

compile_count = 0
compile_seconds = 0.0
cache_load_count = 0


def _on_duration(event: str, duration: float, **kwargs) -> None:
    global compile_count, compile_seconds
    if not _armed or event not in _COMPILE_PHASES:
        return
    compile_seconds += duration
    is_backend = event == _BACKEND_COMPILE
    if is_backend:
        compile_count += 1
    from repro.obs import spans
    from repro.obs.registry import registry

    spans._attribute_compile(duration, is_backend)
    reg = registry()
    if reg.enabled:
        reg.counter("jax.compile_seconds").add(duration)
        if is_backend:
            reg.counter("jax.compiles").add(1)


def _on_event(event: str, **kwargs) -> None:
    global cache_load_count
    if _armed and event == _CACHE_HIT:
        cache_load_count += 1


def install() -> None:
    """Arm compile accounting (idempotent).  Registered once per process;
    never unregistered — disarming via the flag keeps repeat
    enable/disable cycles from stacking listeners."""
    global _installed, _armed
    with _lock:
        if not _installed:
            from jax import monitoring

            monitoring.register_event_duration_secs_listener(_on_duration)
            monitoring.register_event_listener(_on_event)
            _installed = True
        _armed = True


def installed() -> bool:
    return _installed and _armed


class CompileSnapshot:
    """Point-in-time compile totals; subtract two to get a window."""

    def __init__(self):
        self.count = compile_count
        self.seconds = compile_seconds
        self.loads = cache_load_count

    def delta(self) -> tuple[int, float]:
        """(compilations, compile seconds) since this snapshot."""
        return (compile_count - self.count, compile_seconds - self.seconds)

    def cache_loads(self) -> int:
        """How many of those compilations were persistent-cache loads."""
        return cache_load_count - self.loads


def snapshot() -> CompileSnapshot:
    """Arm the hooks and snapshot the totals (see CompileSnapshot)."""
    install()
    return CompileSnapshot()
