"""Process-wide executable cache: ONE compiled callable per (evaluator
family, layout, objective set), shared across every consumer.

Before this module, each :class:`repro.sim.batched.BatchedEvaluator`
instance owned its jitted entry points and its ``_structured_cache`` /
``_multi_cache`` dicts — so two evaluators built over identically-packed
fleets compiled the SAME program twice (jax's compilation cache keys on
function identity, and per-instance closures are distinct functions).  The
what-if serving layer (:mod:`repro.serve`) makes that cost structural: many
tenants, one process, one set of hot shapes.

The fix is an LRU of *callables* keyed by semantic identity:

  * the evaluator family — :func:`graph_key` (operator tuple + edge list,
    so separately-constructed but identical graphs collide on purpose),
    the frozen :class:`~repro.core.costmodel.CostConfig`, and the
    :class:`~repro.kernels.dispatch.Route` the evaluator was built on;
  * the entry point kind (dense grid, structured layout, multi-objective
    set, ...) plus whatever static state it closes over (region layout
    bytes, the hashable ``ObjectiveSet``).

Because the cached value is the jitted *function object*, jax's own
executable cache then does the per-shape-bucket work: the first dispatch of
an unseen (bucket, scenario-count) shape compiles, every later dispatch —
from ANY evaluator instance with an equal key — hits.  Eviction is safe:
a rebuilt callable just recompiles on first use (counted as an eviction
plus a miss).

Hit/miss/evict counters publish into ``repro.obs`` (label ``kind=`` the
key's leading tag) when the registry is enabled; :meth:`ExecutableCache.
stats` reports them unconditionally for the serving layer's per-bucket
accounting.  :func:`fresh_cache` scopes an isolated cache — tests and the
``bench_serve`` dedicated-evaluator baseline use it to measure exactly the
per-consumer recompilation this module deletes.

Across processes, :func:`enable_persistent_cache` turns on JAX's on-disk
compilation cache at one fixed directory, so a second run of the same
commit — from this checkout or another — loads its executables instead of
compiling them.
"""

from __future__ import annotations

import collections
import contextlib
import os
import re
import threading
from pathlib import Path

import jax

from repro import obs

__all__ = ["ExecutableCache", "executable_cache", "set_executable_cache",
           "fresh_cache", "graph_key", "enable_persistent_cache",
           "CHECKOUT_ROOT", "CHECKOUT_CACHE_DIR"]

#: the checkout this module runs from
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]
#: the persistent cache's home when ``JAX_COMPILATION_CACHE_DIR`` is unset:
#: fixed inside the checkout (and git-ignored), because the path is part of
#: what a later run must find again
CHECKOUT_CACHE_DIR = CHECKOUT_ROOT / ".jax_cache"


def enable_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory: JAX reads it
    itself and no other is set here.  Otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`.  Every compile is cached, however quick.

    A Pallas TPU kernel carries the source paths of its operations inside
    the program the cache key hashes, so the checkout's own root is
    stripped from every source path: two checkouts of one commit then key
    their programs alike.  Call it from an entry point before its first
    compile, never at import."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(f"{CHECKOUT_ROOT}{os.sep}"))
    return path


def graph_key(graph) -> tuple:
    """Content identity of an :class:`~repro.core.graph.OpGraph`: the
    operator tuple (frozen dataclasses) plus the edge list.  Two graphs
    built independently from the same spec hash equal — that equality is
    what lets separate consumers share one compiled evaluator."""
    return (tuple(graph.operators), tuple(graph.edges))


class ExecutableCache:
    """Thread-safe LRU of built callables.

    ``get_or_build(key, builder)`` returns the cached callable for ``key``
    or invokes ``builder()`` (cheap — jit *wrapping*, not compilation) and
    caches it.  Keys are arbitrary hashable tuples whose first element
    names the entry-point kind (used as the obs label).
    """

    def __init__(self, capacity: int = 512, name: str = "executables"):
        if capacity < 1:
            raise ValueError(f"need capacity >= 1, got {capacity}")
        self.capacity = capacity
        self.name = name
        self._entries: collections.OrderedDict = collections.OrderedDict()
        self._lock = threading.RLock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def _count(self, event: str, kind: str) -> None:
        reg = obs.registry()
        if reg.enabled:
            reg.counter(f"cache.{self.name}.{event}", kind=kind).add(1)

    def get_or_build(self, key: tuple, builder):
        kind = str(key[0]) if isinstance(key, tuple) and key else "?"
        with self._lock:
            fn = self._entries.get(key)
            if fn is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                self._count("hits", kind)
                return fn
            self.misses += 1
            self._count("misses", kind)
            fn = builder()
            self._entries[key] = fn
            while len(self._entries) > self.capacity:
                self._entries.popitem(last=False)
                self.evictions += 1
                self._count("evictions", kind)
            return fn

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key) -> bool:
        with self._lock:
            return key in self._entries

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    def stats(self) -> dict:
        """JSON-able counters (always collected, registry or not)."""
        with self._lock:
            lookups = self.hits + self.misses
            return {"name": self.name, "size": len(self._entries),
                    "capacity": self.capacity, "hits": self.hits,
                    "misses": self.misses, "evictions": self.evictions,
                    "hit_rate": self.hits / lookups if lookups else None}


_cache = ExecutableCache()


def executable_cache() -> ExecutableCache:
    """The process-wide default cache every evaluator builds through."""
    return _cache


def set_executable_cache(cache: ExecutableCache) -> ExecutableCache:
    """Swap the process-wide cache (returns the previous one)."""
    global _cache
    prev, _cache = _cache, cache
    return prev


@contextlib.contextmanager
def fresh_cache(capacity: int = 512, name: str = "executables"):
    """Scope an isolated ExecutableCache as the process default — restores
    the previous cache on exit.  Used by tests (isolation) and by the
    ``bench_serve`` dedicated-evaluator baseline, which must NOT benefit
    from sharing to measure the cost of per-consumer compilation."""
    prev = set_executable_cache(ExecutableCache(capacity, name))
    try:
        yield executable_cache()
    finally:
        set_executable_cache(prev)
