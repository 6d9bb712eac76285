"""Process-wide executable cache (repro.sim.execache): LRU semantics, the
cross-instance recompile regression the cache exists to kill, and
fresh_cache isolation, and the fixed home of the persistent compile
cache."""

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CostConfig, ExplicitFleet, random_dag, \
    random_placement
from repro.obs import jaxhooks
from repro.sim import (BatchedEvaluator, ExecutableCache, executable_cache,
                       fresh_cache, graph_key, pack_fleets, pack_placements)


def test_lru_eviction_order_and_counters():
    c = ExecutableCache(capacity=2, name="t")
    builds = []
    get = lambda k: c.get_or_build((k,), lambda: builds.append(k) or k)
    get("a"), get("b")
    assert get("a") == "a" and c.stats()["hits"] == 1
    get("c")                      # evicts "b" (least recently used)
    assert ("b",) not in c and ("a",) in c and ("c",) in c
    get("b")                      # rebuild
    assert builds == ["a", "b", "c", "b"]
    st = c.stats()
    assert st["misses"] == 4 and st["evictions"] == 2 and len(c) == 2
    c.clear()
    assert len(c) == 0


def test_fresh_cache_isolates_and_restores():
    base = executable_cache()
    base_len = len(base)
    with fresh_cache() as tmp:
        assert executable_cache() is tmp and tmp is not base
        tmp.get_or_build(("x",), lambda: object())
        assert len(tmp) == 1
    assert executable_cache() is base and len(base) == base_len


def _problem(seed=0, n_ops=5, n_dev=4, n_fleets=3):
    rng = np.random.default_rng(seed)
    g = random_dag(n_ops, edge_prob=0.6, rng=rng)
    fleets = []
    for _ in range(n_fleets):
        com = rng.uniform(0.1, 3.0, (n_dev, n_dev))
        com = (com + com.T) / 2
        np.fill_diagonal(com, 0.0)
        fleets.append(ExplicitFleet(com_cost=com))
    xs = pack_placements([
        random_placement(n_ops, np.ones((n_ops, n_dev), bool), rng)
        for _ in range(6)])
    return g, pack_fleets(fleets), xs


def test_second_instance_never_recompiles():
    """THE regression this PR's cache hoist fixes: two BatchedEvaluators
    over identically-constructed graphs used to recompile everything,
    because jax's compilation cache keys on function identity and each
    instance owned fresh closures.  Now instance 2 resolves the SAME
    jitted callables through the process cache: zero compiles, bitwise
    identical grids."""
    g, coms, xs = _problem()
    with fresh_cache():
        ev1 = BatchedEvaluator(g, CostConfig())
        warm = np.asarray(ev1.score_grid(xs, coms, dq=0.2, beta=0.5))
        # an equal-content graph built independently (same dataclasses)
        g2 = random_dag(5, edge_prob=0.6, rng=np.random.default_rng(0))
        assert graph_key(g2) == graph_key(g)
        snap = jaxhooks.snapshot()
        ev2 = BatchedEvaluator(g2, CostConfig())
        again = np.asarray(ev2.score_grid(xs, coms, dq=0.2, beta=0.5))
        assert snap.delta() == (0, 0.0)
        np.testing.assert_array_equal(warm, again)
        assert ev1._jit_grid is ev2._jit_grid


def test_shared_returns_one_instance_per_content():
    g, _, _ = _problem()
    g2, _, _ = _problem()
    a = BatchedEvaluator.shared(g)
    assert BatchedEvaluator.shared(g2) is a
    assert BatchedEvaluator.shared(g, CostConfig(alpha=0.5)) is not a


def test_distinct_configs_do_not_collide():
    """Different CostConfigs must map to different executables — a cache
    hit across configs would silently score with the wrong alpha."""
    g, coms, xs = _problem()
    with fresh_cache():
        plain = np.asarray(
            BatchedEvaluator(g, CostConfig()).score_grid(xs, coms))
        alpha = np.asarray(
            BatchedEvaluator(g, CostConfig(alpha=1.0)).score_grid(xs, coms))
    assert not np.array_equal(plain, alpha)


@pytest.fixture
def cache_config():
    """Restore JAX's persistent-cache settings after the test, and let the
    cache pick its directory afresh on the next compile."""
    from jax.experimental.compilation_cache import compilation_cache
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes",
             "jax_hlo_source_file_canonicalization_regex")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


def test_persistent_cache_honors_env_dir(monkeypatch, tmp_path,
                                         cache_config):
    """With JAX_COMPILATION_CACHE_DIR set the helper returns it and sets no
    directory of its own."""
    from repro.sim.execache import enable_persistent_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_persistent_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_persistent_cache_defaults_to_fixed_checkout_dir(monkeypatch,
                                                         cache_config):
    """Without the variable every call lands on the same directory inside
    the checkout (never a temp, pid- or time-based path)."""
    from repro.sim.execache import CHECKOUT_CACHE_DIR, enable_persistent_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = enable_persistent_cache()
    assert first == enable_persistent_cache() == str(CHECKOUT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == first
    assert CHECKOUT_CACHE_DIR.parent == Path(__file__).resolve().parents[1]


def test_persistent_cache_writes_entries_and_reads_them_back(
        monkeypatch, tmp_path, cache_config):
    """A compile after the helper writes an entry into the cache directory;
    the same program compiled again, with the in-memory caches cleared,
    is read back from it instead of compiled."""
    from jax.experimental.compilation_cache import compilation_cache

    from repro.obs import jaxhooks
    from repro.sim import execache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(execache, "CHECKOUT_CACHE_DIR", tmp_path / "cache")
    assert execache.enable_persistent_cache() == str(tmp_path / "cache")
    compilation_cache.reset_cache()   # earlier compiles fixed it as unused

    def program(x):
        return jnp.tanh(x @ x.T) * 3.0 + 1.0

    x = jnp.ones((24, 8), jnp.float32)
    snap = jaxhooks.snapshot()
    first = jax.jit(program)(x)
    assert snap.delta()[0] == 1 and snap.cache_loads() == 0
    entries = sorted(p.name for p in (tmp_path / "cache").iterdir())
    assert entries
    jax.clear_caches()
    snap = jaxhooks.snapshot()
    again = jax.jit(program)(x)
    assert snap.delta()[0] == snap.cache_loads() == 1   # loaded
    np.testing.assert_array_equal(np.asarray(again), np.asarray(first))
    assert sorted(p.name for p in (tmp_path / "cache").iterdir()) == entries


def test_persistent_cache_keys_ignore_the_checkout_path(cache_config):
    """Source paths under the checkout lose its root (so two checkouts of
    one commit share entries); paths outside it are left alone."""
    import re

    from repro.sim.execache import CHECKOUT_ROOT, enable_persistent_cache
    enable_persistent_cache()
    pattern = jax.config.jax_hlo_source_file_canonicalization_regex
    kernel = CHECKOUT_ROOT / "src" / "repro" / "kernels" / "edge_latency.py"
    assert re.sub(pattern, "", str(kernel)) == str(
        Path("src", "repro", "kernels", "edge_latency.py"))
    outside = "/elsewhere" + str(kernel)
    assert re.sub(pattern, "", outside) == outside
