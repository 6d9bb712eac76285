"""Ahead-of-time compiles of the what-if path's kernels for a TPU v5e.

The TPU compiler compiles for a chip it is only told about, so these tests
need no chip: a block shape Mosaic cannot lower, or a kernel over its
VMEM, is refused here as it would be on the chip.  Nothing runs, so
nothing here speaks to results or times.  Shapes are ``chip_smoke.py``'s.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library, and under several
test workers the one given this file loads it.
"""

import importlib.util
import os
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune, dispatch
from repro.kernels.edge_latency import (edge_latency_pallas,
                                        edge_latency_structured_pallas,
                                        edge_list)
from repro.sim import BatchedEvaluator, ScenarioConfig, fresh_cache, \
    region_fleet_family

ROOT = Path(__file__).resolve().parents[1]


def _smoke():
    """The repo-root ``chip_smoke.py`` script, imported as a module."""
    if "chip_smoke" not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", ROOT / "chip_smoke.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules["chip_smoke"] = mod
        spec.loader.exec_module(mod)
    return sys.modules["chip_smoke"]


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with the persistent compilation cache off: an
    entry compiled for a chip that is not attached cannot be read back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler can be loaded here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    """(sharding on one described chip, its device_kind)."""
    return SingleDeviceSharding(topo.devices[0]), topo.devices[0].device_kind


@pytest.fixture(scope="module")
def smoke():
    """chip_smoke's sizes and the edge count of its graph."""
    cs = _smoke()
    graph = cs.smoke_graph(np.random.default_rng(0))
    return cs, graph


def _shapes(sharding, *shapes):
    return [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]


def _compiled_text(fn, args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text  # the Pallas kernel is in the program
    return text


@pytest.mark.parametrize("blocks", ["tuned", "default"])
@pytest.mark.parametrize("kind", ["dense", "structured"])
def test_blocked_kernel_compiles(chip, smoke, kind, blocks):
    """Both blocked kernels at the smoke's chunk shape, with the block
    shapes the tuner picks for a v5e and with the wrappers' defaults: the
    dense one on per-edge rows, the structured one on per-operator rows
    with the smoke graph's edge list."""
    sharding, device_kind = chip
    cs, graph = smoke
    P, E, n, full = cs.FULL.chunk_rows, graph.n_edges, graph.n_ops, cs.FULL
    if kind == "dense":
        V, R = full.dense_devices, None
        args = _shapes(sharding, (P, E, V), (P, E, V), (1, V, V))
    else:
        V, R = full.struct_devices, full.regions
        args = _shapes(sharding, (P, n, V), (P, n, R), (1, R, V),
                       (P, n, V))
    cfg = (autotune.get_config(kind, P, E, V, R, backend="tpu",
                               device_kind=device_kind, n_ops=n)
           if blocks == "tuned" else autotune.DEFAULT_CONFIG)
    if kind == "dense":
        _compiled_text(lambda *a: edge_latency_pallas(
            *a, block_edges=cfg.block_edges, block_v=cfg.block_v), args)
    else:
        edges = edge_list([i for i, _ in graph.edges],
                          [j for _, j in graph.edges],
                          [graph.operators[i].selectivity
                           for i, _ in graph.edges])
        _compiled_text(lambda *a: edge_latency_structured_pallas(
            *a, edges, block_v=cfg.block_v), args)


@pytest.fixture
def tpu_dispatch(chip, monkeypatch):
    """Steer the dispatch policy onto the compiled-Pallas route that a TPU
    process takes, inside an isolated executable cache."""
    monkeypatch.setattr(dispatch, "backend_name", lambda: "tpu")
    monkeypatch.setattr(autotune, "local_device_kind",
                        lambda backend: chip[1])
    with fresh_cache():
        yield chip[0]


def test_dense_score_grid_compiles(tpu_dispatch, smoke):
    """The whole dense score_grid of one smoke chunk as the service
    dispatches it: P = 64 placements of 16 operators over S = 8 scenarios
    of V = 4096 devices, with dq per cell and β per placement."""
    cs, graph = smoke
    full = cs.FULL
    assert dispatch.route() == dispatch.Route(pallas=True, interpret=False)
    ev = BatchedEvaluator(graph)
    P, S = full.chunk_rows, full.scenarios
    args = _shapes(tpu_dispatch, (P, graph.n_ops, full.dense_devices),
                   (S, full.dense_devices, full.dense_devices), (S, P), (P,))
    _compiled_text(ev._jit_grid, args)


def test_structured_score_grid_compiles(tpu_dispatch, smoke):
    """The whole structured score_grid of the smoke's smallest query:
    P = 8 placements over S = 8 scenarios of V = 131072 devices in R = 32
    regions."""
    cs, graph = smoke
    full = cs.FULL
    fam = region_fleet_family(
        np.random.default_rng(0), full.scenarios,
        ScenarioConfig(n_regions=(full.regions, full.regions)),
        n_devices=full.struct_devices)
    ev = BatchedEvaluator(graph)
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=tpu_dispatch)
    args = _shapes(tpu_dispatch, (8, graph.n_ops, full.struct_devices),
                   (full.scenarios, full.regions, full.regions),
                   (full.scenarios, full.struct_devices))
    _compiled_text(ev._structured(fam).grid, args + [scalar, scalar])


def test_structured_sparse_score_grid_compiles(tpu_dispatch, smoke):
    """The structured score_grid of a served chunk, the region terms from
    each row's nonzeros: P = 64 placements of 16 operators as (idx, val)
    slots (k = 8) beside their dense rows, S = 8 scenarios of V = 131072
    devices in R = 32 regions."""
    cs, graph = smoke
    full = cs.FULL
    fam = region_fleet_family(
        np.random.default_rng(0), full.scenarios,
        ScenarioConfig(n_regions=(full.regions, full.regions)),
        n_devices=full.struct_devices)
    ev = BatchedEvaluator(graph)
    P, n = full.chunk_rows, graph.n_ops
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=tpu_dispatch)
    idx = jax.ShapeDtypeStruct((P, n, 8), jnp.int32, sharding=tpu_dispatch)
    args = _shapes(tpu_dispatch, (P, n, full.struct_devices),
                   (full.scenarios, full.regions, full.regions),
                   (full.scenarios, full.struct_devices))
    args += [scalar, scalar, (idx, _shapes(tpu_dispatch, (P, n, 8))[0])]
    _compiled_text(ev._structured(fam).grid, args)
