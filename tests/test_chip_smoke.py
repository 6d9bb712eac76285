"""chip_smoke.py's phases end to end on the CPU at a tiny size, and its
refusal to report a result from anything but a TPU."""

import dataclasses
import importlib.util
import sys
from pathlib import Path

import numpy as np
import pytest

from repro import obs
from repro.obs.registry import MetricsRegistry, set_registry
from repro.serve import WhatIfService

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


cs = _smoke()
TINY = cs.Shape(n_ops=6, dense_devices=24, struct_devices=96, regions=4,
                scenarios=3)


@pytest.fixture
def metrics():
    reg = MetricsRegistry(enabled=True)
    old = obs.registry()
    set_registry(reg)
    yield reg
    set_registry(old)


@pytest.mark.parametrize("deployment", ["dense", "structured"])
def test_phase_parity_and_oracle_on_cpu(deployment, metrics):
    """One deployment served cold and warm through one WhatIfService: every
    answer bitwise equal to direct score_grid calls, the warm pass equal to
    the cold one, and the oracle pairs within ORACLE_RTOL."""
    graph, deps = cs.build_deployments(3, TINY)
    dep = next(d for d in deps if d.name == deployment)
    svc = WhatIfService(graph, admission=cs.ADMISSION,
                        max_chunk_rows=TINY.chunk_rows)
    lines = []
    stats = cs.run_phase(svc, graph, dep, 3, TINY, log=lines.append)
    assert stats["bitwise_parity_queries"] == len(cs.QUERIES)
    assert stats["queries"] == len(cs.QUERIES)
    assert stats["rows"] == sum(rows for _, _, rows in cs.QUERIES)
    assert 0.0 <= stats["oracle_max_rel"] <= cs.ORACLE_RTOL
    assert lines and lines[0].startswith("smoke (not a benchmark) ")
    # on the CPU the evaluator takes the jaxmodel twin: no edge-kernel plan
    # exists, compiled or interpreted
    counts = cs.dispatch_counts(metrics)
    assert counts == {"pallas_compiled_plans": 0,
                      "pallas_interpreted_plans": 0}


@pytest.mark.parametrize("devices, route", [(96, "dense"), (256, "sparse")])
def test_structured_phase_counts_its_region_terms_route(devices, route,
                                                        metrics):
    """The structured phase, served and direct, takes the sparse region
    terms wherever ``sparse_placements`` accepts its rows (8 slots need
    V ≥ 128) and the dense ones elsewhere; ``region_terms_routes`` counts
    every dispatch under one route."""
    shape = dataclasses.replace(TINY, struct_devices=devices)
    graph, deps = cs.build_deployments(3, shape)
    dep = next(d for d in deps if d.name == "structured")
    svc = WhatIfService(graph, admission=cs.ADMISSION,
                        max_chunk_rows=shape.chunk_rows)
    cs.run_phase(svc, graph, dep, 3, shape, log=lambda line: None)
    routes = cs.region_terms_routes(metrics)
    assert routes[route] > 0 and sum(routes.values()) == routes[route]


def test_structured_kernel_check_on_cpu():
    """The per-operator structured kernel (interpreted here) against the
    gathered jnp reference, at the tiny deployment's V and R: within the gap
    (the two contract R in different orders, so bits may differ)."""
    graph, deps = cs.build_deployments(5, TINY)
    dep = next(d for d in deps if d.name == "structured")
    got = cs.check_structured_kernel(graph, dep, 5, TINY)
    assert 0.0 <= got["kernel_max_rel"] <= cs.KERNEL_GAP
    assert (got["devices"], got["regions"]) == (96, 4)


def test_oracle_check_catches_a_wrong_score():
    """A served score off by more than ORACLE_RTOL fails the oracle check."""
    graph, deps = cs.build_deployments(4, TINY)
    dep = deps[0]
    svc = WhatIfService(graph, admission=cs.ADMISSION)
    fids = cs.register(svc, dep)
    queries = cs.make_queries(np.random.default_rng(0), graph, dep, TINY,
                              TINY.scenarios)
    served = cs.serve_round(svc, dep, fids, queries)
    first = next(s for s in served if s.query.kind == "score")
    first.result.scores[0, 0] *= 1.0 + 10 * cs.ORACLE_RTOL
    with pytest.raises(AssertionError, match="float64 oracle"):
        cs.check_oracle(graph, dep, served)


def test_sparse_placements_are_rows_of_the_simplex():
    x = cs.sparse_placements(np.random.default_rng(1), 5, 3, 50, 4)
    assert x.shape == (5, 3, 50)
    np.testing.assert_allclose(x.sum(axis=-1), 1.0, rtol=1e-6)
    assert ((x > 0).sum(axis=-1) <= 4).all()


def test_refuses_cpu_without_a_result(capsys):
    """On the CPU main() exits non-zero before building anything and prints
    no result line."""
    rc = cs.main([])
    out = capsys.readouterr()
    assert rc != 0
    assert '"ok"' not in out.out
    assert "needs a TPU" in out.err
