"""The benchmark's copies agree with the program's originals on small
cases: the float64 reference with ``repro.core.costmodel`` and the
decision rules, the graph draw with ``repro.sim.scenarios``."""

import numpy as np
import pytest

from bench.lib import deploy, reference as ref
from repro.core import costmodel
from repro.core.devices import ExplicitFleet, RegionFleet
from repro.core.graph import Operator, OpGraph
from repro.search.decision import joint_dq_scores, pareto_mask
from repro.sim.scenarios import ScenarioConfig, random_graph

GRAPH = {"n_ops": 7, "edge_prob": 0.45, "max_selectivity": 2.0,
         "out_bytes": [0.25, 4.0], "op_work": [0.05, 0.5], "seed": 5}


def _program_graph(g):
    return OpGraph([Operator(f"op{i}", float(g.selectivity[i]),
                             out_bytes=float(g.out_bytes[i]),
                             work=float(g.work[i]))
                    for i in range(g.n_ops)], g.edges)


def _fleet(kind, V, seed):
    cfg = {"devices": V, "scenarios": 3, "layout_seed": 1}
    if kind == "dense":
        cfg["fleet"] = {"kind": "dense", "bandwidth_mbit": [25.0, 1e4],
                        "delay_ms": [1.0, 160.0], "unit_mbit": 8.0}
    else:
        cfg["fleet"] = {"kind": "structured", "n_regions": 4,
                        "com_logmean": 0.0, "com_logstd": 0.6,
                        "intra_discount": 0.1, "region_jitter": 0.3,
                        "straggler_prob": 0.2, "degrade_factor": [2.0, 8.0],
                        "outage_prob": 0.2, "outage_factor": 1e4}
    return deploy.draw_fleet(cfg, seed)


def _scenario(fl, s):
    """Scenario ``s`` as the program's scalar fleet."""
    if isinstance(fl, deploy.Dense):
        return ExplicitFleet(com_cost=fl.com[s].astype(np.float64))
    return RegionFleet(region=fl.region, inter=fl.inter[s],
                       self_cost=fl.self_cost, degrade=fl.degrade[s])


def test_graph_draw_is_the_scenario_generators():
    """Seed 0 of the copied draw is the 16-operator, 51-edge DAG that
    ``random_graph`` gives for the same seed."""
    spec = dict(GRAPH, n_ops=16, seed=0)
    g = deploy.draw_graph(spec)
    want = random_graph(np.random.default_rng(0), ScenarioConfig(
        n_ops=(16, 16), graph_families=("layered",)))
    assert g.n_edges == 51
    assert list(g.edges) == want.edges
    np.testing.assert_array_equal(
        g.selectivity, [op.selectivity for op in want.operators])
    np.testing.assert_array_equal(
        g.out_bytes, [op.out_bytes for op in want.operators])
    np.testing.assert_array_equal(g.work, [op.work for op in want.operators])


@pytest.mark.parametrize("kind", ["dense", "structured"])
def test_reference_agrees_with_costmodel(kind):
    """Latency, F and network movement of every (scenario, placement)
    pair equal the scalar oracle's to float64 rounding, with co-located
    operators so that the u == v diagonal is exercised."""
    g = deploy.draw_graph(GRAPH)
    V = 40
    fl = _fleet(kind, V, seed=3)
    x, idx, w = deploy.placement_pool(np.random.default_rng(4), 12, g.n_ops,
                                      V, 3)
    lat = ref.latency(g, fl, idx, w)
    mov = ref.network_movement(g, idx, w)
    og = _program_graph(g)
    for s in range(fl.n_scenarios):
        f = _scenario(fl, s)
        for p in range(x.shape[0]):
            want = costmodel.latency(og, f, x[p])
            assert lat[s, p] == pytest.approx(want, rel=1e-12)
            assert ref.objective_f(lat[s, p], 0.4, 1.3) == pytest.approx(
                costmodel.objective_F(want, 0.4, 1.3), rel=1e-12)
            assert mov[p] == pytest.approx(
                costmodel.network_movement(og, f, x[p]), rel=1e-12)


def test_decisions_agree_with_the_program_rules():
    rng = np.random.default_rng(0)
    lat = rng.lognormal(0.0, 0.5, (4, 30))
    dq = np.linspace(0.0, 0.9, 7)
    _, best, idx = ref.joint(lat, dq, 0.8)
    want, want_idx = joint_dq_scores(lat, dq, 0.8)
    np.testing.assert_array_equal(best, want)
    np.testing.assert_array_equal(idx, want_idx)
    vals = rng.integers(0, 6, (50, 2)).astype(float)  # ties included
    np.testing.assert_array_equal(ref.pareto_mask(vals), pareto_mask(vals))
