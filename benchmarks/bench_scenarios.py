"""Scenario-simulation throughput: (scenario × placement) grids scored by
the batched evaluator (on the route the dispatch gives the backend: the
jaxmodel twin on the CPU, compiled Pallas on an accelerator) vs looping
the scalar ``latency()`` path.  Writes BENCH_scenarios.json with
candidates-scored-per-second and the batched-vs-scalar speedup (the ISSUE's
≥10× acceptance gate)."""

import json
from pathlib import Path

import jax
import numpy as np

from repro.core import latency, objective_F, random_placement
from repro.obs import bench as obench
from repro.sim import (BatchedEvaluator, ScenarioConfig, pack_fleets,
                       pack_placements, scenario_batch)

OUT_PATH = Path("BENCH_scenarios.json")


def _time(f, n=5):
    """Mean seconds per warm call (shared harness: repro.obs.bench)."""
    return obench.measure(f, n=n, block=False).mean_s


def run() -> list[str]:
    rng = np.random.default_rng(0)
    cfg = ScenarioConfig(n_ops=(12, 12), n_regions=(4, 4),
                         devices_per_region=(8, 8))
    n_scenarios, n_placements = 8, 128
    scens = scenario_batch(rng, n_scenarios, cfg)
    g = scens[0].graph
    v = scens[0].n_devices
    xs = [random_placement(g.n_ops, np.ones((g.n_ops, v), bool), rng, 0.5)
          for _ in range(n_placements)]
    coms = pack_fleets([s.fleet for s in scens])
    P = pack_placements(xs)
    n_cand = n_scenarios * n_placements

    ev = BatchedEvaluator(g)
    s_batched = _time(lambda: np.asarray(ev.score_grid(P, coms, dq=0.3,
                                                       beta=0.5)))

    # scalar reference: python loop over a subset, extrapolated per-candidate
    sub = 32
    pairs = [(scens[k % n_scenarios].fleet, xs[k % n_placements])
             for k in range(sub)]

    def scalar_loop():
        for fleet, x in pairs:
            objective_F(latency(g, fleet, x), 0.3, 0.5)

    s_scalar_per = _time(scalar_loop, n=2) / sub

    batched_per = s_batched / n_cand
    speedup = s_scalar_per / batched_per
    report = {
        "n_scenarios": n_scenarios,
        "n_placements": n_placements,
        "n_candidates": n_cand,
        "n_ops": g.n_ops,
        "n_devices": v,
        "candidates_per_second": 1.0 / batched_per,
        "batched_us_per_candidate": batched_per * 1e6,
        "backend": jax.default_backend(),
        "scalar_us_per_candidate": s_scalar_per * 1e6,
        "batched_vs_scalar_speedup": speedup,
    }
    OUT_PATH.write_text(json.dumps(report, indent=2) + "\n")
    return [
        f"scenarios_grid_{n_scenarios}x{n_placements}_dev{v},"
        f"{batched_per * 1e6:.2f},"
        f"cands_per_s={1.0 / batched_per:.0f};speedup_vs_scalar={speedup:.1f}",
        f"scenarios_scalar_loop_dev{v},{s_scalar_per * 1e6:.2f},per_candidate",
    ]
