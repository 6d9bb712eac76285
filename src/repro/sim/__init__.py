"""Scenario simulation: generated what-if families, batched evaluation
(dense or structured RegionFleetFamily), trace replay (see sim/README.md for
the generators → batched eval → replay pipeline)."""

from repro.sim.batched import (BatchedEvaluator, SparsePlacements,
                               pack_fleets, pack_placements,
                               pack_region_fleets, pack_speeds,
                               sparse_placements)
from repro.sim.execache import (ExecutableCache, executable_cache,
                                fresh_cache, graph_key, set_executable_cache)
from repro.sim.replay import (ReplayReport, ReplayStep, apply_fleet_event,
                              replay_trace, robust_placement,
                              scenario_robust_search)
from repro.sim.scenarios import (MIN_ALIVE_DEVICES, Scenario, ScenarioConfig,
                                 TraceEvent, diurnal_rate, perturbed_fleet,
                                 random_fleet, random_graph, random_scenario,
                                 random_trace, region_fleet_family,
                                 region_scenario_batch, scenario_batch)
from repro.sim.training import TrainingTuples, merge_tuples, training_tuples

__all__ = [
    "BatchedEvaluator", "pack_fleets", "pack_placements", "pack_region_fleets",
    "pack_speeds", "SparsePlacements", "sparse_placements",
    "ExecutableCache", "executable_cache", "fresh_cache", "graph_key",
    "set_executable_cache",
    "ReplayReport", "ReplayStep", "apply_fleet_event", "replay_trace",
    "robust_placement", "scenario_robust_search",
    "MIN_ALIVE_DEVICES", "Scenario", "ScenarioConfig", "TraceEvent",
    "diurnal_rate", "perturbed_fleet", "random_fleet", "random_graph",
    "random_scenario", "random_trace", "region_fleet_family",
    "region_scenario_batch", "scenario_batch",
    "TrainingTuples", "merge_tuples", "training_tuples",
]
