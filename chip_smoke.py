"""One-chip smoke run of the what-if serving path.

Builds two deployments with the ``repro.sim.scenarios`` generators, seeded
from ``--seed``:

  * dense: ``ExplicitFleet`` packs of V = 4096 devices, S = 8 scenarios;
  * structured: a ``RegionFleetFamily`` of V = 131072 devices in R = 32
    regions, S = 8 scenarios;

both scored for one 16-operator DAG.  It registers both with one
``WhatIfService``, submits two dozen ``score`` / ``rank`` / ``pareto`` /
``joint`` queries of 8-64 placements from four tenants, drains, polls,
and then serves the same queries again warm.  It checks that

  * every served answer is bitwise what direct ``score_grid`` calls on the
    same evaluator give, at each query's own dq and β — scores, the pareto
    queries' per-objective grids and scalarization, and the joint dq grid
    (and the warm pass repeats the cold one bitwise);
  * ``ORACLE_PAIRS`` (scenario, placement) pairs per deployment agree with
    the float64 oracle in ``repro.core.costmodel`` within ``ORACLE_RTOL``;
  * the edge kernels ran as compiled Pallas: ``kernels.dispatch.plans``
    with ``interpret=False`` is positive, and no plan was interpreted;
  * every structured dispatch, served or direct, took the region terms
    from its rows' nonzeros: ``eval.region_terms`` counts ``sparse`` and
    no ``dense``;
  * the structured edge kernel, given per-operator rows and the edge
    list, agrees with ``ref.edge_latency_structured_ref`` given rows
    gathered to edges, on one scenario's region terms at the deployment's
    V and R: bitwise, or within ``KERNEL_GAP`` relative (it prints which).

Run it from the root of a checkout:

    python chip_smoke.py [--seed N]

It needs a TPU: when JAX's first device is anything else it exits 1 and
prints no result.  The seconds and bytes it prints are smoke numbers from
one run, not benchmark numbers.  Its last line is the JSON result.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import obs  # noqa: E402
from repro.core.costmodel import latency, objective_F  # noqa: E402
from repro.core.jaxmodel import (region_a_off, region_own,  # noqa: E402
                                 region_terms)
from repro.core.objectives import ObjectiveGrids, ObjectiveSet  # noqa: E402
from repro.kernels import dispatch, ref  # noqa: E402
from repro.kernels.edge_latency import edge_list  # noqa: E402
from repro.obs import jaxhooks  # noqa: E402
from repro.search.decision import (joint_dq_scores, pareto_front,  # noqa: E402
                                   split_dq_term)
from repro.serve import (AdmissionConfig, Admitted, QueryResult,  # noqa: E402
                         WhatIfQuery, WhatIfService)
from repro.sim import (BatchedEvaluator, ScenarioConfig,  # noqa: E402
                       pack_fleets, random_graph, region_fleet_family,
                       scenario_batch)
from repro.sim.execache import enable_persistent_cache  # noqa: E402


@dataclasses.dataclass(frozen=True)
class Shape:
    """Sizes of one smoke run."""

    n_ops: int = 16
    dense_devices: int = 4096
    struct_devices: int = 131072
    regions: int = 32
    scenarios: int = 8
    # WhatIfService.max_chunk_rows: the placements of one dispatch, sized
    # so the structured grid's temporaries stay well inside 16 GiB of HBM
    chunk_rows: int = 64
    devices_per_op: int = 4     # nonzero entries of one placement row


FULL = Shape()

TENANTS = ("tenant-a", "tenant-b", "tenant-c", "tenant-d")

# (tenant, kind, rows) submitted against each deployment.  The
# single-objective rows sum to four 64-row chunks and the pareto rows to
# one, so the service dispatches only full chunks.
QUERIES = (
    ("tenant-a", "score", 8), ("tenant-b", "score", 16),
    ("tenant-c", "score", 32), ("tenant-d", "score", 64),
    ("tenant-a", "rank", 16), ("tenant-b", "rank", 32),
    ("tenant-c", "joint", 8), ("tenant-d", "joint", 16),
    ("tenant-a", "joint", 32), ("tenant-b", "score", 32),
    ("tenant-c", "pareto", 32), ("tenant-d", "pareto", 32),
)

# pareto queries rank placements on latency against WAN bytes moved
OBJECTIVES = ObjectiveSet.from_weights(latency_f=1.0, network_movement=0.05)
JOINT_DQ = np.linspace(0.0, 0.9, 7)
JOINT_BETA = 0.8

# every query is queued before the first dispatch; the budget only has to
# admit them all undegraded, since admission is not what this run checks
ADMISSION = AdmissionConfig(p99_budget_s=3600.0)

ORACLE_PAIRS = 8
# f32 scores against the float64 oracle: relative error, per pair
ORACLE_RTOL = 1e-5


# the structured kernel against the gathered jnp reference: relative, as the
# structured benchmark cell's ``gap`` limit
KERNEL_GAP = 2e-06


@dataclasses.dataclass
class Deployment:
    name: str
    pack: object          # (S, V, V) float32 array or RegionFleetFamily
    fleet: callable       # scenario index -> oracle fleet
    n_devices: int


@dataclasses.dataclass
class Served:
    """One submitted query and its final result."""

    deployment: str
    query_id: int
    query: WhatIfQuery
    result: QueryResult | None = None


def smoke_graph(rng: np.random.Generator, shape: Shape = FULL):
    """The operator DAG every query is scored for."""
    return random_graph(rng, ScenarioConfig(
        n_ops=(shape.n_ops, shape.n_ops), graph_families=("layered",)))


def build_deployments(seed: int, shape: Shape = FULL):
    """The graph and the two deployments, all drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    graph = smoke_graph(rng, shape)
    scens = scenario_batch(rng, shape.scenarios, ScenarioConfig(),
                           graph=graph, n_devices=shape.dense_devices)
    dense_fleets = [s.fleet for s in scens]
    dense = Deployment("dense", np.asarray(pack_fleets(dense_fleets)),
                       dense_fleets.__getitem__, shape.dense_devices)
    fam = region_fleet_family(
        rng, shape.scenarios,
        ScenarioConfig(n_regions=(shape.regions, shape.regions)),
        n_devices=shape.struct_devices)
    structured = Deployment("structured", fam, fam.fleet,
                            shape.struct_devices)
    return graph, [dense, structured]


def sparse_placements(rng: np.random.Generator, rows: int, n_ops: int,
                      n_devices: int, per_op: int) -> np.ndarray:
    """(rows, n_ops, V) placements: each operator split over ``per_op``
    random devices with Dirichlet(1) fractions."""
    x = np.zeros((rows, n_ops, n_devices), np.float32)
    dev = rng.integers(0, n_devices, (rows, n_ops, per_op))
    w = rng.gamma(1.0, 1.0, (rows, n_ops, per_op))
    w /= w.sum(axis=-1, keepdims=True)
    r, o = np.meshgrid(np.arange(rows), np.arange(n_ops), indexing="ij")
    np.add.at(x, (r[..., None], o[..., None], dev), w.astype(np.float32))
    return x


def make_queries(rng: np.random.Generator, graph, dep: Deployment,
                 shape: Shape, n_scenarios: int) -> list[tuple]:
    """(tenant, WhatIfQuery) pairs for one deployment, from QUERIES."""
    out = []
    for k, (tenant, kind, rows) in enumerate(QUERIES):
        x = sparse_placements(rng, rows, graph.n_ops, dep.n_devices,
                              shape.devices_per_op)
        if kind == "joint":
            q = WhatIfQuery(kind="joint", placements=x, beta=JOINT_BETA,
                            dq_values=JOINT_DQ)
        else:
            # scalar and per-scenario dq, β on and off
            dq = (np.linspace(0.1, 0.8, n_scenarios).astype(np.float32)
                  if k % 3 == 1 else float(rng.uniform(0.0, 0.9)))
            beta = 0.0 if k % 4 == 0 else float(rng.uniform(0.1, 1.5))
            q = WhatIfQuery(kind=kind, placements=x, dq=dq, beta=beta,
                            top_k=4)
        out.append((tenant, q))
    return out


def register(svc: WhatIfService, dep: Deployment) -> dict[str, str]:
    """Fleet ids per query kind: pareto needs the objective set."""
    single = svc.register_fleet(TENANTS[0], dep.pack)
    multi = svc.register_fleet(TENANTS[0], dep.pack, objectives=OBJECTIVES)
    return {"score": single, "rank": single, "joint": single,
            "pareto": multi}


def serve_round(svc: WhatIfService, dep: Deployment, fids: dict,
                queries: list[tuple]) -> list[Served]:
    """Submit every query, drain the service, and collect the results."""
    served = []
    for tenant, q in queries:
        ticket = svc.submit(tenant, fids[q.kind], q)
        if not isinstance(ticket.admission, Admitted):
            raise RuntimeError(f"{dep.name} query not admitted whole: "
                               f"{ticket.admission}")
        served.append(Served(dep.name, ticket.query_id, q))
    svc.drain()
    results = {msg.query_id: msg for tenant in TENANTS
               for msg in svc.poll(tenant) if isinstance(msg, QueryResult)}
    for s in served:
        s.result = results[s.query_id]
    return served


def check_parity(graph, dep: Deployment, served: list[Served]) -> int:
    """Each served answer against direct score_grid calls on the shared
    evaluator the service uses; returns the number of queries checked.
    Raises AssertionError on any difference."""
    ev = BatchedEvaluator.shared(graph)
    calls = []
    for s in served:
        q = s.query
        if q.kind == "pareto":
            og = ev.score_grid(q.placements, dep.pack, dq=q.dq, beta=q.beta,
                               objectives=OBJECTIVES)
            calls.append((og.grids, og.scalarized))
        elif q.kind == "joint":     # raw grid, finished over JOINT_DQ below
            calls.append(ev.score_grid(q.placements, dep.pack))
        else:
            calls.append(ev.score_grid(q.placements, dep.pack, dq=q.dq,
                                       beta=q.beta))
    for s, direct in zip(served, jax.device_get(calls)):
        q, res = s.query, s.result
        where = f"{dep.name} {q.kind} query {s.query_id}"
        if q.kind == "pareto":
            grids, scal = direct
            for name in OBJECTIVES.names:
                np.testing.assert_array_equal(res.grids[name], grids[name],
                                              err_msg=f"{where}: {name}")
            np.testing.assert_array_equal(res.scores, scal,
                                          err_msg=f"{where}: scalarized")
            want = pareto_front(ObjectiveGrids(
                names=OBJECTIVES.names, grids=grids, scalarized=scal,
                weights=OBJECTIVES.weights))
            np.testing.assert_array_equal(res.front.indices, want.indices,
                                          err_msg=f"{where}: front")
        elif q.kind == "joint":
            lat, rest, w_lat = split_dq_term(direct)
            want, want_idx = joint_dq_scores(lat, JOINT_DQ, JOINT_BETA,
                                             rest=rest, w_lat=w_lat)
            np.testing.assert_array_equal(res.scores, want,
                                          err_msg=f"{where}: scores")
            np.testing.assert_array_equal(res.dq_idx, want_idx,
                                          err_msg=f"{where}: dq")
        else:
            np.testing.assert_array_equal(res.scores, direct,
                                          err_msg=f"{where}: scores")
    return len(served)


def check_repeat(cold: list[Served], warm: list[Served]) -> None:
    for a, b in zip(cold, warm, strict=True):
        np.testing.assert_array_equal(
            a.result.scores, b.result.scores,
            err_msg=f"{a.deployment} {a.query.kind}: warm != cold")


def check_oracle(graph, dep: Deployment, served: list[Served],
                 n_pairs: int = ORACLE_PAIRS) -> float:
    """Served scores of ``n_pairs`` (scenario, placement) pairs against
    ``objective_F(latency(...))`` in float64; returns the largest
    relative error and raises AssertionError above ORACLE_RTOL."""
    scored = [s for s in served if s.query.kind == "score"]
    worst = 0.0
    for k in range(n_pairs):
        s = scored[k % len(scored)]
        q = s.query
        S, P = s.result.scores.shape
        sc, p = k % S, (k * 7) % P
        dq = float(np.broadcast_to(np.asarray(q.dq, np.float64), (S,))[sc])
        want = objective_F(latency(graph, dep.fleet(sc), q.placements[p]),
                           dq, q.beta)
        got = float(s.result.scores[sc, p])
        rel = abs(got - want) / max(abs(want), 1e-30)
        if not rel <= ORACLE_RTOL:
            raise AssertionError(
                f"{dep.name} scenario {sc} placement {p}: served {got!r} "
                f"vs float64 oracle {want!r} (rel {rel:.3g} > "
                f"{ORACLE_RTOL})")
        worst = max(worst, rel)
    return worst


def check_structured_kernel(graph, dep: Deployment, seed: int,
                            shape: Shape, rows: int = 8) -> dict:
    """The structured edge kernel on per-operator rows with the graph's
    edge list against the jnp reference on the same rows gathered to edges
    (``x[src]·sel``, ``mass[dst]``, ``w[dst]``), for ``rows`` placements
    and the region terms of the family's first scenario.  Raises
    AssertionError beyond ``KERNEL_GAP`` relative."""
    fam = dep.pack
    region_ix = jnp.asarray(np.asarray(fam.region, dtype=np.int64))
    inter = jnp.asarray(fam.inter[0], jnp.float32)
    degrade = jnp.asarray(fam.degrade[0], jnp.float32)
    x = jnp.asarray(sparse_placements(np.random.default_rng([seed, 1]),
                                      rows, graph.n_ops, dep.n_devices,
                                      shape.devices_per_op))
    mass, w = region_terms(x, degrade, region_own(inter, degrade, region_ix),
                           region_ix, fam.n_regions, fam.self_cost)
    a = region_a_off(inter, degrade, region_ix)[None]
    src = np.array([i for i, _ in graph.edges])
    dst = np.array([j for _, j in graph.edges])
    edges = edge_list(src, dst, [graph.operators[i].selectivity
                                 for i in src])
    kernel = dispatch.edge_latency_structured(x, mass, a, w, edges)
    x_i = x[:, src] * jnp.asarray(edges[2], jnp.float32)[None, :, None]
    reference = ref.edge_latency_structured_ref(x_i, mass[:, dst], a,
                                                w[:, dst])
    got, want = (np.asarray(v, np.float64) for v in (kernel, reference))
    rel = float(np.max(np.abs(got - want) / np.abs(want)))
    if not rel <= KERNEL_GAP:
        raise AssertionError(f"structured kernel vs gathered reference: "
                             f"rel {rel:.3g} > {KERNEL_GAP}")
    return {"kernel_bitwise": bool(np.array_equal(got, want)),
            "kernel_max_rel": rel, "kernel_gap": KERNEL_GAP,
            "devices": dep.n_devices, "regions": fam.n_regions}


def dispatch_counts(reg) -> dict[str, float]:
    """Edge-kernel plans, compiled and interpreted."""
    rows = [r for r in reg.snapshot() if r["type"] == "counter"]

    def total(name, **labels):
        return sum(r["value"] for r in rows if r["name"] == name and all(
            r["labels"].get(k) == v for k, v in labels.items()))

    return {
        "pallas_compiled_plans": total("kernels.dispatch.plans",
                                       interpret="False"),
        "pallas_interpreted_plans": total("kernels.dispatch.plans",
                                          interpret="True"),
    }


def region_terms_routes(reg) -> dict[str, float]:
    """Structured dispatches by the operand their region terms came from:
    ``sparse`` (the rows' nonzeros) or ``dense``."""
    rows = [r for r in reg.snapshot() if r["type"] == "counter"
            and r["name"] == "eval.region_terms"]
    return {route: sum(r["value"] for r in rows
                       if r["labels"]["route"] == route)
            for route in ("sparse", "dense")}


def run_phase(svc, graph, dep: Deployment, seed: int, shape: Shape,
              log=print) -> dict:
    """Cold and warm serving of one deployment, then every check."""
    fids = register(svc, dep)
    S = (dep.pack.n_scenarios if hasattr(dep.pack, "n_scenarios")
         else dep.pack.shape[0])
    queries = make_queries(np.random.default_rng([seed, dep.n_devices]),
                           graph, dep, shape, S)
    snap = jaxhooks.snapshot()
    t0 = time.perf_counter()
    cold = serve_round(svc, dep, fids, queries)
    cold_s = time.perf_counter() - t0
    n_compiles, compile_s = snap.delta()
    n_loads = snap.cache_loads()
    t0 = time.perf_counter()
    warm = serve_round(svc, dep, fids, queries)
    warm_s = time.perf_counter() - t0
    check_repeat(cold, warm)
    n_parity = check_parity(graph, dep, cold)
    oracle_rel = check_oracle(graph, dep, cold)
    stats = {"deployment": dep.name, "devices": dep.n_devices,
             "scenarios": S, "queries": len(cold),
             "rows": int(sum(s.query.placements.shape[0] for s in cold)),
             "cold_s": cold_s, "compile_s": compile_s,
             "compiles": n_compiles, "cache_loads": n_loads,
             "warm_s": warm_s,
             "bitwise_parity_queries": n_parity,
             "oracle_pairs": ORACLE_PAIRS, "oracle_max_rel": oracle_rel,
             "oracle_rtol": ORACLE_RTOL}
    log("smoke (not a benchmark) " + json.dumps(stats))
    return stats


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is "
              f"{dev.platform} ({dev.device_kind})", file=sys.stderr)
        return 1
    cache_dir = enable_persistent_cache()
    obs.enable()
    print(f"smoke device_kind={dev.device_kind!r} devices="
          f"{len(jax.devices())} compile_cache={cache_dir}")

    graph, deployments = build_deployments(args.seed)
    print(f"smoke graph: {graph.n_ops} operators, {graph.n_edges} edges")
    svc = WhatIfService(graph, admission=ADMISSION,
                        max_chunk_rows=FULL.chunk_rows)
    for dep in deployments:
        run_phase(svc, graph, dep, args.seed, FULL)
        if dep.name == "structured":
            print("smoke kernel " + json.dumps(check_structured_kernel(
                graph, dep, args.seed, FULL)))

    counts = dispatch_counts(obs.registry())
    print("smoke dispatch " + json.dumps(counts))
    if not (counts["pallas_compiled_plans"] > 0
            and counts["pallas_interpreted_plans"] == 0):
        raise AssertionError(f"edge kernels did not run as compiled "
                             f"Pallas: {counts}")
    routes = region_terms_routes(obs.registry())
    print("smoke region_terms " + json.dumps(routes))
    if not (routes["sparse"] > 0 and routes["dense"] == 0):
        raise AssertionError(f"structured dispatches did not all take the "
                             f"sparse region terms: {routes}")
    mem = dev.memory_stats() or {}
    print(f"smoke peak_bytes_in_use={mem.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
