"""The benchmark's serving loop: set-up, the open-loop window, the drain.

The window drives the program's own entry, ``WhatIfService.submit`` ->
``step`` -> ``poll``, from one thread.  Each query is submitted as soon as
the loop sees it due; while anything is outstanding the loop calls
``step()`` and then polls every tenant, so a query's latency runs from its
due time to the poll that returns its final ``QueryResult``.  Arrivals
stop when the window closes; the loop then steps until every due query is
answered, or until ``DRAIN_S`` past the close, after which the rest count
as unanswered.

Set-up draws the data from the seed, builds the service, and warms every
shape the cell's traffic uses (each power-of-two bucket up to
``max_chunk_rows``, for each coalesce key the mix reaches) in a throwaway
service, so the measured one starts with no history that a compile
polluted while its executables are already loaded.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

from bench.lib import deploy, traffic

__all__ = ["DRAIN_S", "Served", "Record", "Deployment", "build", "warm",
           "serve_window"]

#: how long past the window's close the loop waits for due answers
DRAIN_S = 60.0

clock = time.perf_counter


@dataclasses.dataclass
class Served:
    """One scheduled query and what became of it."""

    q: traffic.Query
    submit_s: float | None = None     # host clock
    done_s: float | None = None
    verdict: str = "due"              # admitted, degraded, rejected
    result: object = None             # the final QueryResult


@dataclasses.dataclass
class Deployment:
    """What set-up made from the configuration and the seed."""

    cfg: dict
    mix: dict
    graph: deploy.Graph
    fleet: deploy.Dense | deploy.Structured
    pool: np.ndarray                  # (rows, n_ops, V) float32
    pool_idx: np.ndarray              # (rows, n_ops, K) device ids
    pool_w: np.ndarray                # (rows, n_ops, K) float32
    program_graph: object             # repro OpGraph
    pack: object                      # what register_fleet takes
    objectives: object                # repro ObjectiveSet or None

    @property
    def kind(self) -> str:
        return self.cfg["fleet"]["kind"]


@dataclasses.dataclass
class Record:
    """Everything a run measured, for the metric readers."""

    dep: Deployment
    served: list[Served]
    t0: float                         # window opens (schedule time 0)
    t_close: float                    # arrivals stop
    t_stop: float                     # the loop ended
    steps: list[tuple[float, float, int]]   # (start, end, real rows)
    dispatch: dict                    # ServeStats totals over the window
    compiles: int                     # jaxhooks backend compiles in it
    trace: dict | None = None         # bench.lib.trace.reduce output
    failed: set[int] = dataclasses.field(default_factory=set)  # by index

    @property
    def chunk_rows(self) -> list[int]:
        """Real rows of every dispatched chunk: a step cuts its queue's
        rows into ``max_chunk_rows`` chunks and one remainder."""
        m = self.dep.cfg["max_chunk_rows"]
        out = []
        for _, _, rows in self.steps:
            out += [m] * (rows // m) + ([rows % m] if rows % m else [])
        return out


def build(cfg: dict, mix: dict, seed: int) -> Deployment:
    """The graph, the fleets and the placement pool, from ``seed``."""
    from repro.core.devices import RegionFleetFamily
    from repro.core.graph import Operator, OpGraph
    from repro.core.objectives import ObjectiveSet

    g = deploy.draw_graph(cfg["graph"])
    fleet = deploy.draw_fleet(cfg, seed)
    pool, idx, w = deploy.placement_pool(
        np.random.default_rng([seed, 3]), mix["pool_rows"], g.n_ops,
        cfg["devices"], cfg["devices_per_op"])
    og = OpGraph([Operator(f"op{i}", float(g.selectivity[i]),
                           out_bytes=float(g.out_bytes[i]),
                           work=float(g.work[i]))
                  for i in range(g.n_ops)], g.edges)
    if isinstance(fleet, deploy.Dense):
        pack = fleet.com
    else:
        pack = RegionFleetFamily(region=fleet.region, inter=fleet.inter,
                                 degrade=fleet.degrade,
                                 self_cost=fleet.self_cost)
    obj = mix.get("pareto_objectives")
    return Deployment(cfg=cfg, mix=mix, graph=g, fleet=fleet, pool=pool,
                      pool_idx=idx, pool_w=w, program_graph=og, pack=pack,
                      objectives=(ObjectiveSet.from_weights(**obj)
                                  if obj else None))


def _service(dep: Deployment):
    from repro.serve import AdmissionConfig, WhatIfService
    svc = WhatIfService(dep.program_graph,
                        admission=AdmissionConfig(**dep.cfg["admission"]),
                        max_chunk_rows=dep.cfg["max_chunk_rows"])
    fids = {"single": svc.register_fleet("tenant-0", dep.pack)}
    if dep.objectives is not None:
        fids["multi"] = svc.register_fleet("tenant-0", dep.pack,
                                           objectives=dep.objectives)
    return svc, fids


def _query(dep: Deployment, q: traffic.Query):
    from repro.serve import WhatIfQuery
    x = dep.pool[q.row0:q.row0 + q.rows]
    dq = np.asarray(q.dq, np.float32) if isinstance(q.dq, tuple) else q.dq
    if q.kind == "joint":
        j = dep.mix["joint"]
        return WhatIfQuery(kind="joint", placements=x, beta=j["beta"],
                           dq_values=np.asarray(j["dq_values"]))
    return WhatIfQuery(kind=q.kind, placements=x, dq=dq, beta=q.beta,
                       top_k=dep.mix.get("rank_top_k", 1))


def warm(dep: Deployment) -> None:
    """Dispatch every bucket the mix can reach, on every key it uses,
    through a throwaway service: each program is loaded (or compiled)
    and run once before the window."""
    svc, fids = _service(dep)
    kinds = set(dep.mix["kinds"])
    m = dep.cfg["max_chunk_rows"]
    buckets = [1 << k for k in range(m.bit_length())]
    keyed = [("score", b) for b in buckets]
    if "pareto" in kinds:
        keyed += [("pareto", b) for b in buckets]
    keyed += [(kind, 4) for kind in sorted(kinds & {"rank", "joint"})]
    for kind, rows in keyed:
        q = traffic.Query(due_s=0.0, tenant=0, kind=kind, rows=rows, row0=0,
                          dq=0.5, beta=0.5)
        svc.submit("tenant-0", fids["multi" if kind == "pareto"
                                    else "single"], _query(dep, q))
        svc.drain()


def _totals(stats) -> dict:
    b = stats.buckets()
    return {"count": sum(x.dispatches for x in b),
            "seconds": sum(x.latency.sum for x in b),
            "rows": sum(x.rows for x in b),
            "padded_rows": sum(x.padded_rows for x in b),
            "recompiles": sum(x.recompiles for x in b)}


def serve_window(dep: Deployment, queries: list[traffic.Query],
                 seconds: float, annotate=None, on_open=None) -> Record:
    """Serve ``queries`` open-loop through a fresh service; the window
    opens when this is called (after ``on_open``, which starts a trace)."""
    from repro.obs import jaxhooks
    from repro.serve import Degraded, QueryResult, Rejected

    ann = annotate or (lambda name: contextlib.nullcontext())
    svc, fids = _service(dep)
    tenants = [f"tenant-{t}" for t in range(dep.mix["tenants"]["count"])]
    served = [Served(q) for q in queries]
    by_id: dict[int, Served] = {}
    steps = []
    if on_open is not None:
        on_open()
    snap = jaxhooks.snapshot()
    t0 = clock()
    t_close, t_end = t0 + seconds, t0 + seconds + DRAIN_S
    i, outstanding = 0, 0
    with ann("bench.window"):
        while True:
            now = clock()
            if now > t_end:
                break
            with ann("bench.submit"):
                while i < len(served) and t0 + served[i].q.due_s <= now:
                    s = served[i]
                    fid = fids["multi" if s.q.kind == "pareto" else "single"]
                    ticket = svc.submit(tenants[s.q.tenant], fid,
                                        _query(dep, s.q))
                    s.submit_s = clock()
                    if isinstance(ticket, Rejected):
                        s.verdict = "rejected"
                    else:
                        s.verdict = ("degraded" if isinstance(
                            ticket.admission, Degraded) else "admitted")
                        by_id[ticket.query_id] = s
                        outstanding += 1
                    i += 1
            if outstanding:
                rows0 = sum(b.rows for b in svc.stats.buckets())
                a = clock()
                with ann("bench.step"):
                    svc.step()
                b = clock()
                steps.append((a, b, sum(x.rows for x in svc.stats.buckets())
                              - rows0))
                with ann("bench.poll"):
                    for t in tenants:
                        for msg in svc.poll(t):
                            if isinstance(msg, QueryResult):
                                s = by_id[msg.query_id]
                                s.result, s.done_s = msg, clock()
                                outstanding -= 1
            elif i < len(served):
                with ann("bench.wait"):
                    time.sleep(max(0.0, min(t0 + served[i].q.due_s, t_end)
                                   - clock()))
            else:
                break
    t_stop = clock()
    return Record(dep=dep, served=served, t0=t0, t_close=t_close,
                  t_stop=t_stop, steps=steps, dispatch=_totals(svc.stats),
                  compiles=snap.delta()[0])
