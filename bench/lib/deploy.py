"""Deployment data drawn from seeds: the operator DAG and the fleets.

The graph and the structured fleets are copied from
``repro.sim.scenarios`` (``random_dag``, the layered family of
``random_graph`` with its per-operator payloads, and
``region_fleet_family``), so that the benchmark's data cannot move when the
program's generators change.  The dense fleets are hosts with the network
of COSTREAM's edge-cloud testbed (arXiv:2403.08444): each host's link has
a bandwidth of 25 Mbit/s to 10 Gbit/s and a delay of 1 to 160 ms.  The
data reaches the program only through its public constructors (``OpGraph``,
the dense pack as a plain ``(S, V, V)`` float32 array,
``RegionFleetFamily``).

Two seeds are in play.  The configuration's own ``layout_seed`` fixes what
the program compiles into its executables (the DAG, and for a structured
fleet its region assignment), so every run of a cell loads the same
programs from the persistent cache.  The run's ``--seed`` draws everything
that is an operand: link costs, degrade multipliers, placements.

Every fleet value is rounded to float32 before either side sees it: the
program computes in float32, and the float64 reference then prices exactly
the inputs the program was given.
"""

from __future__ import annotations

import dataclasses

import numpy as np

__all__ = ["Graph", "Dense", "Structured", "draw_graph", "draw_fleet",
           "placement_pool"]


@dataclasses.dataclass(frozen=True)
class Graph:
    """The operator DAG as plain arrays: per-operator selectivity, output
    bytes and work, and the edge list (i, j) with i < j."""

    selectivity: np.ndarray
    out_bytes: np.ndarray
    work: np.ndarray
    edges: tuple[tuple[int, int], ...]

    @property
    def n_ops(self) -> int:
        return int(self.selectivity.shape[0])

    @property
    def n_edges(self) -> int:
        return len(self.edges)


@dataclasses.dataclass
class Dense:
    """S explicit fleets of V hosts: ``com`` is the (S, V, V) float32
    pack of per-pair link costs, zero on the diagonal."""

    com: np.ndarray

    @property
    def n_scenarios(self) -> int:
        return int(self.com.shape[0])

    @property
    def n_devices(self) -> int:
        return int(self.com.shape[1])


@dataclasses.dataclass
class Structured:
    """S region fleets over one layout: ``com[u, v] = degrade[s, u] *
    degrade[s, v] * inter[s, region[u], region[v]]`` for u != v and
    ``self_cost`` on the diagonal."""

    region: np.ndarray     # (V,) int64
    inter: np.ndarray      # (S, R, R) float64 holding float32 values
    degrade: np.ndarray    # (S, V) float64 holding float32 values
    self_cost: float = 0.0

    @property
    def n_scenarios(self) -> int:
        return int(self.inter.shape[0])

    @property
    def n_devices(self) -> int:
        return int(self.region.shape[0])

    @property
    def n_regions(self) -> int:
        return int(self.inter.shape[1])


def _f32(a: np.ndarray) -> np.ndarray:
    return np.asarray(a, np.float32).astype(np.float64)


def draw_graph(spec: dict) -> Graph:
    """The layered random DAG of ``random_graph(rng, ScenarioConfig(n_ops=
    (n, n), graph_families=("layered",)))`` with ``rng =
    default_rng(seed)``, draw for draw."""
    rng = np.random.default_rng(spec["seed"])
    rng.integers(1)                       # the family pick among one family
    n = int(rng.integers(spec["n_ops"], spec["n_ops"] + 1))
    sel = np.array([float(rng.uniform(0.1, spec["max_selectivity"]))
                    for _ in range(n)])
    edges = []
    for j in range(1, n):
        parents = [i for i in range(j) if rng.random() < spec["edge_prob"]]
        if not parents:
            parents = [int(rng.integers(0, j))]
        edges.extend((i, j) for i in parents)
    out_bytes, work = np.empty(n), np.empty(n)
    for i in range(n):
        out_bytes[i] = float(rng.uniform(*spec["out_bytes"]))
        work[i] = float(rng.uniform(*spec["op_work"]))
    return Graph(selectivity=sel, out_bytes=out_bytes, work=work,
                 edges=tuple(edges))


def _regions(rng: np.random.Generator, n_regions: int,
             n_devices: int) -> np.ndarray:
    """A random partition of the devices into contiguous regions, each
    non-empty (``random_fleet`` with ``n_devices`` pinned)."""
    n_regions = min(n_regions, n_devices)
    per = 1 + rng.multinomial(n_devices - n_regions,
                              np.ones(n_regions) / n_regions)
    return np.repeat(np.arange(n_regions), per)


def _inter(rng: np.random.Generator, n_regions: int, p: dict) -> np.ndarray:
    inter = rng.lognormal(p["com_logmean"], p["com_logstd"],
                          (n_regions, n_regions))
    inter = (inter + inter.T) / 2.0
    np.fill_diagonal(inter, np.diag(inter) * p["intra_discount"])
    return inter


def _log_uniform(rng: np.random.Generator, lo_hi, shape) -> np.ndarray:
    lo, hi = np.log(lo_hi[0]), np.log(lo_hi[1])
    return np.exp(rng.uniform(lo, hi, shape))


def _host_links(rng: np.random.Generator, S: int, V: int,
                p: dict) -> np.ndarray:
    """(S, V, V) float32 seconds to move one unit of data between hosts.

    In each scenario every host's link gets a bandwidth (Mbit/s) and a
    delay (ms), log-uniform over the configuration's ranges.  A unit of
    ``unit_mbit`` goes at the slower end's bandwidth and waits both ends'
    delays; a host sends to itself for free."""
    com = np.empty((S, V, V), np.float32)
    for s in range(S):
        bw = _log_uniform(rng, p["bandwidth_mbit"], V)
        delay = _log_uniform(rng, p["delay_ms"], V) * 1e-3
        com[s] = (p["unit_mbit"] / np.minimum(bw[:, None], bw[None, :])
                  + (delay[:, None] + delay[None, :]))
        np.fill_diagonal(com[s], 0.0)
    return com


def draw_fleet(cfg: dict, seed: int) -> Dense | Structured:
    """The configuration's S fleets, their operands drawn from ``seed``."""
    p = cfg["fleet"]
    S, V = cfg["scenarios"], cfg["devices"]
    rng = np.random.default_rng([seed, 1])
    if p["kind"] == "dense":
        return Dense(com=_host_links(rng, S, V, p))
    if p["kind"] != "structured":
        raise ValueError(f"unknown fleet kind {p['kind']!r}")
    R = p["n_regions"]
    region = _regions(np.random.default_rng(cfg["layout_seed"]), R, V)
    base = _inter(rng, R, p)
    inters = np.empty((S, R, R))
    degrades = np.ones((S, V))
    for s in range(S):
        noise = rng.lognormal(0.0, p["region_jitter"], (R, R))
        inters[s] = base * (noise + noise.T) / 2.0
        d = degrades[s]
        straggler = rng.random(V) < p["straggler_prob"]
        d[straggler] *= rng.uniform(*p["degrade_factor"],
                                    int(straggler.sum()))
        outage = rng.random(R) < p["outage_prob"]
        if outage.all():
            outage[int(rng.integers(R))] = False
        d[outage[region]] *= p["outage_factor"]
    return Structured(region=region, inter=_f32(inters),
                      degrade=_f32(degrades))


def placement_pool(rng: np.random.Generator, rows: int, n_ops: int,
                   n_devices: int, per_op: int):
    """``rows`` candidate placements: each operator split over ``per_op``
    distinct random devices with Dirichlet(1) fractions.

    Returns the dense (rows, n_ops, V) float32 array the program is given
    and the same placements in sparse form, device ids (rows, n_ops,
    per_op) and their float32 fractions, which the reference reads."""
    idx = rng.integers(0, n_devices, (rows, n_ops, per_op))
    while True:      # redraw repeated devices of one operator
        srt = np.sort(idx, axis=-1)
        dup = (srt[..., 1:] == srt[..., :-1]).any(axis=-1)
        if not dup.any():
            break
        idx[dup] = rng.integers(0, n_devices, (int(dup.sum()), per_op))
    w = rng.gamma(1.0, 1.0, (rows, n_ops, per_op))
    w = (w / w.sum(axis=-1, keepdims=True)).astype(np.float32)
    x = np.zeros((rows, n_ops, n_devices), np.float32)
    r, o = np.meshgrid(np.arange(rows), np.arange(n_ops), indexing="ij")
    x[r[..., None], o[..., None], idx] = w
    return x, idx, w
