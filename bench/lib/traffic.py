"""The one traffic generator: an open-loop schedule of what-if queries.

A mix file (``bench/traffic/<mix>.json``) gives the shape of the traffic;
the cell file gives its rate.  The timeline is part of the mix: the
per-slot arrival counts, the arrival times and the sequence of (kind,
rows, tenant) are drawn from the mix's fixed ``shape_seed`` for the cell's
rate and window, so every seed sees the same arrivals and the same work in
the same order.  The run's ``--seed`` draws the data: dq, beta and which
pool rows each query scores (and, in set-up, the fleets and placements).
A queue's tail at a hundred queries is set by how the bursts fall: with
the timeline drawn per seed, runs of different seeds on one TPU v5e
spread by about 40% on ``p95_ms`` where two runs of one seed differ by
about 5%.

Arrivals are Poisson per slot of ``slot_s`` seconds; a share
``burst_share`` of the slots runs at ``burst_factor`` times the base rate,
the base chosen so that the mean is the cell's rate.
"""

from __future__ import annotations

import dataclasses

import numpy as np

__all__ = ["Query", "schedule"]


@dataclasses.dataclass(frozen=True)
class Query:
    """One scheduled query: due ``due_s`` seconds after the window opens,
    scoring pool rows ``[row0, row0 + rows)``."""

    due_s: float
    tenant: int
    kind: str
    rows: int
    row0: int
    dq: float | tuple[float, ...] = 0.0
    beta: float = 0.0


def _slot_counts(rng: np.random.Generator, mix: dict, rate: float,
                 seconds: float) -> np.ndarray:
    """Arrivals per slot: a Poisson process with bursty slots, conditioned
    on ``rate * seconds`` arrivals in all, so the offered load is the
    cell's rate exactly."""
    arr = mix["arrivals"]
    n_slots = max(1, int(np.ceil(seconds / arr["slot_s"])))
    lam = np.ones(n_slots)
    lam[:int(round(arr["burst_share"] * n_slots))] = arr["burst_factor"]
    return rng.multinomial(int(round(rate * seconds)), lam / lam.sum())


def _rows(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    if "fixed" in spec:
        return np.full(n, int(spec["fixed"]))
    r = np.ceil(rng.lognormal(np.log(spec["median"]), spec["sigma"], n))
    return np.clip(r, spec["min"], spec["max"]).astype(int)


def schedule(mix: dict, rate: float, seconds: float, seed: int,
             n_scenarios: int, pool_rows: int) -> list[Query]:
    """The queries due in a window of ``seconds`` at ``rate`` queries/s,
    sorted by due time."""
    shape = np.random.default_rng([mix["shape_seed"], int(rate * 1e6),
                                   int(seconds * 1e3)])
    counts = shape.permutation(_slot_counts(shape, mix, rate, seconds))
    n = int(counts.sum())
    kinds = list(mix["kinds"])
    p = np.array([mix["kinds"][k] for k in kinds], np.float64)
    kind = shape.choice(len(kinds), n, p=p / p.sum())
    rows = _rows(shape, mix["rows"], n)
    t = mix["tenants"]
    pop = 1.0 / np.arange(1, t["count"] + 1) ** t["zipf"]
    tenant = shape.choice(t["count"], n, p=pop / pop.sum())
    slot_s = mix["arrivals"]["slot_s"]
    due = np.concatenate([
        np.sort(k * slot_s + shape.uniform(0.0, slot_s, c))
        for k, c in enumerate(counts)]) if n else np.zeros(0)
    due = due[due < seconds]

    rng = np.random.default_rng([seed, 2])
    fin = mix.get("finish", {})
    out = []
    for q, d in enumerate(due):
        k = kinds[kind[q]]
        r = int(rows[q])
        dq, beta = 0.0, 0.0
        if k != "joint" and fin:
            if rng.random() < fin["dq_per_scenario_share"]:
                dq = tuple(float(v) for v in np.sort(
                    rng.uniform(*fin["dq"], n_scenarios)))
            else:
                dq = float(rng.uniform(*fin["dq"]))
            if rng.random() >= fin["beta_zero_share"]:
                beta = float(rng.uniform(*fin["beta"]))
        out.append(Query(due_s=float(d), tenant=int(tenant[q]), kind=k,
                         rows=r, row0=int(rng.integers(0, pool_rows - r + 1)),
                         dq=dq, beta=beta))
    return out
