"""Rate sweep of one cell on the chip, to find its knee.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 10,20,40 [--drain <s>]

Builds and warms the cell's deployment once, then serves one open-loop
window per rate in the same process and prints one JSON line per rate:
median and 95th-percentile latency, answered queries per second, how long
the backlog took to drain after the window closed, and how many due
queries were left unanswered.  The knee is the highest rate whose backlog
does not grow over the window: its drain stays within a few dispatches.
The benchmark's runs do not use this script; a cell's rate is written
into ``bench/cells/<cell>.json`` from its output.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run  # noqa: E402  (puts the program on sys.path)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--drain", type=float, default=20.0)
    args = ap.parse_args(argv)

    _, cell, cfg, mix, _ = run.load_cell(args.workload)
    dev = run.take_chip(cell)
    from bench.lib import harness, traffic
    t = time.perf_counter()
    dep = harness.build(cfg, mix, args.seed)
    harness.warm(dep)
    print(f"sweep: {args.workload} on {dev.device_kind}: set-up "
          f"{time.perf_counter() - t:.1f} s", file=sys.stderr)
    harness.DRAIN_S = args.drain
    for rate in (float(r) for r in args.rates.split(",")):
        queries = traffic.schedule(mix, rate, args.seconds, args.seed,
                                   cfg["scenarios"], mix["pool_rows"])
        rec = harness.serve_window(dep, queries, args.seconds)
        e2e = run.end_to_end(rec, set(), 0.0)
        print(json.dumps({
            "workload": args.workload, "rate_qps": rate,
            "queries": len(queries), "p50_ms": e2e["p50_ms"],
            "p95_ms": e2e["p95_ms"], "qps": e2e["qps"],
            "drain_s": rec.t_stop - rec.t_close,
            "unanswered": sum(s.result is None for s in rec.served),
            "dispatches": rec.dispatch["count"],
            "dispatch_s": rec.dispatch["seconds"],
            "rows": rec.dispatch["rows"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
