"""The readings a cell's ``correct`` limit is set from, on the chip.

    python3 bench/readings.py --workload <cell> --seconds <s> --seeds 1,2,3

For each seed, in one process: draws the cell's deployment, serves its
window at the cell's rate through the program, and prints one JSON line
with two readings of the check's widest gap: the program's (the lower
reading, from sound runs) and the control's (``bench.lib.control``: the
reference in the program's place at three bf16 passes, which has to read
above the limit).  The limit in ``bench/cells/<cell>.json`` lies between
the largest program reading and the smallest control reading.  The
benchmark's own runs do not run this script.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    _, cell, cfg, mix, cellf = run.load_cell(args.workload)
    dev = run.take_chip(cell)
    from bench.lib import check, control, harness, traffic
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        dep = harness.build(cfg, mix, seed)
        harness.warm(dep)
        queries = traffic.schedule(mix, cellf["rate_qps"], args.seconds,
                                   seed, cfg["scenarios"], mix["pool_rows"])
        rec = harness.serve_window(dep, queries, args.seconds)
        prog = check.compare(dep, rec.served, cellf["limits"]["gap"])
        ctrl = check.compare(
            dep, [harness.Served(s.q, verdict=s.verdict,
                                 result=control.answer(dep, s.q))
                  for s in rec.served if s.result is not None],
            cellf["limits"]["gap"])
        print(json.dumps({
            "workload": args.workload, "seed": seed, "kind": dev.device_kind,
            "queries": len(rec.served),
            "answered": sum(s.result is not None for s in rec.served),
            "program_gap": prog["numbers"]["gap"][0],
            "program_unanswered": prog["numbers"]["unanswered"][0],
            "control_gap": ctrl["numbers"]["gap"][0],
            "limit": cellf["limits"]["gap"],
            "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
