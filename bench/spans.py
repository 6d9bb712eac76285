"""A traced run of one cell with the program's own spans on.

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s>

Runs ``bench/run.py --trace 1`` with one difference: ``repro.obs`` is
enabled when the window's trace starts (after the warm-up), so the served
path's spans land on the profiler's host plane beside the device's
operations (``bench.lib.program``).  Its line is run.py's, with the
program's spans left out of what run.py reduces and its idle gaps named
by the innermost program span; one more line follows with the
``program`` block and the per-layer numbers read from it: ``upload_ms``,
``h2d_mb``, ``fetch_ms``, ``queue_wait_ms``, ``loop_host_ms`` and
``idle_upload``.  The difference between its ``dispatch_ms`` and
``host_ms`` and those of a plain ``--trace 1`` run on the same seed is
what the spans and the upload's wait cost.  The benchmark's own runs do
not run this script.
"""

from __future__ import annotations

import json
import statistics
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run  # noqa: E402  (puts the program on sys.path)
from bench.lib import program  # noqa: E402
from bench.lib import trace as tracing  # noqa: E402


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    found = {}
    start = tracing.start

    def start_with_spans(trace_dir: str) -> None:
        from repro import obs
        start(trace_dir)
        obs.enable()

    def reduce_with_spans(trace_dir: str, device_id: int = 0) -> dict:
        base, found["program"] = program.split(
            *program.read(trace_dir, device_id))
        return base

    tracing.start, tracing.reduce = start_with_spans, reduce_with_spans
    rc = run.main(argv + ["--trace", "1"])
    if rc == 0:
        prog = found.get("program")
        out = {"program": prog, "metrics": program.metrics(prog)}
        if prog is not None:
            w = sorted(prog["queue_waits_s"])
            out["program"] = {**prog, "queue_waits_s": {
                "n": len(w), "p50": statistics.median(w) if w else None,
                "p95": w[int(0.95 * (len(w) - 1))] if w else None,
                "max": w[-1] if w else None}}
        print(json.dumps(out))
    return rc


if __name__ == "__main__":
    sys.exit(main())
