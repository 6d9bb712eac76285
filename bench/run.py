"""The benchmark of the served what-if path on one TPU.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` from the root of a checkout: draws
the cell's deployment and traffic from ``--seed``, warms every shape the
traffic uses, serves the open-loop window through
``repro.serve.WhatIfService``, checks every answer against the float64
reference, and prints one JSON line last on standard output.  With
``--trace 0`` its metrics are the cell's end-to-end metrics; with
``--trace 1`` the window runs under the JAX profiler and the metrics are
the per-layer ones, each read by ``bench/metrics/<name>.py``.

Configurations, traffic mixes, cells and metrics are found by name:
``bench/configs/<config>.json``, ``bench/traffic/<mix>.json``,
``bench/cells/<cell>.json`` (the offered rate and the check's limit) and
``bench/metrics/<metric>.py``.

It exits non-zero with no result line when JAX's first device is not a
TPU, when there are fewer chips than the cell asks for, or when the
program is not beside it.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> tuple[dict, dict, dict, dict, dict]:
    """(BENCHMARK.json, workload entry, config, mix, cell file)."""
    bench = _json(ROOT / "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return (bench, cell, _json(ROOT / conf["file"]),
            _json(BENCH / "traffic" / f"{cell['traffic']}.json"),
            _json(BENCH / "cells" / f"{name}.json"))


def require_chips(n: int):
    """The first device, if it is a TPU and there are ``n`` of them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        print(f"bench: needs {n} TPU chip(s); JAX has {len(devs)} "
              f"{devs[0].platform} device(s) ({devs[0].device_kind})",
              file=sys.stderr)
        raise SystemExit(1)
    return devs[0]


def take_chip(cell: dict):
    """Point the program's compile cache into the checkout, take the
    cell's chips and turn the cache on; returns the first device.  JAX
    reads the cache directory when it is first imported, which
    ``require_chips`` does."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    dev = require_chips(cell["chips"])
    from repro.sim.execache import enable_persistent_cache
    enable_persistent_cache()
    return dev


def read_metric(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}",
        BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def end_to_end(rec, failed: set[int], setup_s: float) -> dict:
    """p50/p95 over every due query (a failed one counts as waiting until
    the loop ended), qps over the time to the last answer, setup_s."""
    lat, done = [], []
    for n, s in enumerate(rec.served):
        due = rec.t0 + s.q.due_s
        if n in failed or s.done_s is None:
            lat.append(rec.t_stop - due)
        else:
            lat.append(s.done_s - due)
            done.append(s.done_s)
    lat.sort()

    def pct(p):
        return lat[max(0, math.ceil(p * len(lat)) - 1)] * 1e3

    answered = len(rec.served) - len(failed)
    return {"p50_ms": pct(0.50), "p95_ms": pct(0.95),
            "qps": answered / (max(done) - rec.t0) if done else 0.0,
            "setup_s": setup_s}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench, cell, cfg, mix, cellf = load_cell(args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    dev = take_chip(cell)

    import jax

    from bench.lib import check, harness, peaks, traffic
    from bench.lib import trace as tracing

    peak = peaks.peak(dev.device_kind)
    dep = harness.build(cfg, mix, args.seed)
    queries = traffic.schedule(mix, cellf["rate_qps"], args.seconds,
                               args.seed, cfg["scenarios"], mix["pool_rows"])
    harness.warm(dep)
    gc.collect()

    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        rec = harness.serve_window(
            dep, queries, args.seconds, annotate=tracing.annotate,
            on_open=lambda: tracing.start(trace_dir))
        jax.profiler.stop_trace()
    else:
        rec = harness.serve_window(dep, queries, args.seconds)
    setup_s = rec.t0 - T_START
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    if trace_dir is not None:
        rec.trace = tracing.reduce(trace_dir, dev.id)
        tracing.remove(trace_dir)
        device["busy_s"] = rec.trace["busy_s"]
        device["window_s"] = rec.trace["window_s"]
    jax.clear_caches()
    gc.collect()

    verdict = check.compare(dep, rec.served, cellf["limits"]["gap"])
    failed = {n for n, s in enumerate(rec.served)
              if s.verdict != "admitted" or s.result is None} \
        | verdict["wrong"]
    rec.failed = failed
    out = {"correct": bool(verdict["correct"]),
           "attempted": len(rec.served), "failed": len(failed)}
    if args.trace:
        metrics = {}
        for m in bench["per_layer"]:
            if args.workload not in m.get("workloads", [args.workload]):
                continue
            v = read_metric(m["name"])(rec, peak)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        out["metrics"] = metrics
    else:
        e2e = end_to_end(rec, failed, setup_s)
        out["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in bench["end_to_end"]
            if args.workload in m.get("workloads", [args.workload])}
    out["device"] = device
    if args.trace:
        out["breakdown"] = {"device_ops": rec.trace["device_ops"],
                            "idle_gaps": rec.trace["idle_gaps"]}
    numbers = {k: {"value": v if math.isfinite(v) else str(v), "limit": lim}
               for k, (v, lim) in verdict["numbers"].items()}
    out["check"] = numbers
    lat = [s.done_s - rec.t0 - s.q.due_s for s in rec.served
           if s.done_s is not None]
    print(f"bench: {cell['name']} seed={args.seed} queries="
          f"{len(rec.served)} dispatches={rec.dispatch['count']} "
          f"drain_s={rec.t_stop - rec.t_close:.3f} setup_s={setup_s:.3f} "
          f"median_latency_s={statistics.median(lat) if lat else None}",
          file=sys.stderr)
    for k, v in numbers.items():
        print(f"check {k} = {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
