"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The harness wraps the window in a ``bench.window`` annotation and each
submit, step, poll and wait in ``bench.<what>`` annotations
(``jax.profiler.TraceAnnotation``), which land on the host plane on the
same clock as the device's operations.  From the trace this keeps:

  * ``window_s``: the length of the ``bench.window`` annotation;
  * ``busy_s``: the union of the device's operation intervals inside it;
  * ``active_s`` / ``active_busy_s``: the same two with the loop's
    ``bench.wait`` spans taken out, which is when no query was
    outstanding: idle time there is the offered load's slack, not a stall;
  * ``kernel_s`` / ``kernel_calls``: summed device time and count of the
    operations whose name contains ``edge_latency`` (the Pallas edge
    kernels' names);
  * ``device_ops``: the ten operations with the most device time, by
    HLO instruction name, leaves only (a ``while`` that holds the lax.map
    over scenarios is not counted beside the kernels it runs);
  * ``idle_gaps``: the ten longest stretches with nothing on the device
    while a query was outstanding, each named by the ``bench.*`` annotation that covered most of it and
    by the host activity (the runtime's own trace events, on any thread)
    that overlapped it most, as ``bench.step:Transpose::ExecuteChunk``.
"""

from __future__ import annotations

import collections
import glob
import os
import shutil

__all__ = ["annotate", "start", "reduce", "reduce_planes", "remove",
           "KERNEL_TAG"]

KERNEL_TAG = "edge_latency"
#: device lines that hold the operations that ran (TPU planes)
OP_LINES = ("XLA Ops",)


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def start(trace_dir: str) -> None:
    """Start the profiler without its Python function tracer, whose
    per-call events would slow the host loop the trace is measuring."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def remove(trace_dir: str) -> None:
    shutil.rmtree(trace_dir, ignore_errors=True)


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _minus(intervals: list[tuple[float, float]],
           holes: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted disjoint ``intervals`` with sorted disjoint ``holes`` cut
    out, in one pass over both."""
    out, j = [], 0
    for a, b in intervals:
        while j < len(holes) and holes[j][1] <= a:
            j += 1
        k = j
        while k < len(holes) and holes[k][0] < b:
            if holes[k][0] > a:
                out.append((a, holes[k][0]))
            a = max(a, holes[k][1])
            k += 1
        if a < b:
            out.append((a, b))
    return out


def _length(intervals: list[tuple[float, float]]) -> float:
    return sum(b - a for a, b in intervals)


def _leaves(ops: list[tuple[str, float, float]]) -> list[bool]:
    """Which operations contain no other operation of their line."""
    order = sorted(range(len(ops)), key=lambda k: (ops[k][1], -ops[k][2]))
    leaf = [True] * len(ops)
    stack: list[int] = []
    for k in order:
        while stack and ops[stack[-1]][2] <= ops[k][1]:
            stack.pop()
        if stack and ops[k][2] <= ops[stack[-1]][2]:
            leaf[stack[-1]] = False
        stack.append(k)
    return leaf


def _most_overlap(events, a: float, b: float) -> str:
    cover = collections.Counter()
    for n, s0, s1 in events:
        if s1 > a and s0 < b:
            cover[n] += min(s1, b) - max(s0, a)
    return cover.most_common(1)[0][0] if cover else "none"


def reduce_planes(host: list[tuple[str, float, float]],
                  device: list[tuple[str, float, float]]) -> dict:
    """The numbers above from plain events: ``host`` holds the host
    plane's events (the ``bench.*`` annotations among them) and
    ``device`` the operations of one device, each ``(name, start_s,
    end_s)`` on one clock."""
    win = [(a, b) for n, a, b in host if n == "bench.window"]
    if not win:
        raise ValueError("no bench.window annotation in the trace")
    w0, w1 = win[0]
    ops = [(n.split(" = ")[0].lstrip("%"), max(a, w0), min(b, w1))
           for n, a, b in device if b > w0 and a < w1]
    busy = _union([(a, b) for _, a, b in ops])
    waits = _union([(max(a, w0), min(b, w1)) for n, a, b in host
                    if n == "bench.wait" and b > w0 and a < w1])
    per_op = collections.Counter()
    kernel_s, kernel_calls = 0.0, 0
    for (n, a, b), leaf in zip(ops, _leaves(ops)):
        if not leaf:
            continue
        per_op[n] += b - a
        if KERNEL_TAG in n:
            kernel_s += b - a
            kernel_calls += 1
    gaps, prev = [], w0
    for a, b in busy + [(w1, w1)]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    spans = [(n, a, b) for n, a, b in host
             if n.startswith("bench.") and n != "bench.window"]
    runtime = [(n, a, b) for n, a, b in host
               if not n.startswith(("bench.", "$"))]
    named = []
    for a, b in sorted(_minus(gaps, waits), key=lambda g: g[0] - g[1])[:10]:
        what = _most_overlap(spans, a, b)
        if runtime:
            what += ":" + _most_overlap(runtime, a, b)
        named.append([what, b - a])
    return {"window_s": w1 - w0, "busy_s": _length(busy),
            "active_s": w1 - w0 - _length(waits),
            "active_busy_s": _length(_minus(busy, waits)),
            "kernel_s": kernel_s,
            "kernel_calls": kernel_calls,
            "device_ops": [[n, s] for n, s in per_op.most_common(10)],
            "idle_gaps": named}


def _xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under "
                                f"{trace_dir}, found {found}")
    return found[0]


def reduce(trace_dir: str, device_id: int = 0) -> dict:
    """Read the profiler's trace in ``trace_dir`` for TPU ``device_id``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(_xplane(trace_dir))
    host, device = [], []
    want = f"/device:TPU:{device_id}"
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                         for e in line.events]
        elif plane.name == want:
            for line in plane.lines:
                if line.name in OP_LINES:
                    device += [(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                               for e in line.events]
    if not device:
        raise ValueError(f"no operations of {want} in the trace")
    return reduce_planes(host, device)
