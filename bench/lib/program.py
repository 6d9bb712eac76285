"""Reduction of the program's own spans in a JAX profiler trace.

With ``repro.obs`` enabled, the served path opens a profiler annotation at
each layer boundary (``repro.obs.span``), on the host plane and on the
same clock as the device's operations:

  * ``serve.admit`` (``rows``) and ``serve.enqueue`` (``query_id``) in
    ``WhatIfService.submit``;
  * ``serve.step`` (``queries``, ``rows``), and inside it per chunk
    ``serve.chunk`` (``bucket``, ``rows``, ``query_ids``: the ids of the
    chunk's queries, joined by spaces) holding ``serve.assemble``
    (``bucket``), ``score_grid``, ``serve.fetch`` (``d2h_bytes``) and
    ``serve.finalize`` (``queries``);
  * ``grid.upload`` (``h2d_bytes``) inside ``score_grid``.

:func:`split` takes them out of the host events before
``bench.lib.trace.reduce_planes`` sees them, so every number it gives
stays what it is on a trace without them, and names each of its idle gaps
by the innermost program span in the middle
(``bench.step:grid.upload:Transpose::ExecuteChunk``).  Beside it
:func:`split` returns the ``program`` block:

  * ``spans``: per span name, the count and summed seconds of the spans
    inside the ``bench.window`` annotation;
  * ``h2d_bytes`` / ``d2h_bytes``: the summed stats of those spans;
    ``padded_rows``: the summed ``bucket`` of the ``serve.chunk`` spans;
  * ``queue_waits_s``: for each query enqueued in the window and carried
    by a chunk, the start of the first ``serve.chunk`` that lists its id
    minus the end of its ``serve.enqueue``;
  * ``step_self_s``: the ``serve.step`` time outside ``score_grid`` and
    ``serve.fetch``;
  * ``idle_s`` and ``idle_by_span``: the device-idle active time (as
    ``device_idle`` counts it: nothing on the device while a query was
    outstanding) and the part of it that each innermost program span
    covers.

:func:`metrics` reads the six per-layer numbers from that block.  A trace
without program spans gives ``None`` for each.
"""

from __future__ import annotations

import collections
import statistics

from bench.lib import trace

__all__ = ["PROGRAM_SPANS", "read", "split", "metrics"]

#: name prefixes of the program's own spans (``repro.obs.span``)
PROGRAM_SPANS = ("serve.", "grid.", "score_grid")


def read(trace_dir: str, device_id: int = 0):
    """The host plane's events as ``(name, start_s, end_s, stats)`` and
    TPU ``device_id``'s operations as ``(name, start_s, end_s)``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(trace._xplane(trace_dir))
    host, device = [], []
    want = f"/device:TPU:{device_id}"
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9,
                          dict(e.stats)) for e in line.events]
        elif plane.name == want:
            for line in plane.lines:
                if line.name in trace.OP_LINES:
                    device += [(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                               for e in line.events]
    if not device:
        raise ValueError(f"no operations of {want} in the trace")
    return host, device


def _innermost(spans: list[tuple[str, float, float]]):
    """Disjoint ``(start, end, name)`` pieces of the time the spans cover,
    each named by the innermost span over it.  The spans come from one
    thread, so they nest."""
    out, stack, t = [], [], 0.0
    for name, a, b in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][1] <= a:
            n, e = stack.pop()
            if e > t:
                out.append((t, e, n))
                t = e
        if stack and a > t:
            out.append((t, a, stack[-1][0]))
        stack.append((name, b))
        t = a
    while stack:
        n, e = stack.pop()
        if e > t:
            out.append((t, e, n))
            t = e
    return out


def _cover(intervals, pieces) -> collections.Counter:
    """Seconds of the sorted disjoint ``intervals`` that each name of the
    sorted disjoint ``pieces`` covers."""
    got, j = collections.Counter(), 0
    for a, b in intervals:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            p0, p1, n = pieces[k]
            got[n] += min(b, p1) - max(a, p0)
            k += 1
    return got


def _gaps(host, device):
    """The stretches of the window with nothing on the device while a
    query was outstanding, and the ten that ``reduce_planes`` names, in
    its order."""
    w0, w1 = next((a, b) for n, a, b in host if n == "bench.window")
    busy = trace._union([(max(a, w0), min(b, w1)) for _, a, b in device
                         if b > w0 and a < w1])
    waits = trace._union([(max(a, w0), min(b, w1)) for n, a, b in host
                          if n == "bench.wait" and b > w0 and a < w1])
    gaps, prev = [], w0
    for a, b in busy + [(w1, w1)]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    idle = trace._minus(gaps, waits)
    return idle, sorted(idle, key=lambda g: g[0] - g[1])[:10]


def split(host, device) -> tuple[dict, dict | None]:
    """``bench.lib.trace.reduce_planes`` of the trace with the program's
    spans left out, its gap labels naming the innermost program span, and
    the ``program`` block (None without program spans).  ``host`` holds
    ``(name, start_s, end_s, stats)``, ``device`` ``(name, start_s,
    end_s)``."""
    prog = [e for e in host if e[0].startswith(PROGRAM_SPANS)]
    base = trace.reduce_planes(
        [e[:3] for e in host if not e[0].startswith(PROGRAM_SPANS)], device)
    if not prog:
        return base, None
    w0, w1 = next((a, b) for n, a, b, _ in host if n == "bench.window")
    inside = [e for e in prog if e[1] >= w0 and e[2] <= w1]
    pieces = _innermost([e[:3] for e in prog])
    idle, named = _gaps([e[:3] for e in host], device)
    labels = []
    for (label, s), (a, b) in zip(base["idle_gaps"], named):
        over = _cover([(a, b)], pieces)
        bench, _, rt = label.partition(":")
        mid = over.most_common(1)[0][0] if over else "none"
        labels.append([":".join([bench, mid] + ([rt] if rt else [])), s])
    base["idle_gaps"] = labels

    spans = collections.defaultdict(lambda: [0, 0.0])
    stat = collections.Counter()
    for n, a, b, st in inside:
        spans[n][0] += 1
        spans[n][1] += b - a
        for k in ("h2d_bytes", "d2h_bytes"):
            stat[k] += int(st.get(k, 0))
        if n == "serve.chunk":
            stat["padded_rows"] += int(st["bucket"])
    first = {}
    for n, a, b, st in sorted(prog, key=lambda e: e[1]):
        if n == "serve.chunk":
            for q in str(st.get("query_ids", "")).split():
                first.setdefault(int(q), a)
    waits = [first[int(st["query_id"])] - b for n, a, b, st in inside
             if n == "serve.enqueue" and int(st["query_id"]) in first]
    steps = trace._union([e[1:3] for e in inside if e[0] == "serve.step"])
    calls = trace._union([e[1:3] for e in inside
                          if e[0] in ("score_grid", "serve.fetch")])
    return base, {
        "spans": {n: spans[n] for n in sorted(spans)},
        "h2d_bytes": stat["h2d_bytes"], "d2h_bytes": stat["d2h_bytes"],
        "padded_rows": stat["padded_rows"],
        "queue_waits_s": waits,
        "step_self_s": trace._length(trace._minus(steps, calls)),
        "idle_s": trace._length(idle),
        "idle_by_span": dict(_cover(idle, pieces).most_common())}


def metrics(prog: dict | None) -> dict:
    """The per-layer numbers of a ``program`` block, each None where the
    block has nothing to read: ``upload_ms`` (mean ``grid.upload``),
    ``h2d_mb`` (host-to-device MB per upload), ``fetch_ms`` (mean
    ``serve.fetch``), ``queue_wait_ms`` (mean queue wait),
    ``loop_host_ms`` (``serve.step`` self time per chunk) and
    ``idle_upload`` (% of the device-idle active time under a
    ``grid.upload``)."""
    names = ("upload_ms", "h2d_mb", "fetch_ms", "queue_wait_ms",
             "loop_host_ms", "idle_upload")
    if prog is None:
        return dict.fromkeys(names)
    sp = prog["spans"]

    def mean_ms(name):
        n, s = sp.get(name, (0, 0.0))
        return s / n * 1e3 if n else None

    uploads = sp.get("grid.upload", (0, 0.0))[0]
    chunks = sp.get("serve.chunk", (0, 0.0))[0]
    w = prog["queue_waits_s"]
    return {
        "upload_ms": mean_ms("grid.upload"),
        "h2d_mb": prog["h2d_bytes"] / uploads / 1e6 if uploads else None,
        "fetch_ms": mean_ms("serve.fetch"),
        "queue_wait_ms": statistics.fmean(w) * 1e3 if w else None,
        "loop_host_ms": (prog["step_self_s"] / chunks * 1e3 if chunks
                         else None),
        "idle_upload": (prog["idle_by_span"].get("grid.upload", 0.0)
                        / prog["idle_s"] * 100.0 if prog["idle_s"]
                        else None)}
