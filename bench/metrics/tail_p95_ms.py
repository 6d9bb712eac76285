"""Serving loop: 95th percentile of query latency, in ms, over every
query due in the window, as ``bench/run.py`` took ``p95_ms`` end to end:
from the due time to the poll that returned the final answer, a failed
query counted as waiting until the loop ended.  Kept per layer because
its runs spread too widely for an end-to-end bound (the synchronous
loop's queue behind the pack copy sets it); it is the tail that
``p50_ms`` should pull down with it."""

import math


def read(rec, peak):
    lat = sorted((rec.t_stop if n in rec.failed or s.done_s is None
                  else s.done_s) - rec.t0 - s.q.due_s
                 for n, s in enumerate(rec.served))
    if not lat:
        return None
    return lat[max(0, math.ceil(0.95 * len(lat)) - 1)] * 1e3
