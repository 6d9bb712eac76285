"""Load generator: 95th percentile of how late each query was submitted
after its due time, in ms.  It shows how long the synchronous serving
loop kept arrivals out of the queue."""

import math


def read(rec, peak):
    late = sorted(s.submit_s - rec.t0 - s.q.due_s for s in rec.served
                  if s.submit_s is not None)
    if not late:
        return None
    return late[max(0, math.ceil(0.95 * len(late)) - 1)] * 1e3
