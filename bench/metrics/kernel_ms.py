"""Edge kernels: device ms per dispatch of the trace's operations whose
names contain ``edge_latency``."""


def read(rec, peak):
    t, n = rec.trace, rec.dispatch["count"]
    if t is None or not n or not t["kernel_calls"]:
        return None
    return t["kernel_s"] / n * 1e3
