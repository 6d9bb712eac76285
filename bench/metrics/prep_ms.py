"""Grid evaluator: device ms per dispatch outside the edge kernels (the
union of device-operation intervals in the traced window less the time
of the operations named ``edge_latency``).  In a structured cell this is
the region-mass precompute, the gathers and the pads around the kernel."""


def read(rec, peak):
    t, n = rec.trace, rec.dispatch["count"]
    if t is None or not n or not t["kernel_calls"]:
        return None
    return (t["busy_s"] - t["kernel_s"]) / n * 1e3
