"""Edge kernels: the roofline's least time for the algorithm's work on the
real rows of every chunk (``bench.lib.work``), over the kernels' device
time, in %.  The bound that applies is the larger of operations over the
bf16 peak and bytes over HBM bandwidth, chunk by chunk."""

from bench.lib.work import grid_work, least_seconds


def read(rec, peak):
    t = rec.trace
    if t is None or not t["kernel_calls"] or not t["kernel_s"]:
        return None
    dep = rec.dep
    f = dep.fleet
    least = 0.0
    for rows in rec.chunk_rows:
        ops, nbytes = grid_work(
            dep.kind, rows, f.n_scenarios, dep.graph.n_edges, f.n_devices,
            dep.graph.n_ops, getattr(f, "n_regions", None))
        least += least_seconds(ops, nbytes, peak)[0]
    return least / t["kernel_s"] * 100.0
