"""The reduction of the program's own spans (``bench.lib.program``) on the
CPU: a small trace counted by hand, and a served step's real spans."""

import glob

import numpy as np
import pytest

from bench.lib import program, trace

# the small trace of test_yardstick: window 10-20, device idle while a
# query was outstanding 11-11.5, 13.5-15, 16-18 and 18.5-20
HOST = [("bench.window", 10.0, 20.0), ("bench.wait", 10.0, 11.0),
        ("bench.step", 11.0, 14.0), ("bench.poll", 14.0, 14.5),
        ("bench.step", 14.5, 19.0), ("bench.submit", 19.0, 20.0),
        ("Transpose::ExecuteChunk", 16.0, 17.5),
        ("$service.py:341 step", 11.0, 14.0)]
DEVICE = [("fusion.1", 9.0, 10.5),
          ("%while.2 = (s32[], f32[8,4096]) while(...)", 11.5, 13.2),
          ("%edge_latency_pallas.6 = f32[8,56,128] custom-call(...)",
           12.0, 13.0),
          ("copy.2", 12.5, 13.5),
          ("edge_latency_pallas.6", 15.0, 16.0),
          ("fusion.1", 18.0, 18.5),
          ("fusion.1", 21.0, 22.0)]
# the program's spans over it: queries 5 and 6 queued during the wait, one
# step each; query 7 admitted at the end, carried by a chunk past the
# window; query 4 enqueued before it
PROGRAM = [
    ("serve.enqueue", 9.0, 9.1, {"query_id": 4}),
    ("serve.enqueue", 10.8, 10.85, {"query_id": 5}),
    ("serve.enqueue", 10.85, 10.9, {"query_id": 6}),
    ("serve.step", 11.0, 14.0, {"queries": 1, "rows": 3}),
    ("serve.chunk", 11.0, 14.0, {"bucket": 4, "rows": 3, "query_ids": 5}),
    ("serve.assemble", 11.0, 11.2, {"bucket": 4}),
    ("score_grid", 11.2, 13.6, {}),
    ("grid.upload", 11.2, 11.5, {"h2d_bytes": 1000}),
    ("serve.fetch", 13.6, 13.8, {"d2h_bytes": 32}),
    ("serve.finalize", 13.8, 14.0, {"queries": 1}),
    ("serve.step", 14.5, 19.0, {"queries": 1, "rows": 2}),
    ("serve.chunk", 14.5, 19.0, {"bucket": 2, "rows": 2,
                                 "query_ids": "6"}),
    ("serve.assemble", 14.5, 14.6, {"bucket": 2}),
    ("score_grid", 14.6, 18.2, {}),
    ("grid.upload", 14.6, 17.8, {"h2d_bytes": 3000}),
    ("serve.fetch", 18.2, 18.4, {"d2h_bytes": 16}),
    ("serve.finalize", 18.4, 19.0, {"queries": 1}),
    ("serve.admit", 19.1, 19.3, {"rows": 2}),
    ("serve.enqueue", 19.3, 19.4, {"query_id": 7}),
    ("serve.chunk", 21.0, 22.0, {"bucket": 4, "rows": 3,
                                 "query_ids": "4 7"})]


def _with_stats(events):
    return [e + ({},) for e in events]


def test_no_program_spans_leaves_the_reduction_as_it_is():
    base, prog = program.split(_with_stats(HOST), DEVICE)
    assert base == trace.reduce_planes(HOST, DEVICE)
    assert prog is None
    assert program.metrics(prog) == dict.fromkeys(
        ("upload_ms", "h2d_mb", "fetch_ms", "queue_wait_ms",
         "loop_host_ms", "idle_upload"))


def test_program_spans_by_hand():
    base, prog = program.split(_with_stats(HOST) + PROGRAM, DEVICE)
    plain = trace.reduce_planes(HOST, DEVICE)
    assert {k: v for k, v in base.items() if k != "idle_gaps"} == \
        {k: v for k, v in plain.items() if k != "idle_gaps"}
    assert [s for _, s in base["idle_gaps"]] == \
        [s for _, s in plain["idle_gaps"]]
    # 16-18: the upload 16-17.8 against score_grid 17.8-18; 13.5-15: the
    # second upload's 0.4 s against finalize's and fetch's 0.2; 18.5-20:
    # the finalize 18.5-19 against admit 0.2 and enqueue 0.1; 11-11.5: the
    # upload 0.3 against assemble 0.2
    assert base["idle_gaps"] == [
        ["bench.step:grid.upload:Transpose::ExecuteChunk", 2.0],
        ["bench.step:grid.upload:none", 1.5],
        ["bench.submit:serve.finalize:none", 1.5],
        ["bench.step:grid.upload:none", 0.5]]
    spans = {n: (c, pytest.approx(s)) for n, (c, s) in prog["spans"].items()}
    assert spans == {
        "serve.admit": (1, 0.2), "serve.enqueue": (3, 0.2),
        "serve.step": (2, 7.5), "serve.chunk": (2, 7.5),
        "serve.assemble": (2, 0.3), "score_grid": (2, 6.0),
        "grid.upload": (2, 3.5), "serve.fetch": (2, 0.4),
        "serve.finalize": (2, 0.8)}
    assert (prog["h2d_bytes"], prog["d2h_bytes"], prog["padded_rows"]) == \
        (4000, 48, 6)
    # queries 5, 6 and 7; 4 was enqueued before the window
    assert prog["queue_waits_s"] == pytest.approx([0.15, 3.6, 1.6])
    # step time outside score_grid and fetch: 0.2 + 0.2 + 0.1 + 0.6
    assert prog["step_self_s"] == pytest.approx(1.1)
    assert prog["idle_s"] == pytest.approx(5.5)
    by = {n: pytest.approx(s) for n, s in prog["idle_by_span"].items()}
    assert by == {"grid.upload": 2.5, "serve.finalize": 0.7,
                  "serve.assemble": 0.3, "score_grid": 0.3,
                  "serve.fetch": 0.2, "serve.admit": 0.2,
                  "serve.enqueue": 0.1}
    m = program.metrics(prog)
    assert m == pytest.approx({
        "upload_ms": 1750.0, "h2d_mb": 0.002, "fetch_ms": 200.0,
        "queue_wait_ms": (0.15 + 3.6 + 1.6) / 3 * 1e3,
        "loop_host_ms": 550.0, "idle_upload": 2.5 / 5.5 * 100})


def test_innermost_pieces_of_nested_spans():
    pieces = program._innermost([("a", 0.0, 10.0), ("b", 1.0, 4.0),
                                 ("c", 2.0, 3.0), ("d", 4.0, 6.0),
                                 ("e", 12.0, 13.0)])
    assert pieces == [(0.0, 1.0, "a"), (1.0, 2.0, "b"), (2.0, 3.0, "c"),
                      (3.0, 4.0, "b"), (4.0, 6.0, "d"), (6.0, 10.0, "a"),
                      (12.0, 13.0, "e")]


def test_a_served_step_reduces_from_a_real_trace(tmp_path):
    """The program's own spans, as a CPU profiler trace holds them, read
    through the reduction: every query waited, every upload counted."""
    import jax
    from jax.profiler import ProfileData

    from repro import obs
    from repro.core import ExplicitFleet, random_dag, random_placement
    from repro.serve import AdmissionConfig, WhatIfQuery, WhatIfService
    from repro.sim import pack_fleets

    rng = np.random.default_rng(0)
    g = random_dag(5, edge_prob=0.6, rng=rng)
    coms = np.asarray(pack_fleets([ExplicitFleet(
        com_cost=rng.uniform(0.1, 3.0, (4, 4))) for _ in range(2)]))
    xs = [np.stack([random_placement(5, np.ones((5, 4), bool), rng)
                    for _ in range(n)]).astype(np.float32)
          for n in (3, 1, 2)]
    svc = WhatIfService(g, admission=AdmissionConfig(p99_budget_s=1e6),
                        max_chunk_rows=4)
    fid = svc.register_fleet("t", coms)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    obs.enable()
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            for x in xs:
                svc.submit("t", fid, WhatIfQuery(kind="score", placements=x))
            with jax.profiler.TraceAnnotation("bench.step"):
                svc.step()
    finally:
        obs.disable()
        jax.profiler.stop_trace()
    (pb,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = [(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9, dict(e.stats))
            for plane in ProfileData.from_file(pb).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]
    base, prog = program.split(host, [])
    # 6 rows in chunks of 4: buckets 4 and 2
    assert prog["spans"]["serve.chunk"][0] == 2
    assert prog["padded_rows"] == 6
    assert len(prog["queue_waits_s"]) == 3
    assert all(w >= 0 for w in prog["queue_waits_s"])
    per_row = 5 * 4 * 4
    assert prog["h2d_bytes"] == sum(
        b * per_row + coms.nbytes + 4 * (2 * b + b) for b in (4, 2))
    # nothing ran on a device here: every active second is idle, and the
    # program's spans name what the host did in it
    assert base["busy_s"] == 0.0
    assert prog["idle_s"] == pytest.approx(base["active_s"])
    assert set(prog["idle_by_span"]) >= {"grid.upload", "serve.fetch"}
    m = program.metrics(prog)
    assert all(v is not None for v in m.values())
