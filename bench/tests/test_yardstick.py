"""The benchmark's yardstick on the CPU: work functions, peaks, the
traffic generator and the trace reduction."""

import numpy as np
import pytest

from bench.lib import peaks, trace, traffic, work

MIX = {"shape_seed": 0,
       "arrivals": {"slot_s": 1.0, "burst_share": 0.1, "burst_factor": 2.0},
       "tenants": {"count": 8, "zipf": 1.1},
       "kinds": {"score": 0.5, "rank": 0.25, "joint": 0.15, "pareto": 0.1},
       "rows": {"median": 6, "sigma": 1.0, "min": 1, "max": 64},
       "finish": {"dq": [0.0, 0.9], "dq_per_scenario_share": 0.33,
                  "beta": [0.1, 1.5], "beta_zero_share": 0.25}}


def test_grid_work_by_hand():
    # dense: 8 scenarios x 2*P*E*V^2, com read once per scenario, x once
    ops, nbytes = work.grid_work("dense", rows=64, S=8, E=51, V=4096,
                                 n_ops=16)
    assert ops == 8 * 2 * 64 * 51 * 4096 ** 2 == 876173328384
    assert nbytes == 4 * (8 * 4096 ** 2 + 64 * 16 * 4096)
    # structured: 8 x 2*P*E*R*V, A (R, V) once per scenario, x once
    ops, nbytes = work.grid_work("structured", rows=64, S=8, E=51,
                                 V=131072, n_ops=16, R=32)
    assert ops == 8 * 2 * 64 * 51 * 32 * 131072 == 219043332096
    assert nbytes == 4 * (8 * 32 * 131072 + 64 * 16 * 131072)
    with pytest.raises(ValueError):
        work.grid_work("sparse", 1, 1, 1, 1, 1)


def test_least_time_names_its_bound():
    v5e = peaks.peak("TPU v5 lite")
    # a full dense chunk is FLOP-bound, a one-row chunk HBM-bound on com
    t, bound = work.least_seconds(*work.grid_work(
        "dense", 64, 8, 51, 4096, 16), v5e)
    assert bound == "flops" and t == pytest.approx(876173328384 / 197e12)
    t, bound = work.least_seconds(*work.grid_work(
        "dense", 1, 8, 51, 4096, 16), v5e)
    assert bound == "hbm"
    assert t == pytest.approx(4 * (8 * 4096 ** 2 + 16 * 4096) / 819e9)


def test_unknown_chip_is_an_error():
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")


def test_schedule_is_deterministic_and_on_its_slots():
    a = traffic.schedule(MIX, 20.0, 30.0, seed=2 ** 33 + 5, n_scenarios=8,
                         pool_rows=256)
    b = traffic.schedule(MIX, 20.0, 30.0, seed=2 ** 33 + 5, n_scenarios=8,
                         pool_rows=256)
    assert a == b
    due = np.array([q.due_s for q in a])
    assert np.all(np.diff(due) >= 0) and due[0] >= 0 and due[-1] < 30.0
    assert len(a) == 600                      # rate x window, exactly
    for q in a:
        assert 1 <= q.rows <= 64 and 0 <= q.row0 <= 256 - q.rows
        assert q.kind in MIX["kinds"] and 0 <= q.tenant < 8
        if q.kind == "joint":
            assert q.dq == 0.0 and q.beta == 0.0


def test_every_seed_gets_the_same_timeline_and_other_data():
    a = traffic.schedule(MIX, 20.0, 30.0, seed=1, n_scenarios=8,
                         pool_rows=256)
    b = traffic.schedule(MIX, 20.0, 30.0, seed=2, n_scenarios=8,
                         pool_rows=256)
    assert [(q.due_s, q.kind, q.rows, q.tenant) for q in a] == \
        [(q.due_s, q.kind, q.rows, q.tenant) for q in b]
    assert [(q.row0, q.dq, q.beta) for q in a] != \
        [(q.row0, q.dq, q.beta) for q in b]
    # bursts: a tenth of the 1-s slots hold about twice the others' mean
    slots = np.sort(np.bincount([int(q.due_s) for q in a], minlength=30))
    assert slots[-3:].mean() > 1.5 * np.median(slots)


def test_interval_cut_keeps_what_no_hole_covers():
    cut = trace._minus([(0.0, 4.0), (5.0, 6.0), (7.0, 9.0)],
                       [(1.0, 2.0), (3.0, 5.5), (8.0, 8.5), (10.0, 11.0)])
    assert cut == [(0.0, 1.0), (2.0, 3.0), (5.5, 6.0), (7.0, 8.0),
                   (8.5, 9.0)]
    assert trace._minus([(0.0, 1.0)], []) == [(0.0, 1.0)]
    assert trace._minus([(1.0, 2.0)], [(0.0, 3.0)]) == []


def test_trace_reduction_on_a_small_trace():
    host = [("bench.window", 10.0, 20.0), ("bench.wait", 10.0, 11.0),
            ("bench.step", 11.0, 14.0), ("bench.poll", 14.0, 14.5),
            ("bench.step", 14.5, 19.0), ("bench.submit", 19.0, 20.0),
            ("Transpose::ExecuteChunk", 16.0, 17.5),
            ("$service.py:341 step", 11.0, 14.0)]
    device = [("fusion.1", 9.0, 10.5),                 # clipped at the window
              ("%while.2 = (s32[], f32[8,4096]) while(...)", 11.5, 13.2),
              ("%edge_latency_pallas.6 = f32[8,56,128] custom-call(...)",
               12.0, 13.0),
              ("copy.2", 12.5, 13.5),                  # overlaps: union
              ("edge_latency_pallas.6", 15.0, 16.0),
              ("fusion.1", 18.0, 18.5),
              ("fusion.1", 21.0, 22.0)]                # outside
    r = trace.reduce_planes(host, device)
    assert r["window_s"] == 10.0
    assert r["busy_s"] == pytest.approx(0.5 + 2.0 + 1.0 + 0.5)
    # the wait for the next arrival (10-11) is out of the active time,
    # and so is the device work inside it (10-10.5)
    assert r["active_s"] == 9.0
    assert r["active_busy_s"] == pytest.approx(2.0 + 1.0 + 0.5)
    # the while holding the kernel is not counted beside it
    assert r["kernel_s"] == 2.0 and r["kernel_calls"] == 2
    assert r["device_ops"][0] == ["edge_latency_pallas.6", 2.0]
    assert "while.2" not in dict(r["device_ops"])
    # idle 16-18 under a step; 13.5-15 step 1.0 against poll 0.5; 18.5-20
    # mostly submit; of 10.5-11.5 only 11-11.5 is past the wait; the
    # runtime's events name what the host did
    assert r["idle_gaps"] == [
        ["bench.step:Transpose::ExecuteChunk", 2.0],
        ["bench.step:none", 1.5], ["bench.submit:none", 1.5],
        ["bench.step:none", 0.5]]
    with pytest.raises(ValueError):
        trace.reduce_planes([("bench.step", 0.0, 1.0)], device)


def test_tail_counts_a_failed_query_as_waiting_to_the_end():
    import importlib.util
    import types
    from pathlib import Path
    path = Path(__file__).resolve().parents[1] / "metrics" / "tail_p95_ms.py"
    spec = importlib.util.spec_from_file_location("tail_p95_ms", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    def served(due, done):
        return types.SimpleNamespace(q=types.SimpleNamespace(due_s=due),
                                     done_s=done)
    # 20 queries due at 0..19 s, each answered 0.1 s after it was due
    rec = types.SimpleNamespace(
        t0=100.0, t_stop=150.0, failed=set(),
        served=[served(float(n), 100.0 + n + 0.1) for n in range(20)])
    assert mod.read(rec, None) == pytest.approx(100.0)
    # one failed, one never answered: both wait until the loop ended;
    # the 19th of 20 is the shorter of those two waits
    rec.failed = {3}
    rec.served[7].done_s = None
    assert mod.read(rec, None) == pytest.approx((150.0 - 100.0 - 7) * 1e3)
    rec.served = []
    assert mod.read(rec, None) is None
