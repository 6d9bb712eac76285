"""repro.obs telemetry layer: registry semantics, span split, trace schema,
recompile accounting, and the hard invariant that enabling telemetry never
changes numerics (same rng streams, same dispatch count, bitwise argmin)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.obs import bench as obench
from repro.obs import jaxhooks, perfbridge
from repro.obs.spans import _NULL, _fresh_trace


# -- registry -----------------------------------------------------------------

def test_registry_disabled_by_default():
    assert not obs.enabled()
    # disabled spans are the shared no-op and the buffer never grows
    with _fresh_trace():
        with obs.span("x", a=1) as sp:
            sp.sync(jnp.ones(2))
        assert obs.trace_events() == []
        obs.counter_sample("c", 1.0)
        assert obs.trace_events() == []


def test_counter_gauge_histogram(telemetry):
    reg = telemetry
    reg.counter("c", path="dense").add(2)
    reg.counter("c", path="dense").add(3)
    reg.counter("c", path="structured").add(1)
    reg.gauge("g").set(7.5)
    h = reg.histogram("h", lo=1.0, growth=2.0, n_buckets=8)
    for v in (1.5, 3.0, 100.0):
        h.observe(v)
    assert reg.value("c", path="dense") == 5
    assert reg.value("c", path="structured") == 1
    assert reg.value("g") == 7.5
    row = h.row()
    assert row["count"] == 3 and row["max"] == 100.0
    assert sum(row["buckets"]) == 3
    names = {(r["name"], tuple(sorted(r["labels"].items())))
             for r in reg.snapshot()}
    assert ("c", (("path", "dense"),)) in names


# -- spans --------------------------------------------------------------------

def test_span_records_compile_and_execute_split(telemetry):
    @jax.jit
    def f(x):
        return (x @ x).sum()

    x = jnp.ones((64, 64))
    with obs.span("cold", n=64) as sp:
        sp.sync(f(x))
    assert sp.n_compiles >= 1
    assert sp.compile_s > 0
    assert sp.wall_s >= sp.compile_s
    with obs.span("warm", n=64) as sp2:
        sp2.sync(f(x))
    assert sp2.n_compiles == 0 and sp2.compile_s == 0.0
    evs = obs.trace_events()
    assert [e["name"] for e in evs if e["ph"] == "X"] == ["cold", "warm"]
    assert evs[0]["args"]["synced"] is True


def test_spans_land_in_the_profiler_trace(telemetry, host_trace):
    """Enabled spans are profiler annotations too: on the host plane, with
    their names, nesting and arguments (``set`` ones included)."""
    with host_trace() as events:
        with obs.span("outer", n=3, ids="3 4 7"):
            with obs.span("inner", k=1.5) as sp:
                sp.set(h2d_bytes=64)
    got = {e[0]: e for e in events if e[0] in ("outer", "inner")}
    outer, inner = got["outer"], got["inner"]
    assert outer[1] <= inner[1] < inner[2] <= outer[2]
    assert outer[3] == {"n": 3, "ids": "3 4 7"}
    assert inner[3] == {"k": 1.5, "h2d_bytes": 64}
    (ev,) = [e for e in obs.trace_events() if e["name"] == "inner"]
    assert ev["args"]["h2d_bytes"] == 64


def test_disabled_spans_make_no_annotation(monkeypatch):
    def refuse(*args, **kwargs):
        raise AssertionError("annotation made with telemetry off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    assert not obs.enabled()
    with obs.span("x", a=1) as sp:
        sp.set(b=2)
    assert sp is _NULL


def test_span_nesting_attributes_innermost(telemetry):
    @jax.jit
    def g(x):
        return x * 3.0

    with obs.span("outer") as outer:
        with obs.span("inner") as inner:
            inner.sync(g(jnp.ones(5)))
    assert inner.n_compiles >= 1
    assert outer.n_compiles == 0  # attributed to the innermost span only


def test_trace_export_roundtrip(tmp_path, telemetry):
    with obs.span("a", k=1):
        pass
    obs.counter_sample("drift", 0.25, extra=1.0)
    path = tmp_path / "t.trace.jsonl"
    n = obs.export_trace(path)
    assert n == 2
    # JSONL: every line is a standalone, schema-valid Chrome-trace event
    lines = path.read_text().strip().splitlines()
    assert len(lines) == 2
    for line in lines:
        json.loads(line)
    back = obs.load_trace(path)
    assert [e["ph"] for e in back] == ["X", "C"]


def test_validate_events_rejects_malformed():
    with pytest.raises(ValueError, match="missing keys"):
        obs.validate_events([{"name": "x", "ph": "X"}])
    with pytest.raises(ValueError, match="dur"):
        obs.validate_events([{"name": "x", "ph": "X", "ts": 0.0,
                              "pid": 1, "tid": 1}])
    with pytest.raises(ValueError, match="numeric series"):
        obs.validate_events([{"name": "x", "ph": "C", "ts": 0.0,
                              "pid": 1, "tid": 1, "args": {}}])
    with pytest.raises(ValueError, match="unknown phase"):
        obs.validate_events([{"name": "x", "ph": "B", "ts": 0.0,
                              "pid": 1, "tid": 1}])


# -- recompile accounting -----------------------------------------------------

def test_snapshot_counts_fresh_compile_and_cache_hit():
    @jax.jit
    def f(x):
        return x + 1.0

    snap = jaxhooks.snapshot()
    f(jnp.ones(3))                       # fresh shape → backend compile
    n1, s1 = snap.delta()
    assert n1 >= 1 and s1 > 0
    snap2 = jaxhooks.snapshot()
    f(jnp.ones(3))                       # cache hit → silence
    n2, _ = snap2.delta()
    assert n2 == 0
    snap3 = jaxhooks.snapshot()
    f(jnp.ones(4))                       # new shape → silent-retrace signal
    n3, _ = snap3.delta()
    assert n3 >= 1


def test_measure_surfaces_recompile_in_timed_region():
    @jax.jit
    def f(x):
        return (x * 2.0).sum()

    t = obench.measure(lambda: f(jnp.ones(7)), n=3, warmup=1)
    assert t.n_recompiles == 0           # warmup absorbed the compile
    assert len(t.times) == 3 and t.seconds > 0
    assert t.result is not None
    # arrays precreated so their own fill-kernels compile OUTSIDE the
    # timed region; each f(new shape) then costs exactly one compile
    arrs = iter([jnp.ones(n) for n in (11, 12, 13, 14)])
    t2 = obench.measure(lambda: f(next(arrs)), n=3, warmup=1)
    assert t2.n_recompiles == 3          # every timed call hit a new shape
    row = t2.row()
    assert row["n_recompiles"] == 3 and row["n_timed"] == 3


# -- perf bridge --------------------------------------------------------------

def test_hlo_record_fields():
    @jax.jit
    def f(a, b):
        return (a @ b).sum()

    a = jnp.ones((32, 32))
    rec = perfbridge.hlo_record(f, args=(a, a), measured_s=1e-3)
    assert rec["hlo_flops"] == pytest.approx(2 * 32 ** 3, rel=0.05)
    assert rec["hlo_bytes"] > 0
    assert rec["roofline_fraction"] is not None
    assert 0 < rec["roofline_fraction"]
    assert "n_recompiles" in rec and "roofline" in rec


# -- instrumented subsystems publish; numerics stay bitwise-identical ---------

def _tiny_problem(seed=0):
    from repro.core import ExplicitFleet, PlacementProblem, linear_graph

    rng = np.random.default_rng(seed)
    com = rng.uniform(0.1, 3.0, (5, 5))
    com = (com + com.T) / 2.0
    np.fill_diagonal(com, 0.0)
    g = linear_graph([1.0, 0.8, 1.2, 0.9])
    return PlacementProblem(g, ExplicitFleet(com_cost=com), beta=1.0)


def test_search_metrics_published(telemetry):
    from repro.search import BatchedProblem, random_search

    prob = _tiny_problem()
    eng = BatchedProblem(prob)
    random_search(prob, np.random.default_rng(3), n_candidates=32,
                  engine=eng)
    reg = telemetry
    assert reg.value("search.dispatches") == eng.dispatches
    assert reg.value("search.candidates") >= 32
    assert reg.value("eval.score_grid.dispatches",
                     path="dense") == eng.dispatches
    # every padded shape this run used was a first-seen bucket
    firsts = [r for r in reg.snapshot()
              if r["name"] == "search.bucket_first_dispatch"]
    assert len(firsts) == len(eng._seen_buckets)
    spans = [e for e in obs.trace_events()
             if e["ph"] == "X" and e["name"] == "search.score_batch"]
    assert len(spans) >= 1


def test_enabling_telemetry_never_changes_numerics():
    from repro.search import BatchedProblem, random_search

    def solve():
        prob = _tiny_problem(seed=1)
        eng = BatchedProblem(prob)
        res = random_search(prob, np.random.default_rng(7),
                            n_candidates=48, engine=eng)
        return res, eng.dispatches, eng.evals

    res_off, disp_off, evals_off = solve()
    saved = obs.registry()
    obs.set_registry(obs.MetricsRegistry(enabled=False))
    try:
        with _fresh_trace():
            obs.enable()
            res_on, disp_on, evals_on = solve()
    finally:
        obs.disable()
        obs.set_registry(saved)
    # the hard invariant: identical rng streams, dispatch count, and a
    # BITWISE-equal argmin — instrumentation only reads computed values
    assert disp_on == disp_off and evals_on == evals_off
    np.testing.assert_array_equal(res_on.x, res_off.x)
    assert res_on.F == res_off.F
    assert res_on.dq_fraction == res_off.dq_fraction


# -- histogram quantile export ------------------------------------------------

def test_histogram_quantile_basics():
    """p50/p95/p99 from exponential buckets: estimates land within one
    growth factor of the true quantile, q=0/q=1 hit the exactly-tracked
    min/max, and the estimate is always clamped inside [min, max]."""
    h = obs.Histogram("t", {}, lo=1e-6)
    vals = [0.001 * (i + 1) for i in range(100)]     # 1ms .. 100ms
    for v in vals:
        h.observe(v)
    true = np.quantile(vals, [0.5, 0.95, 0.99])
    for q, want in zip([0.5, 0.95, 0.99], true):
        est = h.quantile(q)
        assert want / h.growth <= est <= want * h.growth
        assert h.min <= est <= h.max
    assert h.quantile(0.0) == h.min
    assert h.quantile(1.0) == h.max
    # monotone in q
    qs = [h.quantile(q) for q in np.linspace(0, 1, 21)]
    assert all(a <= b for a, b in zip(qs, qs[1:]))


def test_histogram_quantile_edge_cases():
    h = obs.Histogram("t", {}, lo=1e-6)
    assert np.isnan(h.quantile(0.5))                 # empty → NaN
    with pytest.raises(ValueError, match="0 <= q <= 1"):
        h.quantile(1.5)
    h.observe(0.25)
    # single observation: every quantile IS that observation (clamping)
    for q in (0.0, 0.5, 0.99, 1.0):
        assert h.quantile(q) == 0.25
    # underflow bucket: observations at/below lo still answer sanely
    h2 = obs.Histogram("t2", {}, lo=1.0)
    for _ in range(10):
        h2.observe(0.5)
    assert h2.quantile(0.5) == 0.5                   # clamped to min==max
    d = h2.quantiles()
    assert set(d) == {"p50", "p95", "p99"}


def test_histogram_row_exports_quantiles(telemetry):
    hist = telemetry.histogram("q.test", lo=1e-3)
    assert hist.row()["p50"] is None                 # empty export
    for v in (0.1, 0.2, 0.4):
        hist.observe(v)
    row = hist.row()
    assert row["count"] == 3
    for k in ("p50", "p95", "p99"):
        assert row["min"] <= row[k] <= row["max"]
