"""The comparison that decides ``correct``, and the benchmark driven end to
end on the CPU at a tiny size: sound runs pass, the lower-precision
control and answers altered where they are produced fail."""

import json
import types

import numpy as np
import pytest

from bench import run
from bench.lib import check, control, harness, traffic
from bench.lib import reference as ref

LIMIT = 2e-6   # the cells' limit (bench/cells/*.json)
BENCHMARK = run._json(run.ROOT / "BENCHMARK.json")


def _tiny(name, devices, regions=4):
    """A cell, ``<config>.<mix>``, from its files with the scale cut for
    the CPU: the structured deployment too, which has no cell of its own
    until the program's co-location fault is fixed."""
    config, mix_name = name.rsplit(".", 1)
    cfg = run._json(run.BENCH / "configs" / f"{config}.json")
    mix = run._json(run.BENCH / "traffic" / f"{mix_name}.json")
    cfg = dict(cfg, devices=devices, scenarios=3)
    if cfg["fleet"]["kind"] == "structured":
        cfg["fleet"] = dict(cfg["fleet"], n_regions=regions)
    cell = {"name": name, "config": config, "traffic": mix_name, "chips": 1}
    return (BENCHMARK, cell, cfg, dict(mix, pool_rows=64),
            {"rate_qps": 40.0, "limits": {"gap": LIMIT}})


def _reference_answer(dep, q):
    """What a correct program returns, from the float64 reference."""
    rows = slice(q.row0, q.row0 + q.rows)
    lat = ref.latency(dep.graph, dep.fleet, dep.pool_idx[rows],
                      dep.pool_w[rows])
    if q.kind == "joint":
        j = dep.mix["joint"]
        _, best, idx = ref.joint(lat, np.asarray(j["dq_values"]), j["beta"])
        return types.SimpleNamespace(scores=best, dq_idx=idx,
                                     best=int(ref.worst(best).argmin()))
    dq = np.broadcast_to(np.asarray(q.dq, float), (lat.shape[0],))[:, None]
    f = ref.objective_f(lat, dq, q.beta)
    w = ref.worst(f)
    k = min(dep.mix["rank_top_k"], q.rows)
    if q.kind in ("score", "rank"):
        return types.SimpleNamespace(scores=f, worst=w,
                                     top=np.argsort(w, kind="stable")[:k])
    mov = np.broadcast_to(ref.network_movement(
        dep.graph, dep.pool_idx[rows], dep.pool_w[rows]), f.shape)
    vals = np.stack([w, ref.worst(mov)], axis=1)
    return types.SimpleNamespace(
        scores=f + 0.05 * mov, grids={"latency_f": f, "network_movement": mov},
        front=types.SimpleNamespace(indices=np.flatnonzero(
            ref.pareto_mask(vals))))


@pytest.fixture(scope="module")
def dense():
    _, _, cfg, mix, _ = _tiny("dense-v4096.mixed", 96)
    dep = harness.build(cfg, mix, seed=2 ** 31 + 11)
    qs = traffic.schedule(mix, 20.0, 6.0, 2 ** 31 + 11, cfg["scenarios"],
                          mix["pool_rows"])
    served = [harness.Served(q, verdict="admitted",
                             result=_reference_answer(dep, q)) for q in qs]
    return dep, served


def test_reference_answers_pass(dense):
    dep, served = dense
    assert {s.q.kind for s in served} == {"score", "rank", "joint", "pareto"}
    v = check.compare(dep, served, LIMIT)
    assert v["correct"] and v["numbers"]["gap"][0] < 1e-12


def _perturb(kind):
    def scores(r):
        r.scores = np.array(r.scores, float)
        r.scores[0, -1] *= 1 + 1e-3

    def top(r):
        r.top = np.array(r.top)[::-1]

    def dq_idx(r):
        r.dq_idx = (np.array(r.dq_idx) + 1) % 7

    def front(r):
        r.front.indices = np.arange(len(r.scores[0]))

    return {"score": scores, "rank": top, "joint": dq_idx,
            "pareto": front}[kind]


@pytest.mark.parametrize("kind", ["score", "rank", "joint", "pareto"])
def test_a_perturbed_answer_fails(dense, kind):
    dep, served = dense
    for s in served:
        s.result = _reference_answer(dep, s.q)
    victim = next(s for s in served if s.q.kind == kind and s.q.rows > 4
                  and (kind != "pareto"
                       or len(s.result.front.indices) < s.q.rows))
    _perturb(kind)(victim.result)
    v = check.compare(dep, served, LIMIT)
    assert not v["correct"] and v["numbers"]["gap"][0] > LIMIT
    assert v["wrong"] == {served.index(victim)}


def test_an_unanswered_query_fails(dense):
    dep, served = dense
    for s in served:
        s.result = _reference_answer(dep, s.q)
    served[3].result = None
    v = check.compare(dep, served, LIMIT)
    assert not v["correct"] and v["numbers"]["unanswered"] == (1, 0)
    served[3].result = _reference_answer(dep, served[3].q)


@pytest.mark.parametrize("name,devices", [("dense-v4096.mixed", 256),
                                          ("struct-v131072-r32.mixed", 4096)])
def test_control_fails_the_check(name, devices):
    """The reference in three bf16 passes, in the program's place, reads
    above the limit: enough cells meet its rounding."""
    _, _, cfg, mix, _ = _tiny(name, devices, regions=8)
    cfg = dict(cfg, scenarios=8)
    dep = harness.build(cfg, mix, seed=7)
    qs = traffic.schedule(mix, 30.0, 10.0, 7, cfg["scenarios"],
                          mix["pool_rows"])
    served = [harness.Served(q, verdict="admitted",
                             result=control.answer(dep, q)) for q in qs]
    v = check.compare(dep, served, LIMIT)
    assert not v["correct"] and v["numbers"]["gap"][0] > 2 * LIMIT


def _main(monkeypatch, tmp_path, name, devices, capsys):
    import jax
    from bench.lib import peaks
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(run, "load_cell",
                        lambda n: _tiny(n, devices))
    monkeypatch.setattr(run, "require_chips", lambda n: jax.devices()[0])
    monkeypatch.setattr("repro.sim.execache.enable_persistent_cache",
                        lambda: str(tmp_path))
    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind,
                        peaks.Peak(1e12, 1e11, "stands in for the chip"))
    assert run.main(["--workload", name, "--seed", str(2 ** 32 + 3),
                     "--seconds", "3", "--trace", "0"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("name,devices", [("dense-v4096.mixed", 64),
                                          ("struct-v131072-r32.mixed", 512),
                                          ("dense-v4096.interactive", 64)])
def test_a_run_is_correct(monkeypatch, tmp_path, capsys, name, devices):
    out = _main(monkeypatch, tmp_path, name, devices, capsys)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 50
    assert set(out["metrics"]) == {
        m["name"] for m in BENCHMARK["end_to_end"]
        if name in m.get("workloads", [name])}
    assert list(out)[-1] == "check"
    assert out["check"]["gap"]["limit"] == LIMIT


def _alter_grid(monkeypatch):
    """The grid evaluator's answer altered where it is produced."""
    from repro.sim.batched import BatchedEvaluator
    orig = BatchedEvaluator._finish_grid

    def finish(lat, S, dq, beta):
        return orig(lat, S, dq, beta).at[0, 0].multiply(1.001)
    monkeypatch.setattr(BatchedEvaluator, "_finish_grid",
                        staticmethod(finish))


def _alter_decision(monkeypatch):
    """The host's min-max pick altered where it is produced."""
    import repro.serve.service as service

    def robust_select(grid):
        worst = np.asarray(grid, np.float64).max(axis=0)
        return int(np.argmax(worst)), -worst
    monkeypatch.setattr(service, "robust_select", robust_select)


@pytest.mark.parametrize("fault", [_alter_grid, _alter_decision])
def test_a_run_with_a_fault_is_not_correct(monkeypatch, tmp_path, capsys,
                                           fault):
    from repro.sim import batched
    from repro.sim.execache import ExecutableCache, fresh_cache
    # evaluators and their jitted functions built anew, so the fault is
    # traced into the programs this run compiles
    monkeypatch.setattr(batched, "_shared_evaluators",
                        ExecutableCache(64, "evaluators"))
    fault(monkeypatch)
    with fresh_cache():
        out = _main(monkeypatch, tmp_path, "dense-v4096.mixed", 64, capsys)
    assert out["correct"] is False and out["failed"] > 0


def test_no_result_without_a_tpu(monkeypatch, capsys):
    """On the CPU the benchmark exits non-zero and prints nothing."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "dense-v4096.mixed", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert e.value.code == 1
    assert capsys.readouterr().out == ""
