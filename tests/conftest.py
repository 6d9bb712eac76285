import contextlib
import glob

import pytest


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running integration tests")


@pytest.fixture
def telemetry():
    """Enable telemetry against a fresh registry + trace buffer, restore
    the disabled default afterwards."""
    from repro import obs
    from repro.obs.spans import _fresh_trace

    saved = obs.registry()
    reg = obs.MetricsRegistry(enabled=False)
    obs.set_registry(reg)
    with _fresh_trace():
        obs.enable()
        try:
            yield reg
        finally:
            obs.disable()
            obs.set_registry(saved)


@contextlib.contextmanager
def _interpreted_pallas():
    from repro.kernels import dispatch

    substituted = dispatch.Route(pallas=True, interpret=True)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(dispatch, "route", lambda: substituted)
        yield substituted


@pytest.fixture(scope="session")
def pallas_route():
    """``with pallas_route():`` substitutes the dispatch route with the
    Pallas kernels, interpreted: evaluators built (and called) inside score
    through the edge kernels on the CPU as they do compiled on a chip.
    Session-scoped because it holds no state, so hypothesis tests can use
    it too."""
    return _interpreted_pallas


@pytest.fixture
def host_trace(tmp_path):
    """``with host_trace() as events:`` runs the block under the JAX
    profiler; afterwards ``events`` holds the host plane's events as
    ``(name, start_ns, end_ns, stats)``."""
    import jax
    from jax.profiler import ProfileData

    @contextlib.contextmanager
    def record():
        events = []
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            yield events
        finally:
            jax.profiler.stop_trace()
        (pb,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                          recursive=True)
        for plane in ProfileData.from_file(pb).planes:
            if plane.name.startswith("/host:"):
                events += [(e.name, e.start_ns, e.end_ns, dict(e.stats))
                           for line in plane.lines for e in line.events]

    return record
