"""The ``prep_ms`` reader on the CPU: device time per dispatch outside the
edge kernels, and nothing where the run has no trace or no kernel."""

import types

import pytest

from bench import run

read = run.read_metric("prep_ms")


def _rec(trace, count=4):
    return types.SimpleNamespace(trace=trace, dispatch={"count": count})


def test_prep_ms_is_busy_time_outside_the_kernels_per_dispatch():
    t = {"busy_s": 1.0, "kernel_s": 0.2, "kernel_calls": 8}
    assert read(_rec(t), None) == pytest.approx((1.0 - 0.2) / 4 * 1e3)


@pytest.mark.parametrize("trace, count", [
    (None, 4),                                            # --trace 0
    ({"busy_s": 1.0, "kernel_s": 0.0, "kernel_calls": 0}, 4),  # no kernel
    ({"busy_s": 1.0, "kernel_s": 0.2, "kernel_calls": 8}, 0),  # no dispatch
])
def test_prep_ms_reads_nothing_without_a_traced_dispatch(trace, count):
    assert read(_rec(trace, count), None) is None
