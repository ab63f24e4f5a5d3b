"""The trace reduction on a small trace recorded on a TPU v5 lite: three
calls of one jitted program holding a Pallas kernel, each inside a
``QueryScheduler.step`` span and followed by a 20 ms sleep inside a
``bench.wait_for_arrival`` span, all inside the traced span."""
import os

import pytest

import bench_tiny  # noqa: F401  (import paths)
from lib import readers
from lib.trace import breakdown, op_label, reduce

SAMPLE = os.path.join(os.path.dirname(__file__), "data", "sample.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    import jax
    return reduce(jax.profiler.ProfileData.from_file(SAMPLE))


def test_busy_and_window(summary):
    assert summary.n_devices == 1
    assert 0.06 < summary.window_s < 0.07         # three 20 ms sleeps
    assert 0 < summary.busy_s < 1e-3              # microseconds of work
    ctx = readers.Context(window_s=1.0, trace=summary)
    assert 99.0 < readers.idle_share(ctx) < 100.0


def test_programs_and_kernels(summary):
    seconds, count = summary.modules_matching(["jit__lambda"])
    assert count >= 2 and 0 < seconds < 2 * summary.busy_s
    kernel = summary.ops_matching(["%pack_pallas"])
    assert 0 < kernel < seconds
    assert summary.ops_matching(["%no_such_kernel"]) == 0
    labels = [op_label(k) for k in summary.op_seconds]
    assert any(x.startswith("%pack_pallas.1 = s32[256,128] custom-call")
               for x in labels), labels


def test_idle_gaps_are_named_by_host_spans(summary):
    longest = summary.gaps[:3]
    assert [g[0] for g in longest] == ["bench.wait_for_arrival"] * 3
    assert all(0.019 < g[1] < 0.03 for g in longest)
    b = breakdown(summary)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["idle_gaps"][0][0] == "bench.wait_for_arrival"
