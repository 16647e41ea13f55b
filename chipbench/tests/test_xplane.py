"""The trace reduction on a small trace recorded on the chip.

``data/small.xplane.pb`` was written by ``record_trace.py`` on one TPU v5
lite: two sweeps (the harness's ``chipbench.sweep 0`` / ``1`` spans) of a
cut Fig. 4 grid, two lane-scan programs per sweep. Its four program
executions (``XLA Modules``) last 1,949,022 + 1,561,776 + 1,948,917 +
1,561,510 ns and do not overlap; the sweep spans run from 42,468,371 ns to
68,182,668 + 23,827,366 ns.
"""
import pytest

import xplane
from conftest import HERE

TRACE = str(HERE / "data" / "small.xplane.pb")
BUSY_NS = 1_949_022 + 1_561_776 + 1_948_917 + 1_561_510
WINDOW_NS = 68_182_668 + 23_827_366 - 42_468_371


@pytest.fixture(scope="module")
def reduced():
    return xplane.reduce(TRACE, 1)


def test_busy_and_window(reduced):
    assert reduced["busy_s"] == pytest.approx(BUSY_NS / 1e9, abs=1e-12)
    assert reduced["window_s"] == pytest.approx(WINDOW_NS / 1e9, abs=1e-12)


def test_device_ops_are_programs(reduced):
    assert reduced["breakdown"]["device_ops"] == [
        ["jit__simulate_stacked_lanes", pytest.approx(BUSY_NS / 1e9)]]


def test_idle_gaps_cover_the_rest(reduced):
    gaps = dict(reduced["breakdown"]["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx((WINDOW_NS - BUSY_NS) / 1e9)
    # the host waits in the counters' readback, and does its own work
    # (trace generation, stacking) inside the sweep's span
    assert set(gaps) <= {"np.asarray(jax.Array)", xplane.SWEEP_SPAN,
                         "shard_args", "DevicePutWithSharding"}
    assert gaps["np.asarray(jax.Array)"] > gaps[xplane.SWEEP_SPAN]


def test_busy_is_averaged_over_the_chips(reduced):
    assert xplane.reduce(TRACE, 4)["busy_s"] == pytest.approx(
        reduced["busy_s"] / 4)


def test_every_sweep_ran_its_programs():
    assert xplane.reduce(TRACE, 1, programs={0: 2, 1: 2})["busy_s"] == \
        pytest.approx(BUSY_NS / 1e9, abs=1e-12)


def test_a_trace_that_lost_programs_is_refused():
    """Sweep 1 ran three programs, the trace holds two of them: the
    profiler dropped events, and the idle time would be made up."""
    with pytest.raises(RuntimeError, match="dropped events"):
        xplane.reduce(TRACE, 1, programs={0: 2, 1: 3})


def test_union():
    assert xplane.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]
