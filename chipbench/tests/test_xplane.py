"""The trace reduction on a small trace recorded on the chip.

``data/small.xplane.pb`` was written by ``record_trace.py`` on one TPU v5
lite: two sweeps (the harness's ``chipbench.sweep 0`` / ``1`` spans) of a
cut Fig. 4 grid, two lane-scan programs per sweep. Its four program
executions (``XLA Modules``) last 1,949,022 + 1,561,776 + 1,948,917 +
1,561,510 ns and do not overlap; the sweep spans run from 42,468,371 ns to
68,182,668 + 23,827,366 ns.
"""
import types

import pytest

import run
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


def test_busy_per_chip_on_one_chip(reduced):
    assert reduced["busy_per_chip_s"] == [pytest.approx(BUSY_NS / 1e9,
                                                        abs=1e-12)]


def four_planes(overlapped: bool):
    """One sweep, four shards of 200 us, one on each chip: one after
    another in a 1 ms sweep, or all at once in a 300 us one."""
    starts = (50_000,) * 4 if overlapped else (0, 250_000, 500_000, 750_000)
    spans = [(f"{xplane.SWEEP_SPAN} 0", 0,
              300_000 if overlapped else 1_000_000)]
    device_ops = [[("jit__simulate_stacked_lanes(7)", t, t + 200_000)]
                  for t in starts]
    return xplane.reduce_events(spans, [], device_ops, 4, programs={0: 1})


@pytest.mark.parametrize("overlapped", [False, True])
def test_busy_per_chip_of_four_planes(overlapped):
    reduced = four_planes(overlapped)
    assert reduced["busy_per_chip_s"] == [pytest.approx(2e-4)] * 4
    # busy_s stays the chips' mean
    assert reduced["busy_s"] == pytest.approx(2e-4)
    assert reduced["breakdown"]["device_ops"] == [
        ["jit__simulate_stacked_lanes", pytest.approx(8e-4)]]


def test_a_chip_that_lost_its_shard_is_refused():
    """Each chip runs its shard of every bucket: a plane without it lost
    events."""
    spans = [(f"{xplane.SWEEP_SPAN} 0", 0, 1_000_000)]
    device_ops = [[("p", 0, 10)]] * 3 + [[]]
    with pytest.raises(RuntimeError, match="device plane 3 holds 0"):
        xplane.reduce_events(spans, [], device_ops, 4, programs={0: 1})


def busy_chips(reduced):
    return run.load_reader("busy_chips")(types.SimpleNamespace(trace=reduced))


def test_busy_chips_serial_shards_keep_to_one():
    assert busy_chips(four_planes(overlapped=False)) == pytest.approx(0.8)


def test_busy_chips_overlapped_shards_pass_one():
    assert busy_chips(four_planes(overlapped=True)) == pytest.approx(8 / 3)
