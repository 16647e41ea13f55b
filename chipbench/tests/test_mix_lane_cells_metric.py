"""The reader of the lane-major mix scan counter, on synthetic sweep
records."""
import types

import pytest

import run
import sweeps


def record(index, stats):
    return sweeps.SweepRecord(index=index, seed=index, wall_s=1.0,
                              n_cells=40, requests=1_280_000,
                              stats={"sim_batches": 10, **stats},
                              cells={}, quarantined=0)


def read(records):
    return run.load_reader("mix_lane_cells_pct")(
        types.SimpleNamespace(records=records))


def test_share_over_the_window():
    recs = [record(0, {"n_cells": 40, "mix_lane_cells": 40}),
            record(1, {"n_cells": 40, "mix_lane_cells": 10})]
    assert read(recs) == pytest.approx(100 * 50 / 80, rel=1e-12)


def test_none_without_the_counter():
    """A program whose mix sweeps do not count lane-major cells gives
    nothing to read, and raises nothing."""
    assert read([record(0, {"n_cells": 40})]) is None
    assert read([record(0, {"n_cells": 40, "mix_lane_cells": 40}),
                 record(1, {"n_cells": 40})]) is None
    assert read([]) is None
    assert read([record(0, {"n_cells": 0, "mix_lane_cells": 0})]) is None
