"""The reader of the lane-scan counter, on synthetic sweep records."""
import types

import pytest

import run
import sweeps


def record(index, stats):
    return sweeps.SweepRecord(index=index, seed=index, wall_s=1.0,
                              n_cells=64, requests=512_000,
                              stats={"sim_batches": 2, **stats},
                              cells={}, quarantined=0)


def read(records):
    return run.load_reader("lane_cells_pct")(
        types.SimpleNamespace(records=records))


def test_share_over_the_window():
    recs = [record(0, {"simulated_cells": 64, "lane_cells": 64}),
            record(1, {"simulated_cells": 64, "lane_cells": 32})]
    assert read(recs) == pytest.approx(100 * 96 / 128, rel=1e-12)


def test_none_without_the_counter():
    """A program whose sweeps do not count lane cells gives nothing to
    read, and raises nothing."""
    assert read([record(0, {"simulated_cells": 64})]) is None
    assert read([record(0, {"simulated_cells": 64, "lane_cells": 64}),
                 record(1, {"simulated_cells": 64})]) is None
    assert read([]) is None
    assert read([record(0, {"simulated_cells": 0, "lane_cells": 0})]) is None
