"""The control, the reference without the four-activate window (tFAW) put
in the program's place, comes out as mismatched on every seed tried."""
import pytest

import check
import sweeps
from conftest import SEEDS, shrink, window_of

CELLS = ("ddr3_1core.fig4", "ddr3_4core.mixes", "ddr3_1core.darp8gb")


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", CELLS)
def test_control_fails(name, seed):
    cell = sweeps.load_cell(name)
    # the most memory-intensive workloads, where four ACTs crowd tFAW
    t = dict(cell.traffic)
    key = "mixes" if cell.is_mix else "workloads"
    t[key] = t[key][-3:] if cell.is_mix else t[key][-4:]
    cell = shrink(type(cell)(cell.name, cell.entry, cell.config, t),
                  n_requests=400, units=4, sample=6)
    readings = check.control(cell, window_of(cell, seed), seed)
    assert readings["sampled_cells"] == 6
    assert readings["mismatched_cells"] > 0
