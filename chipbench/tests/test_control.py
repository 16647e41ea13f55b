"""The control, the reference without the four-activate window (tFAW) put
in the program's place, comes out as mismatched on every seed tried, in
every one-chip cell of ``BENCHMARK.json``."""
import pytest

import per_cell
import sweeps
from conftest import SEEDS


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", per_cell.cells(chips=1))
def test_control_fails(name, seed):
    per_cell.check_control_fails(sweeps.load_cell(name), seed)
