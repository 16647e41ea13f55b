"""A run with the timed path broken underneath comes out not correct.

Each test drives ``run.main`` on the CPU at a size a test can hold, with
the look for a chip replaced, on every one-chip cell of ``BENCHMARK.json``,
and breaks the program where its results are produced (``per_cell``): a
scan that leaves its state unchanged, half of a batch left out with the
mean of the rest in its place, and an answer altered where it is produced.
(These cells run on one chip, so there is no exchange between chips to
leave out; ``test_sharded`` drives the four-chip cell.) A run without a
fault reads 0 and is correct.
"""
import pytest

import per_cell
from conftest import SEEDS

CELLS = per_cell.cells(chips=1)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name, capsys):
    per_cell.check_sound_run(name, capsys, SEEDS[0])


@pytest.mark.parametrize("kind", per_cell.FAULTS)
@pytest.mark.parametrize("name", CELLS)
def test_broken_path_is_not_correct(name, kind, capsys, monkeypatch):
    per_cell.check_broken_run(name, kind, capsys, monkeypatch, SEEDS[0])
