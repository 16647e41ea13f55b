"""Tests of the benchmark's own code, on the CPU: ``pytest chipbench/tests``.

They import the harness from ``chipbench/`` and the program from ``src/``,
and drive runs at sizes a test can hold through ``run.main``'s seams.
"""
import dataclasses
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

#: Seeds of the tests' runs: large, as the driver's are.
SEEDS = (3_000_000_017, 2_147_483_659, 4_000_000_001)


def shrink(cell, n_requests=240, units=3, sample=8):
    """The cell at a size a test can hold: its first ``units`` workloads
    or mixes, ``n_requests`` each, every policy and axis kept."""
    t = dict(cell.traffic)
    key = "mixes" if cell.is_mix else "workloads"
    t[key] = t[key][:units]
    t["n_requests"], t["sample"] = n_requests, sample
    return dataclasses.replace(cell, traffic=t)


def cpu_chips(n):
    import jax
    return jax.devices("cpu")[:n]


def window_of(cell, seed, n_sweeps=2):
    """Records of a window of ``n_sweeps`` sweeps that produced no cells,
    for a comparison whose results come from elsewhere."""
    import sweeps
    return [sweeps.SweepRecord(index=i, seed=sweeps.sweep_seed(seed, i),
                               wall_s=0.0, n_cells=cell.n_cells, requests=0,
                               stats={}, cells={}, quarantined=0)
            for i in range(n_sweeps)]
