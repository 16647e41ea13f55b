"""Decide ``correct``: the window's results against the plain reference.

After the window has closed, a sample of the grid cells that its sweeps
produced is drawn from the run's seed, over every sweep of the window, and
each sampled cell is recomputed by the configuration's plain reference
from the cell's own data: its workload profile(s), the sweep's seed, the
configuration's timing table. The reference regenerates the trace, serves
it, and for a mix also the cores' run-alone baselines, so the comparison
covers every layer a sweep passes through: trace generation, bucketing and
stacking, the scan on the device, the readback into the cell's counters
(for a sharded sweep, the merge of its shards' fragments), and for mixes
``core_cycles``, ``alone_cycles`` and ``weighted_speedup``.

The number compared is ``mismatched_cells``: sampled cells whose integer
counters (or, for a mix, per-core cycles, run-alone cycles or weighted
speedup) differ in any way from the reference's, a cell the sweep never
produced counting as one. The simulator's stated contract is
bit-identity, so its limit is 0.

A configuration names its reference with the key ``"reference":
"<module>"``: the file ``<module>.py`` beside this one, loaded by path
(:func:`reference_for`); without the key it is ``reference.py``. A
reference module imports nothing of the program and gives, from plain
Python data (the configuration with the traffic's overrides and the
cell's axes as a ``dict``; workload profiles as the traffic file's
objects):

* ``generate_trace(profile, n, seed, cfg, row_space_offset=0)``: one
  core's request stream, a ``dict`` of lists;
* ``simulate(tr, policy, cfg, faw=True)``: the stream served under a
  policy (by name), as ``{counter: int}`` named as the program's
  ``SimResult`` fields;
* ``simulate_mix(trs, mpkis, policy, scheduler, cfg, faw=True)``: several
  cores' streams sharing the channel, as ``dict(counters=...,
  core_cycles=..., alone_cycles=..., weighted_speedup=...)``. Only a
  configuration that a mix cell (``run_mix_sweep``) uses needs it.

``faw=False`` drops the four-activate window (tFAW): the control, which
has to read as mismatched.
"""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

import numpy as np

from sweeps import Cell, SweepRecord, cell_key

HERE = Path(__file__).resolve().parent

#: The compared number and its limit: an exact comparison.
LIMITS = {"mismatched_cells": 0}


def grid_keys(cell: Cell) -> list[tuple]:
    """Every cell of one sweep of the grid, as (unit, policy, overrides)
    keys, with the unit's profile(s)."""
    t = cell.traffic
    axes = t.get("config_axes", {})
    points = [{}]
    for k, vals in axes.items():
        points = [{**p, k: v} for p in points for v in vals]
    if cell.is_mix:
        units = [("+".join(p["name"] for p in m), m) for m in t["mixes"]]
    else:
        units = [(p["name"], p) for p in t["workloads"]]
    return [(cell_key(name, pol, ov), prof, ov)
            for ov in points for name, prof in units
            for pol in t["policies"]]


def draw_sample(cell: Cell, records: list[SweepRecord], seed: int,
                size: int) -> list[tuple[int, tuple]]:
    """``size`` distinct (sweep index, grid cell) pairs drawn from the
    seed over all sweeps of the window."""
    keys = grid_keys(cell)
    total = len(records) * len(keys)
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x5EED]))
    picks = rng.choice(total, size=min(size, total), replace=False)
    return sorted((int(p) // len(keys), keys[int(p) % len(keys)])
                  for p in picks)


@functools.cache
def _load(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_reference_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_for(config: dict):
    """The plain reference a configuration names (``"reference"``), by
    default ``reference.py``."""
    name = config.get("reference", "reference")
    if not name.isidentifier():
        raise ValueError(f"reference {name!r} is not a module name")
    return _load(HERE / f"{name}.py")


def expected(cell: Cell, sweep_seed: int, prof, policy: str, ov: dict,
             faw: bool = True) -> dict:
    """The reference's results for one grid cell."""
    reference = reference_for(cell.config)
    cfg = {**cell.sim_config(), **ov}
    n = cell.traffic["n_requests"]
    if not cell.is_mix:
        tr = reference.generate_trace(prof, n, sweep_seed, cfg)
        return dict(counters=reference.simulate(tr, policy, cfg, faw=faw))
    stride = cfg["row_space_stride"]
    trs = [reference.generate_trace(p, n, sweep_seed, cfg,
                                    row_space_offset=stride * i)
           for i, p in enumerate(prof)]
    return reference.simulate_mix(trs, [p["mpki"] for p in prof], policy,
                                  ov.get("scheduler", cfg.get("scheduler")),
                                  cfg, faw=faw)


def compare(cell: Cell, records: list[SweepRecord], seed: int,
            produce=None) -> dict:
    """Compare the sampled cells; return the readings.

    ``produce(sweep_seed, prof, policy, ov)``, where given, stands in for
    the program's results (the control and the tests use it); otherwise
    the window's records are read.
    """
    sample = draw_sample(cell, records, seed, cell.traffic["sample"])
    mismatched, first = 0, None
    for index, (key, prof, ov) in sample:
        rec = records[index]
        want = expected(cell, rec.seed, prof, key[1], ov)
        got = (produce(rec.seed, prof, key[1], ov) if produce is not None
               else rec.cells.get(key))
        if got != want:
            mismatched += 1
            if first is None:
                first = (f"sweep {index} (seed {rec.seed}) cell {key}: "
                         f"got {got}, reference {want}")
    return dict(mismatched_cells=mismatched, sampled_cells=len(sample),
                window_cells=sum(r.n_cells for r in records),
                first_mismatch=first)


def control(cell: Cell, records: list[SweepRecord], seed: int) -> dict:
    """The control in the program's place: the reference without the
    four-activate window (tFAW), a simulator that breaks one stated JEDEC
    rule. It has to come out as mismatched."""
    def produce(sweep_seed, prof, policy, ov):
        return expected(cell, sweep_seed, prof, policy, ov, faw=False)
    return compare(cell, records, seed, produce=produce)
