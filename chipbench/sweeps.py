"""Build a cell's sweep grid from its data files and run it as a user would.

A cell is one entry of ``BENCHMARK.json``'s ``workloads``: a configuration
(``configs/<config>.json``: geometry, timing table, analytic core) under a
traffic mix (``traffic/<traffic>.json``: the grid a user sweeps, pinned as
data). Nothing here is particular to one cell, so a cell is added by adding
those files and an entry. Every key of the configuration (with the
traffic's overrides) that names a field of the program's ``SimConfig``
reaches the simulator; the other keys describe the deployment.

Each sweep goes through the program's own entry (``run_sweep`` or
``run_mix_sweep``, as the traffic names it) with a seed of its own, a fresh
in-memory result cache and the default resilience policy, after the
runner's trace memo is cleared: trace generation, bucketing, the scans on
the device and the readback are all paid in every sweep. A traffic mix
that names ``shards`` runs every sweep sharded over the cell's chips
(``ShardPlan(shards, devices)``: each bucket's cells split into that many
contiguous shards, shard ``s`` on chip ``s`` modulo their number), its
fragments kept in memory. What the harness keeps of a sweep is a
:class:`SweepRecord`: the counts of the sweep's line and each cell's
integer results, for the comparison with the reference; a sharded sweep's
results are read from the merge of its fragments, as a user of the
sharded runner reads them.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import enum
import json
import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One benchmark cell: its entry, configuration and traffic files."""
    name: str
    entry: dict
    config: dict
    traffic: dict

    @property
    def is_mix(self) -> bool:
        return self.traffic["entry"] == "run_mix_sweep"

    def sim_config(self) -> dict:
        """The configuration the cell simulates: the configuration file
        with the traffic's overrides applied (``timing`` merged key by key)."""
        cfg = dict(self.config)
        over = dict(self.traffic.get("config_overrides", {}))
        cfg["timing"] = {**cfg["timing"], **over.pop("timing", {})}
        cfg.update(over)
        return cfg

    @property
    def n_cells(self) -> int:
        t = self.traffic
        units = len(t["mixes"]) if self.is_mix else len(t["workloads"])
        axes = 1
        for vals in t.get("config_axes", {}).values():
            axes *= len(vals)
        return units * len(t["policies"]) * axes


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Find cell ``name`` in ``BENCHMARK.json`` and load its files."""
    bench = load_json(root / "BENCHMARK.json")
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(entries)}")
    entry = entries[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[entry["config"]]["file"])
    return Cell(name=name, entry=entry, config=config,
                traffic=load_traffic(entry["traffic"], root))


def load_traffic(name: str, root: Path = ROOT) -> dict:
    """Traffic mix ``name``. A mix with ``"extends": "<mix>"`` is that mix
    with its own keys put in place of the other's."""
    t = load_json(root / HERE.name / "traffic" / f"{name}.json")
    base = t.pop("extends", None)
    return t if base is None else {**load_traffic(base, root), **t}


def sweep_seed(seed: int, index: int) -> int:
    """Seed of sweep ``index`` of a run with ``--seed seed``; index -1 is
    the warm-up sweep, which the window never repeats."""
    return seed * 1000 + index + 1


def sim_config_fields(cfg: dict):
    """The program's ``SimConfig`` from every key of ``cfg`` that names one
    of its fields. A field whose default is a dataclass (the timing table)
    is built from the key's object, one whose default is an enum from the
    member's name; any other value is passed as it is."""
    from repro.core.dram import SimConfig
    kw = {}
    for f in dataclasses.fields(SimConfig):
        if f.name not in cfg:
            continue
        v = cfg[f.name]
        if dataclasses.is_dataclass(f.default) and isinstance(v, dict):
            v = type(f.default)(**v)
        elif isinstance(f.default, enum.Enum) and isinstance(v, str):
            v = type(f.default)[v]
        kw[f.name] = v
    return SimConfig(**kw)


class Program:
    """The system under test, driven through its sweep entry points.

    ``devices`` are the chips the cell runs on. Where the traffic names
    ``shards``, every sweep is sharded: ``ShardPlan(shards, devices)``, as
    ``benchmarks.run --shards <n> --mesh tpu:<chips>`` runs it."""

    def __init__(self, cell: Cell, devices=None):
        from repro.core.dram import Policy, Scheduler, WorkloadProfile
        self.cell = cell
        self.devices = tuple(devices or ())
        self.base = sim_config_fields(cell.sim_config())
        t = cell.traffic
        self.policies = tuple(Policy[p] for p in t["policies"])
        self.axes = {k: tuple(Scheduler[v] if k == "scheduler" else v
                              for v in vals)
                     for k, vals in t.get("config_axes", {}).items()}
        if cell.is_mix:
            self.units = [tuple(WorkloadProfile(**p) for p in m)
                          for m in t["mixes"]]
        else:
            self.units = [WorkloadProfile(**p) for p in t["workloads"]]

    def grid(self, seed: int):
        from repro.experiments import MixGrid, SweepGrid
        t = self.cell.traffic
        if self.cell.is_mix:
            return MixGrid(name=self.cell.name, mixes=self.units,
                           policies=self.policies, n_requests=t["n_requests"],
                           seed=seed, base_config=self.base,
                           config_axes=self.axes)
        return SweepGrid(name=self.cell.name, workloads=self.units,
                         policies=self.policies, n_requests=t["n_requests"],
                         seed=seed, base_config=self.base,
                         config_axes=self.axes)

    def sweep(self, seed: int):
        """One sweep as a user's invocation runs it."""
        from repro.experiments import (ResultCache, ShardPlan, run_mix_sweep,
                                       run_sweep)
        from repro.experiments import runner
        runner.clear_trace_cache()
        grid = self.grid(seed)
        kw = {}
        if "shards" in self.cell.traffic:
            kw["shards"] = ShardPlan(self.cell.traffic["shards"], self.devices)
        if self.cell.is_mix:
            return run_mix_sweep(grid, **kw)
        return run_sweep(grid, ResultCache(), **kw)


@dataclasses.dataclass
class SweepRecord:
    """What the harness keeps of one sweep. ``chip_programs`` is the least
    number of programs the sweep ran on each of its chips: its batches on
    one chip, the shards placed on the least-used chip when sharded."""
    index: int
    seed: int
    wall_s: float
    n_cells: int
    requests: int
    stats: dict
    cells: dict          # (unit, policy, overrides) -> integer results
    quarantined: int
    chip_programs: int = 0

    def line(self) -> str:
        shards = self.stats.get("sharding")
        where = (f", {shards['n_shards']} shards on "
                 f"{len(set(shards['devices']))} devices" if shards else "")
        return (f"# sweep {self.index}: seed {self.seed}, wall "
                f"{self.wall_s:.6f} s, {self.n_cells} cells, "
                f"{self.requests} requests, {self.stats.get('sim_batches')} "
                f"sim_batches{where}, {self.stats.get('retries', 0)} retries, "
                f"{self.stats.get('bisections', 0)} bisections, "
                f"{self.quarantined} quarantined")


def cell_key(unit: str, policy: str, overrides: dict) -> tuple:
    """A grid cell's identity: unit (workload or mix), policy and the
    configuration axes' values, enums by name."""
    return (unit, policy, tuple(sorted((k, str(getattr(v, "name", v)))
                                       for k, v in overrides.items())))


def merged_cells(cell: Cell, fragments: list) -> list[tuple[tuple, dict]]:
    """A sharded sweep's cells, keyed, from the merge of its fragments.
    A merge that fails (a fragment lost, doubled or from another sweep)
    raises."""
    from repro.experiments import merge_fragments
    out = []
    for c in merge_fragments(fragments)["cells"]:
        if cell.is_mix:
            out.append((cell_key(c["mix"], c["policy"], c["overrides"]),
                        {k: c[k] for k in ("counters", "core_cycles",
                                           "alone_cycles",
                                           "weighted_speedup")}))
        else:
            out.append((cell_key(c["workload"], c["policy"], c["overrides"]),
                        dict(counters=c["counters"])))
    return out


def sweep_cells(cell: Cell, sweep) -> list[tuple[tuple, dict]]:
    """An unsharded sweep's cells, keyed."""
    if cell.is_mix:
        return [(cell_key(c.mix_name, c.policy.name, c.cell.override_dict),
                 dict(counters=c.counters, core_cycles=c.core_cycles,
                      alone_cycles=c.alone_cycles,
                      weighted_speedup=c.weighted_speedup))
                for c in sweep.cells]
    return [(cell_key(c.workload.name, c.policy.name, c.overrides),
             dict(counters=c.counters)) for c in sweep.cells]


def chip_programs(sweep) -> int:
    """The least number of programs the sweep ran on any one of its chips
    (at least one per batch; a shard is a batch on its own chip)."""
    shards = sweep.stats.get("sharding")
    if not shards:
        return sweep.stats.get("sim_batches", 0)
    per_chip = collections.Counter(
        f["shard"]["device"] for f in sweep.fragments
        if f["shard"]["role"] == "shard")
    return min(per_chip[d] for d in shards["devices"])


def record(cell: Cell, index: int, seed: int, sweep, wall_s: float
           ) -> SweepRecord:
    """Keep a sweep's counts and each cell's integer results. A sharded
    sweep's come from the merge of its fragments; where the merge fails,
    the sweep produced none of its cells."""
    if sweep.stats.get("sharding"):
        try:
            pairs = merged_cells(cell, sweep.fragments)
        except ValueError as e:
            print(f"chipbench: sweep {index}: the merge of its fragments "
                  f"failed: {e}", file=sys.stderr)
            pairs = []
    else:
        pairs = sweep_cells(cell, sweep)
    cores = len(cell.traffic["mixes"][0]) if cell.is_mix else 1
    done = len(pairs)
    return SweepRecord(index=index, seed=seed, wall_s=wall_s,
                       n_cells=cell.n_cells,
                       requests=done * cores * cell.traffic["n_requests"],
                       stats=dict(sweep.stats), cells=dict(pairs),
                       quarantined=cell.n_cells - done,
                       chip_programs=chip_programs(sweep))


def accuracy_line(cell: Cell, sweep) -> str | None:
    """Mean gain of each policy over the baseline, beside the paper's:
    IPC for single-core grids, weighted speedup for mixes (over every mix
    and scheduler)."""
    acc = cell.traffic.get("accuracy")
    if not acc or sweep.quarantined:
        return None
    from repro.core.dram import Policy, Scheduler
    base = Policy[acc["baseline"]]
    parts = []
    for pol in cell.traffic["policies"]:
        if pol == acc["baseline"]:
            continue
        p = Policy[pol]
        if cell.is_mix:
            gains = []
            for sched in cell.traffic["config_axes"].get("scheduler", [None]):
                eq = {} if sched is None else {"scheduler": Scheduler[sched]}
                gains.append(sweep.weighted_speedups(p, **eq)
                             / sweep.weighted_speedups(base, **eq) - 1)
            gain = 100 * float(np.mean(np.concatenate(gains)))
        else:
            gain = 100 * float(np.mean(sweep.metric("ipc", policy=p)
                                       / sweep.metric("ipc", policy=base)
                                       - 1))
        paper = acc["paper"].get(pol)
        parts.append(f"{pol} {gain:.2f} %"
                     + (f" (paper {paper} %)" if paper is not None else ""))
    return f"# accuracy, {acc['metric']}: " + "; ".join(parts)


def run_window(program: Program, seed: int, seconds: float,
               annotate=None) -> tuple[list[SweepRecord], float, object]:
    """Whole sweeps back to back until ``seconds`` have passed.

    Returns the records, the window's wall seconds (first sweep's start to
    the last sweep's end) and the first sweep itself (for its accuracy
    line). ``annotate(index)``, where given, is a context manager that
    wraps each sweep (the traced run's host span).
    """
    records, first = [], None
    t_start = time.perf_counter()
    index = 0
    while True:
        s = sweep_seed(seed, index)
        t0 = time.perf_counter()
        with annotate(index) if annotate else contextlib.nullcontext():
            sweep = program.sweep(s)
        wall = time.perf_counter() - t0
        records.append(record(program.cell, index, s, sweep, wall))
        if first is None:
            first = sweep
        del sweep
        index += 1
        if time.perf_counter() - t_start >= seconds:
            break
    return records, time.perf_counter() - t_start, first
