"""The checks every cell of ``BENCHMARK.json`` gets, as functions of the cell.

``test_files``, ``test_faults`` and ``test_control`` run them on each cell
that ``BENCHMARK.json`` lists (the faults and the control on its one-chip
cells; ``test_sharded`` drives the four-chip cell), and the drop-in test
runs them on a cell that arrives as new files alone. So a configuration,
its reference, a traffic mix and a cell are checked with no file of the
benchmark edited:

* its grid has as many cells as its traffic file says, counted here from
  the file and not by ``Cell.n_cells``;
* every key of its configuration that names a ``SimConfig`` field reaches
  the program's ``SimConfig`` as written;
* a sound run at a test's size is correct;
* a run with the timed path broken underneath is not: a scan that leaves
  its state unchanged, half of a batch left out with the mean of the rest
  in its place, an answer altered where it is produced. The batched
  simulation behind ``run_sweep`` (``runner._SIMULATE``) is broken for a
  single-core cell, the one behind ``run_mix_sweep``
  (``multicore.simulate_multicore_batch``) for a mix;
* the control, the reference without tFAW in the program's place, comes
  out as mismatched.
"""
from __future__ import annotations

import dataclasses
import enum
import json
import math

import numpy as np

import check
import run
import sweeps
from conftest import BENCH, ROOT, cpu_chips, shrink, window_of

BENCHMARK = sweeps.load_json(ROOT / "BENCHMARK.json")
#: The faults a sweep on one chip can have (``test_sharded`` adds those of
#: the exchange between chips).
FAULTS = ("unchanged", "half_batch", "altered")


def cells(chips: int | None = None) -> list[str]:
    """The names of ``BENCHMARK.json``'s cells, those on ``chips`` chips
    where given."""
    return [w["name"] for w in BENCHMARK["workloads"]
            if chips is None or w["chips"] == chips]


def traffic_file(name: str, root=ROOT) -> dict:
    """Traffic mix ``name`` as its files give it, ``"extends"`` followed."""
    t = json.loads((root / BENCH.name / "traffic" / f"{name}.json")
                   .read_text())
    base = t.pop("extends", None)
    return t if base is None else {**traffic_file(base, root), **t}


def grid_size(traffic: str, root=ROOT) -> int:
    """Grid cells of one sweep: units x policies x every axis's values."""
    t = traffic_file(traffic, root)
    units = t["mixes"] if t["entry"] == "run_mix_sweep" else t["workloads"]
    axes = math.prod(len(v) for v in t.get("config_axes", {}).values())
    return len(units) * len(t["policies"]) * axes


def check_grid(cell, size: int) -> None:
    assert cell.n_cells == size
    grid = sweeps.Program(cell).grid(seed=1)
    assert len(grid.expand()) == size
    cores = cell.config["n_cores"]
    assert grid.n_cores == cores if cell.is_mix else cores == 1


def check_sim_config(cell) -> None:
    """Every key of the cell's configuration that names a ``SimConfig``
    field is that field of the program's ``SimConfig``: an enum by its
    member's name, the timing table field by field."""
    from repro.core.dram import SimConfig
    cfg = cell.sim_config()
    base = sweeps.Program(cell).base
    named = {f.name for f in dataclasses.fields(SimConfig)} & set(cfg)
    assert "timing" in named
    for key in sorted(named):
        got, want = getattr(base, key), cfg[key]
        if dataclasses.is_dataclass(got):
            for k, v in want.items():
                assert getattr(got, k) == v, f"{key}.{k}"
        elif isinstance(got, enum.Enum):
            assert got.name == want, key
        else:
            assert got == want, key


def run_cell(name: str, capsys, seed: int, shrink_to=None) -> dict:
    """One run of cell ``name`` on the CPU, cut by ``shrink_to`` (by
    default three units of 240 requests, eight cells compared); its result
    line."""
    if shrink_to is None:
        def shrink_to(c):
            return shrink(c, units=3, sample=8)
    rc = run.main(["--workload", name, "--seed", str(seed), "--seconds", "0",
                   "--trace", "0"], chips=cpu_chips, shrink=shrink_to)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def check_sound_run(name: str, capsys, seed: int) -> None:
    result = run_cell(name, capsys, seed)
    assert result["correct"] is True
    assert result["checks"]["mismatched_cells"] == {"value": 0, "limit": 0}
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {
        m["name"] for m in run.metrics_for(BENCHMARK, name, False)}


def _with(res, **fields):
    return dataclasses.replace(res, **fields)


def single_fault(kind, orig):
    from repro.core.dram.engine import SimResult
    names = [f.name for f in dataclasses.fields(SimResult)]

    def broken(stacked, policy, config):
        B, N = np.asarray(stacked["bank"]).shape
        if kind == "unchanged":
            zero = np.zeros(B, np.int32)
            return SimResult(**{f: (np.full(B, N, np.int32)
                                    if f == "n_requests" else zero)
                                for f in names})
        if kind == "half_batch":
            h = max(1, B // 2)
            res = orig({k: np.asarray(v)[:h] for k, v in stacked.items()},
                       policy, config)
            out = {}
            for f in names:
                a = np.asarray(getattr(res, f))
                fill = np.full(B - h, int(a.mean()), a.dtype)
                out[f] = np.concatenate([a, fill])
            return SimResult(**out)
        res = orig(stacked, policy, config)
        return _with(res, total_cycles=np.asarray(res.total_cycles) + 1)
    return broken


def mix_fault(kind, orig):
    def broken(mixes, policy, config, alone_cycles=None, **kw):
        if kind == "half_batch":
            h = max(1, len(mixes) // 2)
            cores = len(mixes[0])
            res = orig(mixes[:h], policy, config,
                       alone_cycles=alone_cycles[:h * cores], **kw)
            res = [res[i % h] for i in range(len(mixes))]
            return res
        res = orig(mixes, policy, config, alone_cycles=alone_cycles, **kw)
        out = []
        for r in res:
            if kind == "unchanged":
                shared = {f: np.zeros_like(np.asarray(v)) for f, v in
                          dataclasses.asdict(r.shared).items()}
                shared["n_requests"] = np.asarray(r.shared.n_requests)
                r = dataclasses.replace(
                    r, shared=type(r.shared)(**shared),
                    core_cycles=np.zeros_like(r.core_cycles))
            else:
                r = dataclasses.replace(r, shared=_with(
                    r.shared, total_cycles=np.asarray(r.shared.total_cycles)
                    + 1))
            out.append(r)
        return out
    return broken


def break_timed_path(cell, kind: str, monkeypatch) -> None:
    """Plant fault ``kind`` where the cell's entry produces its results."""
    from repro.core.dram import multicore
    from repro.experiments import runner
    if cell.is_mix:
        monkeypatch.setattr(multicore, "simulate_multicore_batch",
                            mix_fault(kind, multicore.simulate_multicore_batch))
    else:
        monkeypatch.setattr(runner, "_SIMULATE",
                            single_fault(kind, runner._SIMULATE))


def check_broken_run(name: str, kind: str, capsys, monkeypatch,
                     seed: int) -> None:
    break_timed_path(sweeps.load_cell(name), kind, monkeypatch)
    result = run_cell(name, capsys, seed)
    assert result["correct"] is False
    assert result["checks"]["mismatched_cells"]["value"] > 0


def control_readings(cell, seed: int) -> dict:
    """The control's readings on the traffic's last units (its most
    memory-intensive: three mixes, or four workloads, where four ACTs crowd
    tFAW), 400 requests each, six cells compared."""
    t = dict(cell.traffic)
    key = "mixes" if cell.is_mix else "workloads"
    t[key] = t[key][-3:] if cell.is_mix else t[key][-4:]
    cell = shrink(dataclasses.replace(cell, traffic=t),
                  n_requests=400, units=4, sample=6)
    return check.control(cell, window_of(cell, seed), seed)


def check_control_fails(cell, seed: int) -> None:
    readings = control_readings(cell, seed)
    assert readings["sampled_cells"] == 6
    assert readings["mismatched_cells"] > 0
