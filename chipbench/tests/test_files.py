"""Every file of the benchmark loads, and the benchmark is driven by them.

Each expectation of a cell comes from ``BENCHMARK.json`` and the cell's own
files (``per_cell``), so a cell that arrives as new files is checked with
no edit here; the drop-in test at the end shows it.
"""
import dataclasses
import functools
import json
import re
import shutil
from pathlib import Path

import pytest

import check
import per_cell
import sweeps
from conftest import BENCH, ROOT, SEEDS

BENCHMARK = per_cell.BENCHMARK
CELLS = per_cell.cells()
ENTRIES = {w["name"]: w for w in BENCHMARK["workloads"]}
#: Grid cells per sweep of the cells that came before per-cell
#: expectations were read from the files: a guard on ``grid_size``.
PINNED_GRID_CELLS = {"ddr3_1core.fig4": 160, "ddr3_4core.mixes": 40,
                     "ddr3_1core.darp8gb": 64, "ddr3_1core.fig4_shard4": 160}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("name", CELLS)
def test_cell_loads_and_expands(name):
    per_cell.check_grid(sweeps.load_cell(name),
                        per_cell.grid_size(ENTRIES[name]["traffic"]))


@pytest.mark.parametrize("name, size", PINNED_GRID_CELLS.items())
def test_pinned_grid_sizes(name, size):
    assert per_cell.grid_size(ENTRIES[name]["traffic"]) == size
    assert sweeps.load_cell(name).n_cells == size


def explicit_sim_config(cfg):
    """The construction the harness made before it read every field: seven
    keys by name."""
    from repro.core.dram import SimConfig
    from repro.core.dram.timing import DramTiming
    return SimConfig(
        n_banks=cfg["n_banks"], n_subarrays=cfg["n_subarrays"],
        timing=DramTiming(**cfg["timing"]), memtech=cfg["memtech"],
        row_policy=cfg["row_policy"], mapping=cfg["mapping"],
        refresh_policy=cfg["refresh_policy"])


@pytest.mark.parametrize("name", PINNED_GRID_CELLS)
def test_sim_config_is_unchanged(name):
    """Each cell that came before ``sim_config_fields`` builds the same
    SimConfig as the seven named keys did."""
    cell = sweeps.load_cell(name)
    assert sweeps.Program(cell).base == explicit_sim_config(
        cell.sim_config())


@pytest.mark.parametrize("name", CELLS)
def test_sim_config_fields_reach_the_program(name):
    per_cell.check_sim_config(sweeps.load_cell(name))


def test_every_sim_config_field_reaches_the_program():
    """A key that names a SimConfig field reaches the simulator from the
    configuration file alone; a key that names none is left out."""
    from repro.core.dram import Scheduler, SimConfig
    fig4 = sweeps.load_cell("ddr3_1core.fig4").sim_config()
    cfg = {**fig4, "backend": "pallas-interpret", "scheduler": "TCM",
           "emit_commands": True, "speed_bin": "DDR3-1066 7-7-7"}
    base = sweeps.sim_config_fields(cfg)
    assert base.backend == "pallas-interpret"
    assert base.scheduler is Scheduler.TCM
    assert base.emit_commands is True
    assert base.timing.t_faw == cfg["timing"]["t_faw"]
    assert "speed_bin" not in {f.name for f in dataclasses.fields(SimConfig)}
    assert sweeps.sim_config_fields(
        {k: v for k, v in cfg.items() if k != "speed_bin"}) == base


@pytest.mark.parametrize("cfg", BENCHMARK["configs"], ids=lambda c: c["name"])
def test_config_file_matches_entry(cfg):
    data = json.loads((ROOT / cfg["file"]).read_text())
    assert data["name"] == cfg["name"]
    assert data["reduced"] == cfg["reduced"]
    assert set(data["timing"]) >= {"t_rcd", "t_rp", "t_ras", "t_faw"}


def test_names_units_and_keys():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in BENCHMARK["configs"]] + CELLS + [
        m["name"] for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert (BENCH / "metrics" / f"{m['name']}.py").exists()
    for w in BENCHMARK["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        assert (BENCH / "traffic" / f"{w['traffic']}.json").exists()


def test_a_traffic_extends_another():
    """The sharded mix is the Fig. 4 grid, with its shards."""
    fig4 = sweeps.load_traffic("fig4")
    shard4 = sweeps.load_traffic("fig4_shard4")
    assert shard4 == {**fig4, "shards": 4}
    assert "shards" not in fig4


#: The drop-in: a configuration with a SimConfig field beyond the seven
#: (``scheduler``) and its own reference module, a traffic mix and a cell,
#: all new files and appended entries.
DROP_IN = "probe_1core.fig4_tail"


@pytest.fixture
def dropped_in(tmp_path, monkeypatch):
    """A checkout with the drop-in's files added and nothing edited; the
    harness reads its cells, and ``check`` its references, from there."""
    bench_dir = tmp_path / BENCH.name
    shutil.copytree(BENCH, bench_dir,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    config = json.loads((BENCH / "configs" / "ddr3_1066_1core.json")
                        .read_text())
    config.update(name="probe_1core", n_subarrays=16, scheduler="FRFCFS",
                  reference="probe_reference")
    (bench_dir / "configs" / "probe_1core.json").write_text(
        json.dumps(config))
    shutil.copy(BENCH / "reference.py", bench_dir / "probe_reference.py")
    traffic = per_cell.traffic_file("fig4")
    traffic.update(policies=["BASELINE", "SALP2", "MASA"],
                   workloads=traffic["workloads"][-6:])
    (bench_dir / "traffic" / "fig4_tail.json").write_text(json.dumps(traffic))
    bench = dict(BENCHMARK)
    bench["configs"] = BENCHMARK["configs"] + [
        {"name": "probe_1core", "source": "test",
         "file": f"{BENCH.name}/configs/probe_1core.json", "reduced": [],
         "why": "test"}]
    bench["workloads"] = BENCHMARK["workloads"] + [
        {"name": DROP_IN, "config": "probe_1core", "traffic": "fig4_tail",
         "chips": 1, "why": "test"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(sweeps, "load_cell",
                        functools.partial(sweeps.load_cell, root=tmp_path))
    monkeypatch.setattr(check, "HERE", bench_dir)
    return tmp_path


def test_a_dropped_in_cell_is_found(dropped_in):
    """A new cell needs only its files and entries: no edit. Its grid and
    its SimConfig pass the checks every cell gets."""
    from repro.core.dram import Scheduler
    cell = sweeps.load_cell(DROP_IN)
    assert per_cell.grid_size("fig4_tail", root=dropped_in) == 18
    per_cell.check_grid(cell, 18)
    per_cell.check_sim_config(cell)
    base = sweeps.Program(cell).base
    assert base.scheduler is Scheduler.FRFCFS and base.n_subarrays == 16
    assert Path(check.reference_for(cell.config).__file__) == \
        dropped_in / BENCH.name / "probe_reference.py"


@pytest.mark.parametrize("kind", ("sound", "control") + per_cell.FAULTS)
def test_a_dropped_in_cell_is_checked(dropped_in, kind, capsys, monkeypatch):
    """The drop-in is run and checked as every one-chip cell is: a sound
    run is correct, the control and each fault are not."""
    if kind == "sound":
        per_cell.check_sound_run(DROP_IN, capsys, SEEDS[1])
    elif kind == "control":
        per_cell.check_control_fails(sweeps.load_cell(DROP_IN), SEEDS[1])
    else:
        per_cell.check_broken_run(DROP_IN, kind, capsys, monkeypatch,
                                  SEEDS[1])
