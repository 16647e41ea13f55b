"""Every file of the benchmark loads, and the benchmark is driven by them."""
import json
import re
import shutil

import pytest

import sweeps
from conftest import BENCH, ROOT

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
#: Grid cells per sweep of each benchmark cell.
GRID_CELLS = {"ddr3_1core.fig4": 160, "ddr3_4core.mixes": 40,
              "ddr3_1core.darp8gb": 64, "ddr3_1core.fig4_shard4": 160}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("name", CELLS)
def test_cell_loads_and_expands(name):
    cell = sweeps.load_cell(name)
    assert cell.n_cells == GRID_CELLS[name]
    grid = sweeps.Program(cell).grid(seed=1)
    assert len(grid.expand()) == GRID_CELLS[name]
    cores = cell.config["n_cores"]
    assert grid.n_cores == cores if cell.is_mix else cores == 1


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


@pytest.mark.parametrize("name", CELLS)
def test_sim_config_is_unchanged(name):
    """Each cell's configuration builds the same SimConfig as the seven
    named keys did."""
    cell = sweeps.load_cell(name)
    assert sweeps.Program(cell).base == explicit_sim_config(
        cell.sim_config())


def test_every_sim_config_field_reaches_the_program():
    """A key that names a SimConfig field reaches the simulator from the
    configuration file alone; a key that names none is left out."""
    from repro.core.dram import Scheduler
    cfg = {**sweeps.load_cell("ddr3_1core.fig4").sim_config(),
           "backend": "pallas-interpret", "scheduler": "TCM",
           "emit_commands": True, "rows_per_bank": 65536}
    base = sweeps.sim_config_fields(cfg)
    assert base.backend == "pallas-interpret"
    assert base.scheduler is Scheduler.TCM
    assert base.emit_commands is True
    assert base.timing.t_faw == cfg["timing"]["t_faw"]


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


def test_a_dropped_in_cell_is_found(tmp_path):
    """A new cell needs only its files and an entry: no edit."""
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = dict(BENCHMARK)
    traffic = json.loads((BENCH / "traffic" / "fig4.json").read_text())
    traffic["policies"] = ["BASELINE", "MASA"]
    traffic["workloads"] = traffic["workloads"][:5]
    (tmp_path / "chipbench" / "traffic" / "fig4_small.json").write_text(
        json.dumps(traffic))
    bench["workloads"] = BENCHMARK["workloads"] + [
        {"name": "ddr3_1core.fig4_small", "config": "ddr3_1066_1core",
         "traffic": "fig4_small", "chips": 1, "why": "test"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = sweeps.load_cell("ddr3_1core.fig4_small", root=tmp_path)
    assert cell.n_cells == 10
    assert len(sweeps.Program(cell).grid(seed=1).expand()) == 10
