"""The plain reference agrees with the program bit for bit at small sizes,
on every policy, with refresh off and under DARP, and on mixes under
FR-FCFS and TCM. (At the cells' sizes the benchmark compares them itself.)
Each configuration's reference is the module it names, as ``check`` finds
it.
"""
import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

import check
import per_cell
import sweeps
from conftest import BENCH, SEEDS, shrink, window_of

CFG1 = json.loads((BENCH / "configs" / "ddr3_1066_1core.json").read_text())
CFG4 = json.loads((BENCH / "configs" / "ddr3_1066_4core.json").read_text())
REF1, REF4 = check.reference_for(CFG1), check.reference_for(CFG4)
FIG4 = json.loads((BENCH / "traffic" / "fig4.json").read_text())
MIXES = json.loads((BENCH / "traffic" / "mixes.json").read_text())
N = 300


def program_config(cfg, **over):
    from repro.core.dram import SimConfig
    from repro.core.dram.timing import DramTiming
    return SimConfig(n_banks=cfg["n_banks"], n_subarrays=cfg["n_subarrays"],
                     timing=DramTiming(**cfg["timing"]), **over)


def as_ints(res):
    return {k: int(np.asarray(v)) for k, v in dataclasses.asdict(res).items()}


@pytest.mark.parametrize("refresh", ["none", "darp"])
@pytest.mark.parametrize("index", [0, 13, 22, 27, 31])
def test_single_core(index, refresh):
    from repro.core.dram import Policy, WorkloadProfile, generate_trace
    from repro.core.dram import simulate
    prof = FIG4["workloads"][index]
    seed = SEEDS[index % len(SEEDS)]
    tr = generate_trace(WorkloadProfile(**prof), N, seed=seed)
    cfg = {**CFG1, "refresh_policy": refresh}
    rt = REF1.generate_trace(prof, N, seed, cfg)
    for k in ("bank", "subarray", "row", "is_write", "gap", "dep"):
        assert getattr(tr, k).tolist() == rt[k], k
    assert tr.mlp_window == rt["mlp_window"]
    for pol in Policy:
        got = as_ints(simulate(tr, pol, program_config(
            CFG1, refresh_policy=refresh)))
        assert got == REF1.simulate(rt, pol.name, cfg), pol.name


@pytest.mark.parametrize("scheduler", ["FRFCFS", "TCM"])
@pytest.mark.parametrize("mix", [0, 1, 2, 3])
def test_mix(mix, scheduler):
    from repro.core.dram import (Policy, Scheduler, WorkloadProfile,
                                 generate_trace)
    from repro.core.dram.multicore import simulate_multicore
    profs = MIXES["mixes"][mix]
    seed, stride = SEEDS[mix % len(SEEDS)], CFG4["row_space_stride"]
    trs = [generate_trace(WorkloadProfile(**p), N, seed=seed,
                          row_space_offset=stride * i)
           for i, p in enumerate(profs)]
    cfg = {**CFG4, "refresh_policy": "none"}
    rts = [REF4.generate_trace(p, N, seed, cfg,
                                    row_space_offset=stride * i)
           for i, p in enumerate(profs)]
    for pol in Policy:
        r = simulate_multicore(trs, pol, program_config(
            CFG4, scheduler=Scheduler[scheduler]))
        want = REF4.simulate_mix(rts, [p["mpki"] for p in profs],
                                      pol.name, scheduler, cfg)
        assert as_ints(r.shared) == want["counters"], pol.name
        assert [int(x) for x in r.core_cycles] == want["core_cycles"]
        assert [float(x) for x in r.alone_cycles] == want["alone_cycles"]
        assert r.weighted_speedup == want["weighted_speedup"]


#: A reference that serves every stream with the same counters.
STUB = """
def generate_trace(profile, n, seed, cfg, row_space_offset=0):
    return {"name": profile["name"], "n": n}


def simulate(tr, policy, cfg, faw=True):
    return {"stub": tr["n"]}
"""


def small_fig4(**config):
    cell = sweeps.load_cell("ddr3_1core.fig4")
    cell = dataclasses.replace(cell, config={**cell.config, **config})
    return shrink(cell, n_requests=60, units=2, sample=4)


def test_a_configuration_names_its_reference(tmp_path, monkeypatch):
    """A configuration with ``"reference": "<module>"`` is checked against
    that module, loaded from beside ``check.py``."""
    (tmp_path / "stub_reference.py").write_text(STUB)
    monkeypatch.setattr(check, "HERE", tmp_path)
    cell = small_fig4(reference="stub_reference")
    assert Path(check.reference_for(cell.config).__file__) == \
        tmp_path / "stub_reference.py"
    readings = check.compare(cell, window_of(cell, SEEDS[0]), SEEDS[0],
                             produce=lambda *_: {"counters": {"stub": 60}})
    assert readings["sampled_cells"] == 4
    assert readings["mismatched_cells"] == 0


def test_without_the_key_the_reference_is_reference_py(monkeypatch):
    cell = small_fig4()
    assert "reference" not in cell.config
    ref = check.reference_for(cell.config)
    assert Path(ref.__file__) == BENCH / "reference.py"
    calls = []

    def simulate(tr, policy, cfg, faw=True):
        calls.append(policy)
        return {"stub": 60}

    monkeypatch.setattr(ref, "simulate", simulate)
    readings = check.compare(cell, window_of(cell, SEEDS[0]), SEEDS[0],
                             produce=lambda *_: {"counters": {"stub": 60}})
    assert len(calls) == 4 and readings["mismatched_cells"] == 0


#: A single-core reference: the plain reference's trace generator and
#: simulator, and no ``simulate_mix``.
SINGLE_CORE = """
import importlib.util

_spec = importlib.util.spec_from_file_location("plain_reference", {path!r})
_plain = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_plain)
generate_trace, simulate = _plain.generate_trace, _plain.simulate
"""


def test_a_single_core_reference_needs_no_simulate_mix(tmp_path, capsys,
                                                       monkeypatch):
    """A reference without ``simulate_mix`` checks a ``run_sweep`` cell:
    a run of a shrunk fig4 against it is correct, and its control is
    not."""
    (tmp_path / "single_core.py").write_text(
        SINGLE_CORE.format(path=str(BENCH / "reference.py")))
    monkeypatch.setattr(check, "HERE", tmp_path)

    def single_core(cell):
        return dataclasses.replace(
            cell, config={**cell.config, "reference": "single_core"})

    cell = single_core(sweeps.load_cell("ddr3_1core.fig4"))
    assert not cell.is_mix
    assert not hasattr(check.reference_for(cell.config), "simulate_mix")
    result = per_cell.run_cell(
        cell.name, capsys, SEEDS[2],
        shrink_to=lambda c: shrink(single_core(c), units=3, sample=8))
    assert result["correct"] is True
    assert result["checks"]["mismatched_cells"] == {"value": 0, "limit": 0}
    per_cell.check_control_fails(cell, SEEDS[2])


def test_a_reference_is_a_module_name():
    with pytest.raises(ValueError, match="not a module name"):
        check.reference_for({"reference": "../reference"})
