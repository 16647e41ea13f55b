"""A run with the timed path broken underneath comes out not correct.

Each test drives ``run.main`` on the CPU at a size a test can hold, with
the look for a chip replaced, and breaks the program where its results are
produced: the batched simulation behind ``run_sweep``
(``runner._SIMULATE``) or behind ``run_mix_sweep``
(``multicore.simulate_multicore_batch``). The faults are the ones a sweep
can have: a scan that leaves its state unchanged, half of a batch left
out with the mean of the rest in its place, and an answer altered where it
is produced. (The cells run on one chip, so there is no exchange between
chips to leave out.) A run without a fault reads 0 and is correct.
"""
import dataclasses
import json

import numpy as np
import pytest

import run
from conftest import SEEDS, cpu_chips, shrink

CELLS = ("ddr3_1core.fig4", "ddr3_4core.mixes", "ddr3_1core.darp8gb")


def run_cell(name, capsys, seed=SEEDS[0]):
    rc = run.main(["--workload", name, "--seed", str(seed), "--seconds", "0",
                   "--trace", "0"], chips=cpu_chips,
                  shrink=lambda c: shrink(c, units=3, sample=8))
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


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


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name, capsys):
    result = run_cell(name, capsys)
    assert result["correct"] is True
    assert result["checks"]["mismatched_cells"] == {"value": 0, "limit": 0}
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"sim_req_per_s", "setup_s"}


@pytest.mark.parametrize("kind", ["unchanged", "half_batch", "altered"])
@pytest.mark.parametrize("name", CELLS)
def test_broken_path_is_not_correct(name, kind, capsys, monkeypatch):
    from repro.core.dram import multicore
    from repro.experiments import runner
    if name == "ddr3_4core.mixes":
        monkeypatch.setattr(multicore, "simulate_multicore_batch",
                            mix_fault(kind, multicore.simulate_multicore_batch))
    else:
        monkeypatch.setattr(runner, "_SIMULATE",
                            single_fault(kind, runner._SIMULATE))
    result = run_cell(name, capsys)
    assert result["correct"] is False
    assert result["checks"]["mismatched_cells"]["value"] > 0
