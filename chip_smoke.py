"""Run the paper's sweeps once on a TPU and check them against the CPU.

    python chip_smoke.py              # one chip: phases A-C + golden replay
    python chip_smoke.py --chips 4    # four chips: Phase A sharded vs one chip

One process drives the chip; nothing here starts another. The measured
path goes through the same library entry points the suites use, at the
suites' full size (``N_REQUESTS`` requests per trace):

* **Phase A, paper grid** — the Fig. 4 grid of ``examples/dram_paper_repro``
  (32 workloads x 5 policies, DDR3-1066, 8 banks x 8 subarrays) through
  ``run_sweep``: the lane-batched scan.
* **Phase B, refresh on** — the same 32 workloads under BASELINE and MASA
  with DARP refresh at the 8 Gb preset: the lane-batched scan's refresh
  step.
* **Phase C, mixes** — the four 4-core mixes of ``benchmarks.multicore_bench``
  x 5 policies x {FR-FCFS, TCM} through ``run_mix_sweep``: the C-core step.
* **Golden replay** — every cell of ``tests/data/golden_packed_state.json``
  through ``simulate`` / ``simulate_multicore`` on the chip, compared with
  the fixture bit for bit.

Every phase is re-run whole on the CPU backend of the same process
(``jax.default_device``) as the reference: integer counters must be
bit-identical, and floats derived from them must agree to ``FLOAT_RTOL``.
Sweeps run with ``ResiliencePolicy(fail_fast=True)``, so the first error
surfaces as itself instead of as a quarantined cell.

Each phase prints its wall time on the chip for this run (cold: compile
and trace generation included; the seconds JAX reports for tracing and
lowering, and for XLA compiling or loading from the compilation cache;
warm: the same phase again with everything compiled), and cells and
requests per second of warm time. The last line of standard output is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
or ``{"ok": false, "error": ...}`` with exit code 1 when no TPU is found, a
phase raises, a cell is quarantined or a comparison differs.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

#: Requests per trace (per core in mixes): ``benchmarks.common.N_REQUESTS``.
N_REQUESTS = 8000
SEED = 7
#: Relative tolerance for floats derived from the counters (IPC, energy,
#: weighted speedup). They are computed on the host in float64 from the
#: integer counters, so equal counters give equal floats; the tolerance
#: only absorbs summation order.
FLOAT_RTOL = 1e-12

#: Seconds JAX reported, summed over the process: tracing and lowering to
#: MLIR ("lower"), and XLA compiling or loading the program from the
#: persistent compilation cache ("xla").
_COMPILE_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "lower",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
                   "/jax/core/compile/backend_compile_duration": "xla"}
_compile_s = {"lower": 0.0, "xla": 0.0}
#: Devices that held the result of each single-core bucket simulation.
_result_devices: list = []


class SmokeFailure(Exception):
    """A check of this script failed."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _on_duration(event: str, duration: float, **_) -> None:
    if event in _COMPILE_EVENTS:
        _compile_s[_COMPILE_EVENTS[event]] += duration


def _install_probes() -> None:
    """Count compile seconds, and record where each bucket's result lives
    (through the runner's single simulation seam)."""
    import jax
    from repro.experiments import runner

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    simulate = runner._SIMULATE

    def recording(*args, **kwargs):
        res = simulate(*args, **kwargs)
        _result_devices.extend(res.total_cycles.devices())
        return res

    runner._SIMULATE = recording


# ---------------------------------------------------------------------------
# The phases: each builds its grid and runs it once, on the default device.
# ---------------------------------------------------------------------------

def strict():
    """Sweeps re-raise their first error: no retry, no quarantine."""
    from repro.experiments import ResiliencePolicy
    return ResiliencePolicy(fail_fast=True)


def paper_grid(n: int):
    from examples.dram_paper_repro import make_grid
    return make_grid(n, SEED)


def phase_a(n: int):
    from repro.experiments import run_sweep
    return run_sweep(paper_grid(n), resilience=strict())


def phase_b(n: int):
    from repro.core.dram import PAPER_WORKLOADS, Policy, SimConfig
    from repro.experiments import SweepGrid, run_sweep
    cfg = SimConfig.for_tech("ddr3", density_gb=8, refresh_policy="darp")
    grid = SweepGrid(name="refresh_darp_8gb", workloads=PAPER_WORKLOADS,
                     policies=(Policy.BASELINE, Policy.MASA), n_requests=n,
                     seed=SEED, base_config=cfg)
    return run_sweep(grid, resilience=strict())


def phase_c(n: int):
    from benchmarks.multicore_bench import MIXES
    from examples.dram_paper_repro import POLICIES
    from repro.core.dram import Scheduler, workload
    from repro.experiments import MixGrid, run_mix_sweep
    grid = MixGrid(name="multicore", mixes=[tuple(workload(w) for w in m)
                                            for m in MIXES],
                   policies=POLICIES, n_requests=n, seed=SEED,
                   config_axes={"scheduler": (Scheduler.FRFCFS,
                                              Scheduler.TCM)})
    return run_mix_sweep(grid, resilience=strict())


# ---------------------------------------------------------------------------
# Checks
# ---------------------------------------------------------------------------

def check_complete(name: str, sweep, n_cells: int) -> None:
    """Every cell simulated in this process: none cached, none quarantined."""
    st = sweep.stats
    _check(not sweep.quarantined,
           f"{name}: {len(sweep.quarantined)} cells quarantined, first: "
           f"{sweep.quarantined[:1]}")
    _check(st["n_cells"] == n_cells and len(sweep.cells) == n_cells,
           f"{name}: {len(sweep.cells)}/{st['n_cells']} cells, expected "
           f"{n_cells}")
    if "cache_hits" in st:
        _check(st["cache_hits"] == 0 and st["simulated_cells"] == n_cells,
               f"{name}: {st['cache_hits']} cache hits, "
               f"{st['simulated_cells']} simulated of {n_cells}")


def _close(a: float, b: float) -> bool:
    return abs(a - b) <= FLOAT_RTOL * max(abs(a), abs(b))


def compare(name: str, got, ref) -> None:
    """Chip sweep vs the CPU reference: counters bit-identical, derived
    floats within FLOAT_RTOL, cell for cell in grid order."""
    _check(len(got.cells) == len(ref.cells),
           f"{name}: {len(got.cells)} cells vs {len(ref.cells)} on the CPU")
    for g, r in zip(got.cells, ref.cells):
        gj, rj = g.to_json(), r.to_json()
        where = f"{name}: {gj.get('workload', gj.get('mix'))}/{gj['policy']}"
        _check(gj["counters"] == rj["counters"],
               f"{where}: counters {gj['counters']} != CPU {rj['counters']}")
        for key in ("core_cycles", "alone_cycles"):
            _check(gj.get(key) == rj.get(key), f"{where}: {key} differ")
        floats = dict(gj.get("derived", {}))
        ref_floats = dict(rj.get("derived", {}))
        if "weighted_speedup" in gj:
            floats["weighted_speedup"] = gj["weighted_speedup"]
            ref_floats["weighted_speedup"] = rj["weighted_speedup"]
        for k, v in floats.items():
            _check(_close(v, ref_floats[k]),
                   f"{where}: {k} {v} vs CPU {ref_floats[k]}")


def replay_golden() -> int:
    """Every golden-fixture cell on the default device, bit for bit."""
    import jax
    from repro.core.dram import (ROW_SPACE_STRIDE, Policy, Scheduler,
                                 SimConfig, generate_trace, simulate,
                                 workload)
    from repro.core.dram.multicore import simulate_multicore
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    from test_packed_state import CONFIGS, GOLDEN_PATH, random_trace

    def ints(res) -> dict:
        return {k: int(v) for k, v in
                dataclasses.asdict(jax.device_get(res)).items()}

    with open(GOLDEN_PATH) as f:
        golden = json.load(f)
    for cell in golden["single"]:
        got = ints(simulate(random_trace(cell["seed"]),
                            Policy[cell["policy"]],
                            SimConfig(**CONFIGS[cell["config"]])))
        _check(got == cell["counters"],
               f"golden single {cell['config']}/{cell['policy']}/"
               f"seed {cell['seed']}: {got} != {cell['counters']}")
    for cell in golden["multicore"]:
        mix = [generate_trace(workload(m), 150, seed=cell["seed"],
                              row_space_offset=ROW_SPACE_STRIDE * i)
               for i, m in enumerate(("mcf", "lbm"))]
        cfg = SimConfig(scheduler=Scheduler[cell["scheduler"]],
                        **CONFIGS[cell["config"]])
        r = simulate_multicore(mix, Policy[cell["policy"]], cfg)
        got = ints(r.shared)
        cc = [int(x) for x in r.core_cycles]
        _check(got == cell["counters"] and cc == cell["core_cycles"],
               f"golden multicore {cell['config']}/{cell['scheduler']}/"
               f"{cell['policy']}/seed {cell['seed']}: {got} {cc}")
    return len(golden["single"]) + len(golden["multicore"])


# ---------------------------------------------------------------------------
# Runs
# ---------------------------------------------------------------------------

def timed(fn, *args):
    """(result, wall seconds, {"lower": s, "xla": s} compile seconds)."""
    c0, t0 = dict(_compile_s), time.perf_counter()
    out = fn(*args)
    wall = time.perf_counter() - t0
    return out, wall, {k: v - c0[k] for k, v in _compile_s.items()}


def report(label: str, n_cells: int, n_req: int, cold: float,
           compile_s: dict, warm: float | None = None) -> None:
    line = (f"# chip run, this process: {label}: cold {cold:.3f} s "
            f"(trace+lower {compile_s['lower']:.3f} s, XLA compile or "
            f"cache load {compile_s['xla']:.3f} s)")
    if warm is not None:
        line += (f", warm {warm:.3f} s, {n_cells / warm:.1f} cells/s, "
                 f"{n_req / warm:.0f} requests/s")
    print(line, flush=True)


def run_one_chip(n: int, cpu) -> None:
    """Phases A-C on the default device, each checked against a whole
    re-run on ``cpu``; then the golden replay on the default device."""
    import jax
    import numpy as np
    from examples.dram_paper_repro import PAPER_IPC_GAIN_PCT
    from repro.core.dram import Policy

    phases = (("A paper grid", phase_a, 160, n),
              ("B refresh on (DARP, 8 Gb)", phase_b, 64, n),
              ("C 4-core mixes", phase_c, 40, 4 * n))
    for label, fn, n_cells, req_per_cell in phases:
        _result_devices.clear()
        sweep, cold, comp = timed(fn, n)
        check_complete(label, sweep, n_cells)
        default = jax.devices()[0]
        _check(all(d == default for d in _result_devices),
               f"{label}: results on {set(map(str, _result_devices))}, "
               f"expected {default}")
        again, warm, _ = timed(fn, n)
        compare(f"{label} (warm re-run)", again, sweep)
        report(label, n_cells, n_cells * req_per_cell, cold, comp, warm)
        with jax.default_device(cpu):
            ref, cpu_s, _ = timed(fn, n)
        check_complete(f"{label} on the CPU", ref, n_cells)
        compare(label, sweep, ref)
        print(f"# {label}: {n_cells} cells bit-identical to the CPU "
              f"reference ({cpu_s:.3f} s on the CPU)", flush=True)
        if fn is phase_a:
            base = sweep.metric("ipc", policy=Policy.BASELINE)
            for pol, paper in PAPER_IPC_GAIN_PCT.items():
                gain = 100 * float(np.mean(
                    sweep.metric("ipc", policy=pol) / base - 1))
                print(f"# {label}: {pol.pretty} mean IPC gain {gain:.2f} % "
                      f"(paper {paper} %)")

    n_golden, wall, comp = timed(replay_golden)
    report(f"golden replay, {n_golden} cells bit-identical to the fixture",
           n_golden, 0, wall, comp)


def run_four_chips(n: int, devices) -> None:
    """Phase A sharded over four devices vs the same grid on one."""
    from repro.experiments import ShardPlan, merge_fragment_dir, run_sweep

    _check(len(devices) >= 4, f"--chips 4 needs 4 devices, found "
                              f"{len(devices)}")
    one, cold_1, comp_1 = timed(phase_a, n)
    check_complete("A on one chip", one, 160)
    report("A paper grid, one chip", 160, 160 * n, cold_1, comp_1)

    plan = ShardPlan(4, devices[:4])
    _result_devices.clear()
    with tempfile.TemporaryDirectory() as frag_dir:
        sharded, cold_4, comp_4 = timed(
            lambda: run_sweep(paper_grid(n), resilience=strict(), shards=plan,
                              fragment_dir=frag_dir))
        merged = merge_fragment_dir(frag_dir)
    check_complete("A sharded", sharded, 160)
    report("A paper grid, 4 shards on 4 chips", 160, 160 * n, cold_4, comp_4)
    _check(merged["quarantined"] == [], "sharded merge has quarantined cells")
    _check(json.dumps(merged["cells"], sort_keys=True)
           == json.dumps(one.to_json()["cells"], sort_keys=True),
           "merged fragments differ from the one-chip sweep")
    used = set(_result_devices)
    _check(used == set(plan.devices),
           f"shard results lived on {sorted(map(str, used))}, expected the "
           f"4 plan devices {sorted(map(str, plan.devices))}")
    frag_devices = {f["shard"]["device"] for f in sharded.fragments
                    if f["shard"]["role"] == "shard"}
    _check(len(frag_devices) == 4, f"fragment devices: {frag_devices}")
    print(f"# A sharded: {len(merged['cells'])} merged cells bit-identical "
          f"to the one-chip sweep; shard results on "
          f"{len(used)} distinct devices", flush=True)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only Phase A sharded over four chips, "
                         "against the same grid on one chip")
    args = ap.parse_args(argv)

    # The reference runs on the CPU backend of this process: keep it
    # available where the platforms are pinned.
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    try:
        import jax
        devices = jax.devices()
        dev = devices[0]
        if dev.platform != "tpu":
            raise SmokeFailure(f"no TPU found: JAX's default device is "
                               f"{dev.platform} ({dev.device_kind})")
        sys.path.insert(0, os.path.join(ROOT, "src"))
        from repro import compile_cache
        print(f"# compilation cache: {compile_cache.enable()}")
        _install_probes()
        print(f"# devices: {len(devices)} x {dev.device_kind}", flush=True)
        if args.chips == 4:
            run_four_chips(N_REQUESTS, devices)
        else:
            run_one_chip(N_REQUESTS, jax.devices("cpu")[0])
    except Exception as e:  # noqa: BLE001 — every failure ends in "ok": false
        traceback.print_exc()
        print(json.dumps({"ok": False, "error": f"{type(e).__name__}: {e}"}))
        return 1
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
