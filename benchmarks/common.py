"""Shared helpers for the benchmark suite.

Canonical reproduction settings: 8000 requests/workload, seed 7 — the
calibration frozen in EXPERIMENTS.md. Every benchmark prints
``name,us_per_call,derived`` CSV rows (one per paper table/figure entry).
"""
from __future__ import annotations

import time
from typing import Callable

import jax
import numpy as np

N_REQUESTS = 8000
SEED = 7

#: Sweep artifacts (``repro.sweep/v1`` dicts) produced by benchmarks in this
#: process; ``benchmarks.run`` folds them into its single bench artifact.
SWEEPS: list[dict] = []

#: Deterministic fault-injection plan (``repro.experiments.FaultPlan``) set by
#: ``benchmarks.run --inject-faults``; threaded through every sweep so CI can
#: exercise the retry/bisect/quarantine paths on the real pipeline.
FAULT_PLAN = None

#: Optional ``repro.experiments.ResiliencePolicy`` override for every sweep.
RESILIENCE = None

#: Optional ``repro.experiments.ShardPlan`` set by ``benchmarks.run
#: --shards/--mesh``: every sweep partitions its buckets across the plan's
#: devices and streams per-shard fragments (bit-identical results).
SHARD_PLAN = None

#: Root directory for streamed ``repro.sweep-fragment/v1`` documents
#: (``benchmarks.run --fragments``); each sweep writes under
#: ``<FRAGMENT_DIR>/<grid name>/``. ``None`` = keep fragments in memory only.
FRAGMENT_DIR = None


def _fragment_dir(grid) -> str | None:
    if FRAGMENT_DIR is None:
        return None
    import os
    return os.path.join(FRAGMENT_DIR, grid.name)


def mem_intensive(min_mpki: float = 9.0):
    """The memory-intensive subset (the regime where geometry matters)."""
    from repro.core.dram import PAPER_WORKLOADS
    return tuple(p for p in PAPER_WORKLOADS if p.mpki >= min_mpki)


def run_grid(grid):
    """Run a SweepGrid against the process-wide result cache.

    All benchmarks of one ``benchmarks.run`` invocation share
    ``GLOBAL_CACHE``, so a (workload, geometry, policy) cell is simulated at
    most once per process no matter how many benchmarks touch it. With a
    journal-backed cache installed (``benchmarks.run --journal``) the sharing
    extends across processes: completed cells replay from disk.
    """
    from repro.experiments import GLOBAL_CACHE, run_sweep
    sweep = run_sweep(grid, GLOBAL_CACHE, resilience=RESILIENCE,
                      fault_plan=FAULT_PLAN, shards=SHARD_PLAN,
                      fragment_dir=_fragment_dir(grid))
    SWEEPS.append(sweep.to_json())
    return sweep


def run_mix_grid(grid):
    """Run a MixGrid (multi-core policy x scheduler sweep), registering its
    ``repro.sweep/v1`` artifact alongside the single-core sweeps."""
    from repro.experiments import run_mix_sweep
    sweep = run_mix_sweep(grid, resilience=RESILIENCE, fault_plan=FAULT_PLAN,
                          shards=SHARD_PLAN, fragment_dir=_fragment_dir(grid))
    SWEEPS.append(sweep.to_json())
    return sweep


def per_sim_cell_us(sweep, us: float) -> float:
    """us per actually-simulated cell (cache hits cost ~nothing and would
    dilute the column into meaninglessness on warm caches)."""
    return us / max(sweep.stats["simulated_cells"], 1)


def timed(fn: Callable, *args, **kwargs):
    """Wall-time one call in microseconds, *including* device completion.

    JAX dispatch is asynchronous: without ``block_until_ready`` the clock
    stops when the result is enqueued, not when it is computed, so every
    ``us_per_call`` CSV row would underreport device time. Non-array leaves
    (sweep objects, floats) pass through ``block_until_ready`` untouched.
    """
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kwargs))
    return out, (time.perf_counter() - t0) * 1e6


def emit(name: str, us: float, derived) -> str:
    row = f"{name},{us:.1f},{derived}"
    print(row)
    return row


def suite_traces(n: int = N_REQUESTS, seed: int = SEED):
    """Suite traces via the sweep runner's memoized trace cache.

    Trace generation is a host-side Python loop over n requests; routing
    through :func:`repro.experiments.runner.trace_for` means benchmark
    modules sharing (workload, n, geometry, seed) cells regenerate nothing.
    """
    from repro.core.dram import PAPER_WORKLOADS
    from repro.core.dram.engine import SimConfig
    from repro.experiments.runner import trace_for
    cfg = SimConfig()  # default geometry — matches generate_trace defaults
    return [trace_for(p, n, cfg, seed) for p in PAPER_WORKLOADS]


def command_slice(trace, policy, config, out_path: str) -> dict:
    """One command-level fidelity cell: export, check, cross-validate, dump.

    Runs the emitting simulation, asserts the stream is legal under the full
    JEDEC rule table (``check_trace``), asserts the stream alone reproduces
    the engine's SimResult counters (minus the non-derivable
    ``sa_open_cycles``), then writes the ramulator-style dump to ``out_path``
    so CI can re-parse and re-check it (``benchmarks.validate
    --check-commands``) and upload it next to the JSON artifact.
    """
    import dataclasses
    import hashlib
    import os

    from repro.core.dram import (check_trace, counters_from_commands,
                                 simulate_commands)
    from repro.core.dram.engine import SimResult

    res, ct = simulate_commands(trace, policy, config)
    chk = check_trace(ct)
    if not chk.ok:
        raise AssertionError(f"illegal command stream: {chk.summary()}")
    got = counters_from_commands(ct)
    want = {f.name: int(np.asarray(getattr(res, f.name)))
            for f in dataclasses.fields(SimResult)}
    want.pop("sa_open_cycles")
    if got != want:
        raise AssertionError(
            f"command stream does not reproduce the engine's counters: "
            f"{got} != {want}")
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    ct.dump(out_path)
    return {"path": out_path, "n_commands": len(ct), "ops": ct.counts(),
            "n_rules": chk.n_rules, "checker_ok": True,
            "sha256": hashlib.sha256(ct.dumps().encode()).hexdigest()}
