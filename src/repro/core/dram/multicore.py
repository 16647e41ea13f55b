"""Multi-core shared-channel simulation (paper Sec. 4 / Sec. 9.3 of [66]).

``n_cores`` request streams share one channel's banks. Each core issues its
own requests in program order (same analytic OoO core as the single-core
engine); the memory controller (:mod:`repro.core.dram.controller` — the SAME
scan step ``simulate`` instantiates with one core) picks among the cores' head
requests with the configured scheduler (``SimConfig.scheduler``): FCFS,
FR-FCFS, FR-FCFS+SALP-aware, or TCM-style application-aware ranking — the
scheduler combinations the paper evaluates on top of SALP. Refresh/DSARP and
the closed-row policy apply here exactly as in single-core, via ``SimConfig``.

The controller scan underneath runs on the packed state layout
(:mod:`repro.core.dram.state_layout`). On the scan backend a batch of M
mixes — and a single mix, as M = 1 — runs ONE lane-major C-core scan
(``controller._simulate_mixes_lanes``): the mixes ride side by side in the
scan's carry, and each step gathers every lane's heads, keys them, serves
each lane's ``argmin`` and moves each lane's served bank block with one
gather and one scatter. It is bit-identical to ``jax.vmap`` of the per-mix
controller (``controller._simulate_controller``, which ``simulate`` and
command export still run) — tests/test_controller.py and
tests/test_packed_state.py pin both against each other and the golden
fixture.

Metrics: weighted speedup = sum_i IPC_shared(i) / IPC_alone(i).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.core.dram import controller
from repro.core.dram.engine import SimConfig, SimResult, _controller_args
from repro.core.dram.policies import Policy
from repro.core.dram.schedulers import Scheduler
from repro.core.dram.trace import Trace, WorkloadProfile, to_ideal, stack_traces


@dataclasses.dataclass
class MulticoreResult:
    shared: SimResult
    core_cycles: np.ndarray          # per-core completion of its own stream
    alone_cycles: np.ndarray         # per-core cycles when run ALONE on the BASELINE
    profiles: list[WorkloadProfile]

    @property
    def weighted_speedup(self) -> float:
        """Sum_i IPC_shared,i / IPC_alone-baseline,i.

        The alone reference is the *baseline* memory system for every policy, so
        cross-policy WS ratios reflect the full mechanism benefit (the paper's
        multi-core system-performance metric).
        """
        return float(np.sum(self.alone_cycles / np.maximum(self.core_cycles, 1)))


def _prep_mix(traces: list[Trace], policy: Policy, config: SimConfig):
    work = [to_ideal(t, config.n_banks, config.n_subarrays) if policy == Policy.IDEAL else t
            for t in traces]
    st = stack_traces(work)
    # TCM-style ranking: lower MPKI -> higher priority (rank 0 first)
    mpkis = np.array([t.profile.mpki for t in traces])
    rank = np.argsort(np.argsort(mpkis)).astype(np.int32)
    return st, rank


def _scheduler_for(config: SimConfig, use_ranking: bool) -> SimConfig:
    """Fold the deprecated ``use_ranking`` flag into ``config.scheduler``."""
    if use_ranking:
        return dataclasses.replace(config, scheduler=Scheduler.TCM)
    return config


def alone_baseline_cycles(mixes: list[list[Trace]],
                          config: SimConfig = SimConfig()) -> np.ndarray:
    """Per-trace run-alone BASELINE cycles for all mixes, one vmapped call.

    Policy-independent (the alone reference is the baseline memory system for
    every policy), so callers comparing several policies over the same mixes
    should compute it once and pass it to ``simulate_multicore_batch``. The
    scheduler is normalized to FCFS — with a single stream it is inert, and
    normalizing avoids one redundant XLA compile per scheduler value.
    """
    from repro.core.dram.engine import simulate_batch
    cfg = dataclasses.replace(config, scheduler=Scheduler.FCFS)
    flat = [t for m in mixes for t in m]
    return np.asarray(simulate_batch(flat, Policy.BASELINE, cfg).total_cycles,
                      np.float64)


def runs_mix_lanes(config: SimConfig) -> bool:
    """Whether :func:`simulate_multicore_batch` serves a batch under
    ``config`` with the lane-major C-core scan
    (``controller._simulate_mixes_lanes``): the scan backend, under every
    scheduler, refresh policy and row policy. The Pallas backends run the
    fused mix kernel instead."""
    return config.backend == "scan"


def simulate_multicore_batch(mixes: list[list[Trace]], policy: Policy,
                             config: SimConfig = SimConfig(),
                             use_ranking: bool = False,
                             alone_cycles: np.ndarray | None = None,
                             ) -> list[MulticoreResult]:
    """Batched entry point: simulate M mixes in one compiled program.

    All mixes must have the same core count and trace length; they share one
    compiled program ([M, C, N] stacked arrays) instead of M sequential scans:
    on the scan backend the lane-major C-core scan
    (``controller._simulate_mixes_lanes``), under every scheduler, refresh
    policy and row policy; on the Pallas backend the fused mix kernel.
    ``alone_cycles`` (flat [sum_len(mixes)] array from
    ``alone_baseline_cycles``) skips recomputing the policy-independent
    run-alone references on every policy comparison. ``use_ranking=True`` is a
    deprecated alias for ``config.scheduler = Scheduler.TCM``.
    """
    config = _scheduler_for(config, use_ranking)
    eff, sched, nb, ns = _controller_args(policy, config)
    with spans.span("repro.bucket.stage"):
        prepped = [_prep_mix(m, policy, config) for m in mixes]
        stacked = {k: jnp.asarray(np.stack([st[k] for st, _ in prepped]))
                   for k in prepped[0][0]}
        ranks = jnp.asarray(np.stack([r for _, r in prepped]))
        controller.validate_mlp_window(stacked["mlp_window"])

        if not runs_mix_lanes(config):
            # fused Pallas mix kernel: the mix dimension is the kernel grid
            # axis, no outer vmap (docs/kernels.md). Refuses emit_commands.
            from repro.core.dram import pallas_step
            pallas_step.check_no_emit(config)
            shared, core_cycles = pallas_step._simulate_cores_pallas(
                eff, sched, nb, ns, config.timing, config.refresh_mode,
                stacked["bank"], stacked["subarray"], stacked["row"],
                stacked["is_write"], stacked["gap"], stacked["dep"],
                stacked["mlp_window"], ranks,
                closed_row=config.row_policy == "closed",
                interpret=config.backend == "pallas-interpret")
        else:
            shared, core_cycles = controller._simulate_mixes_lanes(
                eff, sched, nb, ns, config.timing, config.refresh_mode,
                stacked["bank"], stacked["subarray"], stacked["row"],
                stacked["is_write"], stacked["gap"], stacked["dep"],
                stacked["mlp_window"], ranks,
                closed_row=config.row_policy == "closed")

    alone_all = (alone_cycles if alone_cycles is not None
                 else alone_baseline_cycles(mixes, config))

    with spans.span("repro.bucket.device_wait"):
        jax.block_until_ready((shared, core_cycles))
    with spans.span("repro.bucket.readback"):
        out = []
        pos = 0
        for i, m in enumerate(mixes):
            res_i = SimResult(**{f.name: np.asarray(getattr(shared, f.name))[i]
                                 for f in dataclasses.fields(SimResult)})
            out.append(MulticoreResult(
                shared=res_i,
                core_cycles=np.asarray(core_cycles, np.float64)[i],
                alone_cycles=alone_all[pos:pos + len(m)],
                profiles=[t.profile for t in m]))
            pos += len(m)
    return out


def simulate_multicore(traces: list[Trace], policy: Policy,
                       config: SimConfig = SimConfig(),
                       use_ranking: bool = False) -> MulticoreResult:
    """Simulate one mix of traces sharing a channel: the batched entry
    point with M = 1."""
    return simulate_multicore_batch([traces], policy, config, use_ranking)[0]
