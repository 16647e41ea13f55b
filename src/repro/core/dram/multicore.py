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
(:mod:`repro.core.dram.state_layout`); with C == 1 it takes a statically
specialized fast path (serve order = program order, no scheduler argmin)
that is bit-identical to the general path — the 1-core-mix ≡ ``simulate``
assertions in tests/test_controller.py pin exactly that equivalence.

Metrics: weighted speedup = sum_i IPC_shared(i) / IPC_alone(i).
"""
from __future__ import annotations

import dataclasses
import functools

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


def simulate_multicore_batch(mixes: list[list[Trace]], policy: Policy,
                             config: SimConfig = SimConfig(),
                             use_ranking: bool = False,
                             alone_cycles: np.ndarray | None = None,
                             ) -> list[MulticoreResult]:
    """Batched entry point: vmap the shared-channel controller over M mixes.

    All mixes must have the same core count and trace length; they share one
    compiled program ([M, C, N] stacked arrays) instead of M sequential scans.
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

        if config.backend != "scan":
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
            fn = _controller_fn(eff, sched, nb, ns, config)
            shared, core_cycles = jax.vmap(fn)(
                stacked["bank"], stacked["subarray"], stacked["row"],
                stacked["is_write"], stacked["gap"], stacked["dep"],
                stacked["mlp_window"], ranks)

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


def _controller_fn(eff: int, sched: int, nb: int, ns: int,
                   config: SimConfig):
    return functools.partial(
        controller._simulate_controller, eff, sched, nb, ns,
        config.timing, config.refresh_mode,
        closed_row=config.row_policy == "closed")


def simulate_multicore(traces: list[Trace], policy: Policy,
                       config: SimConfig = SimConfig(),
                       use_ranking: bool = False) -> MulticoreResult:
    """Simulate one mix of traces sharing a channel (C-core controller)."""
    config = _scheduler_for(config, use_ranking)
    eff, sched, nb, ns = _controller_args(policy, config)
    st, rank = _prep_mix(traces, policy, config)
    controller.validate_mlp_window(st["mlp_window"])
    if config.backend != "scan":
        # fused Pallas mix kernel with M = 1 (docs/kernels.md)
        from repro.core.dram import pallas_step
        pallas_step.check_no_emit(config)
        shared, core_cycles = pallas_step._simulate_cores_pallas(
            eff, sched, nb, ns, config.timing, config.refresh_mode,
            jnp.asarray(st["bank"])[None], jnp.asarray(st["subarray"])[None],
            jnp.asarray(st["row"])[None], jnp.asarray(st["is_write"])[None],
            jnp.asarray(st["gap"])[None], jnp.asarray(st["dep"])[None],
            jnp.asarray(st["mlp_window"], jnp.int32)[None],
            jnp.asarray(rank)[None],
            closed_row=config.row_policy == "closed",
            interpret=config.backend == "pallas-interpret")
        shared = jax.tree_util.tree_map(lambda x: x[0], shared)
        core_cycles = core_cycles[0]
    else:
        shared, core_cycles = _controller_fn(eff, sched, nb, ns, config)(
            jnp.asarray(st["bank"]), jnp.asarray(st["subarray"]),
            jnp.asarray(st["row"]), jnp.asarray(st["is_write"]),
            jnp.asarray(st["gap"]), jnp.asarray(st["dep"]),
            jnp.asarray(st["mlp_window"]), jnp.asarray(rank))
    alone = alone_baseline_cycles([traces], config)
    return MulticoreResult(shared=shared,
                           core_cycles=np.asarray(core_cycles, np.float64),
                           alone_cycles=alone,
                           profiles=[t.profile for t in traces])
