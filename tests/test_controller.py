"""Controller layer + pluggable schedulers: single/multi-core unification,
the completion-ring invariant, refresh in multicore, and scheduler ordering
properties."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dram import (ROW_SPACE_STRIDE, Policy, Scheduler, SimConfig,
                             controller, generate_trace, simulate, workload)
from repro.core.dram.engine import SimResult, _RING, _controller_args
from repro.core.dram.multicore import (_prep_mix, runs_mix_lanes,
                                       simulate_multicore,
                                       simulate_multicore_batch)
from repro.core.dram.trace import Trace

FCFS = SimConfig(scheduler=Scheduler.FCFS)
FRFCFS = SimConfig(scheduler=Scheduler.FRFCFS)


def mix_of(names, n=400, seed=7):
    return [generate_trace(workload(m), n, seed=seed,
                           row_space_offset=ROW_SPACE_STRIDE * i)
            for i, m in enumerate(names)]


def counters(res: SimResult) -> dict:
    return {f.name: int(np.asarray(getattr(res, f.name)))
            for f in dataclasses.fields(SimResult)}


class TestRingInvariant:
    """`mlp_window < _RING` — a window as deep as the ring would read the
    slot the current request overwrites (silent corruption pre-refactor)."""

    def bad_trace(self, mlp):
        tr = generate_trace(workload("mcf"), 64, seed=1)
        return dataclasses.replace(tr, mlp_window=mlp)

    def test_simulate_rejects_oversized_window(self):
        with pytest.raises(ValueError, match="mlp_window"):
            simulate(self.bad_trace(_RING), Policy.BASELINE)
        with pytest.raises(ValueError, match="mlp_window"):
            simulate(self.bad_trace(_RING + 7), Policy.MASA)

    def test_simulate_rejects_nonpositive_window(self):
        with pytest.raises(ValueError, match="mlp_window"):
            simulate(self.bad_trace(0), Policy.BASELINE)

    def test_multicore_rejects_oversized_window(self):
        mix = [self.bad_trace(_RING), generate_trace(workload("lbm"), 64, seed=1)]
        with pytest.raises(ValueError, match="mlp_window"):
            simulate_multicore(mix, Policy.BASELINE)

    def test_batch_rejects_oversized_window(self):
        from repro.core.dram import simulate_batch
        with pytest.raises(ValueError, match="mlp_window"):
            simulate_batch([self.bad_trace(_RING)] * 2, Policy.BASELINE)

    def test_boundary_window_accepted(self):
        res = simulate(self.bad_trace(_RING - 1), Policy.BASELINE)
        assert int(res.n_requests) == 64


class TestSingleMulticoreUnification:
    """`simulate` and `simulate_multicore` share one controller step: a
    1-core mix must be bit-identical to the single-core entry point,
    including under refresh and DSARP (which multicore previously lacked)."""

    @pytest.mark.parametrize("cfg", [
        SimConfig(),
        SimConfig(refresh=True),
        SimConfig(refresh=True, dsarp=True),
        SimConfig(row_policy="closed"),
    ], ids=["default", "refresh", "dsarp", "closed"])
    @pytest.mark.parametrize("policy", [Policy.BASELINE, Policy.MASA])
    def test_one_core_mix_bit_identical(self, policy, cfg):
        tr = generate_trace(workload("lbm"), 600, seed=7)
        single = counters(simulate(tr, policy, cfg))
        multi = counters(simulate_multicore([tr], policy, cfg).shared)
        assert single == multi

    def test_refresh_slows_multicore(self):
        """Refresh now exists in multicore: it must cost cycles there too."""
        mix = mix_of(("mcf", "lbm"))
        off = int(simulate_multicore(mix, Policy.BASELINE, FRFCFS).shared.total_cycles)
        ref = int(simulate_multicore(
            mix, Policy.BASELINE,
            dataclasses.replace(FRFCFS, refresh=True)).shared.total_cycles)
        assert ref > off

    def test_dsarp_recovers_refresh_penalty_in_multicore(self):
        """DSARP + MASA parallelizes refresh in the shared-channel sim too."""
        mix = mix_of(("lbm", "milc"))
        cfg_ref = dataclasses.replace(FRFCFS, refresh=True)
        cfg_dsarp = dataclasses.replace(FRFCFS, refresh=True, dsarp=True)
        off = int(simulate_multicore(mix, Policy.MASA, FRFCFS).shared.total_cycles)
        blocking = int(simulate_multicore(mix, Policy.MASA, cfg_ref).shared.total_cycles)
        dsarp = int(simulate_multicore(mix, Policy.MASA, cfg_dsarp).shared.total_cycles)
        # subarray-granular refresh can absorb the penalty entirely (== off)
        assert off <= dsarp <= blocking
        assert blocking > off

    def test_closed_row_in_multicore(self):
        mix = mix_of(("lbm", "milc"))
        closed = dataclasses.replace(FRFCFS, row_policy="closed")
        res = simulate_multicore(mix, Policy.BASELINE, closed).shared
        assert int(res.n_hit) == 0


class TestPinnedMulticoreRegression:
    """Literal multicore regression pins (mcf+lbm, 400 reqs, seed 7, MASA).

    The FR-FCFS and TCM rows were captured from the pre-refactor inline
    multicore implementation and survive the controller extraction AND the
    pending-gate scheduler fix bit-for-bit on this mix; FCFS and
    FR-FCFS+SALP pin the new layer's semantics going forward."""

    # scheduler -> (shared total_cycles, n_act, n_hit, per-core cycles)
    EXPECTED = {
        Scheduler.FCFS: (6454, 164, 636, [6454, 4459]),
        Scheduler.FRFCFS: (6699, 161, 639, [6699, 4061]),       # pre-refactor
        Scheduler.FRFCFS_SALP: (6915, 167, 633, [6915, 4897]),
        Scheduler.TCM: (7070, 153, 647, [7070, 3047]),          # pre-refactor
        # PALP_RP pins the read-priority rung's semantics going forward. On
        # this DRAM mix its shared counters happen to coincide with
        # FRFCFS_SALP (both add one middle tier over FR-FCFS) but the
        # per-core split differs — the rung favors mcf's read-heavy stream.
        Scheduler.PALP_RP: (6915, 167, 633, [6127, 6915]),
    }

    @pytest.mark.parametrize("sched", list(Scheduler))
    def test_pinned_values(self, sched):
        mix = mix_of(("mcf", "lbm"))
        r = simulate_multicore(mix, Policy.MASA, SimConfig(scheduler=sched))
        got = (int(r.shared.total_cycles), int(r.shared.n_act),
               int(r.shared.n_hit), [int(x) for x in r.core_cycles])
        assert got == self.EXPECTED[sched]
        # and the batch path is bit-identical to the sequential one
        ref = simulate_multicore_batch([mix], Policy.MASA,
                                       SimConfig(scheduler=sched))[0]
        assert int(ref.shared.total_cycles) == got[0]
        assert [int(x) for x in ref.core_cycles] == got[3]

    def test_use_ranking_is_tcm_alias(self):
        mix = mix_of(("mcf", "lbm"))
        via_flag = simulate_multicore(mix, Policy.MASA, FRFCFS, use_ranking=True)
        via_config = simulate_multicore(mix, Policy.MASA,
                                        SimConfig(scheduler=Scheduler.TCM))
        assert counters(via_flag.shared) == counters(via_config.shared)


class TestSchedulerProperties:
    # row-hit-heavy: high row_run / seq_frac suite members
    HIT_HEAVY = (("libquantum", "stream_copy", "bwaves", "hmmer"),
                 ("libquantum", "stream_copy"))

    @pytest.mark.parametrize("names", HIT_HEAVY, ids=["4core", "2core"])
    @pytest.mark.parametrize("seed", [1, 7, 13])
    def test_frfcfs_never_slower_on_hit_heavy(self, names, seed):
        """FR-FCFS (hits first among queued requests) never increases total
        cycles vs FCFS on a row-hit-heavy mix under the baseline policy."""
        mix = mix_of(names, n=500, seed=seed)
        fcfs = int(simulate_multicore(mix, Policy.BASELINE, FCFS).shared.total_cycles)
        frfcfs = int(simulate_multicore(mix, Policy.BASELINE, FRFCFS).shared.total_cycles)
        assert frfcfs <= fcfs

    def test_single_core_scheduler_inert(self):
        """With one core there is a single head request: every scheduler is
        program order, so the choice cannot change results."""
        tr = generate_trace(workload("lbm"), 400, seed=7)
        ref = counters(simulate(tr, Policy.MASA, FCFS))
        for sched in (Scheduler.FRFCFS, Scheduler.FRFCFS_SALP, Scheduler.TCM,
                      Scheduler.PALP_RP):
            got = counters(simulate(tr, Policy.MASA, SimConfig(scheduler=sched)))
            assert got == ref, sched

    @pytest.mark.parametrize("sched", list(Scheduler))
    def test_conservation_under_any_scheduler(self, sched):
        """Every request is served exactly once whatever the discipline."""
        mix = mix_of(("mcf", "lbm", "gups"), n=200)
        res = simulate_multicore(mix, Policy.MASA,
                                 SimConfig(scheduler=sched)).shared
        n = 3 * 200
        assert int(res.n_rd) + int(res.n_wr) == n
        assert int(res.n_act) + int(res.n_hit) == n

    def test_salp_aware_prefers_open_subarrays(self):
        """Under MASA, the SALP-aware scheduler must not lower the row-hit
        count vs plain FR-FCFS on a conflict-heavy mix (it steers requests
        to still-activated subarrays)."""
        mix = mix_of(("lbm", "milc", "zeusmp", "GemsFDTD"), n=500)
        fr = simulate_multicore(mix, Policy.MASA, FRFCFS).shared
        sa = simulate_multicore(
            mix, Policy.MASA,
            SimConfig(scheduler=Scheduler.FRFCFS_SALP)).shared
        assert int(sa.n_hit) >= int(fr.n_hit) - 5  # small reorder slack

    def test_tcm_prioritizes_latency_sensitive_cores(self):
        """TCM ranking must not worsen the low-MPKI cores' completion vs
        plain FR-FCFS (they are strictly prioritized)."""
        mix = mix_of(("gamess", "lbm", "povray", "stream_copy"), n=400)
        mpki = np.array([t.profile.mpki for t in mix])
        lat_sensitive = np.argsort(np.argsort(mpki)) < 2
        fr = simulate_multicore(mix, Policy.MASA, FRFCFS)
        tcm = simulate_multicore(mix, Policy.MASA,
                                 SimConfig(scheduler=Scheduler.TCM))
        assert (tcm.core_cycles[lat_sensitive]
                <= fr.core_cycles[lat_sensitive] + 1).all()


class TestMixGridApi:
    def test_mix_sweep_matches_direct_multicore(self):
        from repro.experiments import MixGrid, run_mix_sweep
        from repro.experiments.runner import trace_for
        grid = MixGrid(
            name="t", mixes=[(workload("mcf"), workload("lbm"))],
            policies=(Policy.MASA,), n_requests=200,
            configs=({"scheduler": Scheduler.FRFCFS, "refresh": True},))
        sweep = run_mix_sweep(grid)
        assert sweep.stats["n_cells"] == 1
        cell = sweep.cells[0]
        cfg = SimConfig(scheduler=Scheduler.FRFCFS, refresh=True)
        mix = [trace_for(workload("mcf"), 200, cfg, grid.seed, 0),
               trace_for(workload("lbm"), 200, cfg, grid.seed, ROW_SPACE_STRIDE)]
        ref = simulate_multicore(mix, Policy.MASA, cfg)
        assert cell.counters == counters(ref.shared)
        assert cell.core_cycles == [int(x) for x in ref.core_cycles]

    def test_scheduler_axis_in_overrides_and_json(self):
        import json
        from repro.experiments import MixGrid, run_mix_sweep
        grid = MixGrid(
            name="t", mixes=[(workload("mcf"), workload("lbm"))],
            policies=(Policy.BASELINE,), n_requests=100,
            config_axes={"scheduler": (Scheduler.FCFS, Scheduler.FRFCFS)})
        sweep = run_mix_sweep(grid)
        doc = sweep.to_json()
        json.dumps(doc)   # enum values must serialize
        assert doc["kind"] == "mix_sweep"
        assert {c["overrides"]["scheduler"] for c in doc["cells"]} == {
            "FCFS", "FRFCFS"}
        assert doc["grid"]["mixes"] == [["mcf", "lbm"]]

    def test_mismatched_core_counts_rejected(self):
        from repro.experiments import MixGrid
        with pytest.raises(ValueError, match="core count"):
            MixGrid(name="t",
                    mixes=[(workload("mcf"),), (workload("mcf"), workload("lbm"))],
                    policies=(Policy.BASELINE,))

    def test_sweepgrid_scheduler_axis(self):
        """The scheduler axis threads through the single-core grid too."""
        from repro.experiments import ResultCache, SweepGrid, run_sweep
        grid = SweepGrid(name="t", workloads=(workload("mcf"),),
                         policies=(Policy.MASA,), n_requests=100,
                         config_axes={"scheduler": (Scheduler.FCFS,
                                                    Scheduler.FRFCFS)})
        sweep = run_sweep(grid, ResultCache())
        a, b = [c.counters for c in sweep.cells]
        assert a == b   # single-core: schedulers are inert, results identical


#: Refresh deadlines inside the parity traces (the default tREFI of 4,160
#: cycles is past the end of a few hundred requests).
REF_TIMING = dataclasses.replace(
    SimConfig().timing, t_refi=520, t_rfc=80, t_rfc_pb=32, ref_postpone_max=2)

#: (policy, scheduler, SimConfig kwargs, cores) cases of the lane-major mix
#: scan's parity test: every scheduler (PALP_RP on its PCM pack), every
#: policy, every refresh policy, open and closed rows, C = 2 and C = 4.
MIX_CASES = [
    (Policy.BASELINE, Scheduler.FCFS, dict(), 2),
    (Policy.SALP1, Scheduler.FRFCFS,
     dict(refresh_policy="all_bank", timing=REF_TIMING), 4),
    (Policy.SALP2, Scheduler.FRFCFS_SALP,
     dict(refresh_policy="per_bank", timing=REF_TIMING), 2),
    (Policy.MASA, Scheduler.TCM,
     dict(refresh_policy="darp", timing=REF_TIMING), 4),
    (Policy.MASA, Scheduler.PALP_RP, dict(memtech="pcm_palp"), 4),
    (Policy.IDEAL, Scheduler.FRFCFS,
     dict(refresh_policy="sarp", timing=REF_TIMING), 2),
    (Policy.MASA, Scheduler.FCFS,
     dict(refresh_policy="dsarp", timing=REF_TIMING), 2),
    (Policy.BASELINE, Scheduler.TCM,
     dict(refresh_policy="dsarp", timing=REF_TIMING, row_policy="closed"), 4),
    (Policy.MASA, Scheduler.FRFCFS, dict(row_policy="closed"), 2),
    (Policy.IDEAL, Scheduler.FRFCFS_SALP,
     dict(refresh_policy="darp", timing=REF_TIMING, row_policy="closed"), 4),
    (Policy.SALP2, Scheduler.PALP_RP,
     dict(memtech="pcm_palp", row_policy="closed"), 2),
    (Policy.SALP1, Scheduler.TCM,
     dict(refresh_policy="sarp", timing=REF_TIMING, row_policy="closed"), 2),
]

#: Workloads of the parity mixes, and the per-core ROB windows that replace
#: the profiles' own (they differ within every mix).
MIX_NAMES = ("mcf", "lbm", "milc", "gups", "libquantum", "gamess")
MIX_MLP = (3, 12, 7, 1, 9)


def _mix_case_id(case) -> str:
    policy, sched, kw, C = case
    extra = [f"{k}={v}" for k, v in kw.items() if k != "timing"]
    return "-".join([policy.name, sched.name, *extra, f"C{C}"])


class TestLaneMajorMixes:
    """``simulate_multicore_batch`` runs M mixes on one lane-major C-core
    scan (``controller._simulate_mixes_lanes``): bit-identical, in every
    SimResult field and in ``core_cycles``, to ``jax.vmap`` of the per-mix
    controller and to ``simulate_multicore`` one mix at a time."""

    @staticmethod
    def mixes(C, n=160, M=3):
        out = []
        for m in range(M):
            mix = mix_of([MIX_NAMES[(m + i) % len(MIX_NAMES)]
                          for i in range(C)], n=n, seed=11 + m)
            out.append([dataclasses.replace(
                tr, mlp_window=MIX_MLP[(m + i) % len(MIX_MLP)])
                for i, tr in enumerate(mix)])
        return out

    @pytest.mark.parametrize("case", MIX_CASES, ids=_mix_case_id)
    def test_batch_matches_vmapped_controller(self, case):
        policy, sched, kw, C = case
        cfg = SimConfig(scheduler=sched, **kw)
        assert runs_mix_lanes(cfg)
        mixes = self.mixes(C)
        assert all(len({t.mlp_window for t in mix}) > 1 for mix in mixes)
        batch = simulate_multicore_batch(mixes, policy, cfg)

        eff, sch, nb, ns = _controller_args(policy, cfg)
        prepped = [_prep_mix(m, policy, cfg) for m in mixes]
        st = {k: jnp.asarray(np.stack([s[k] for s, _ in prepped]))
              for k in prepped[0][0]}
        ranks = jnp.asarray(np.stack([r for _, r in prepped]))
        shared, core_cycles = jax.vmap(functools.partial(
            controller._simulate_controller, eff, sch, nb, ns, cfg.timing,
            cfg.refresh_mode, closed_row=cfg.row_policy == "closed"))(
            *(st[k] for k in ("bank", "subarray", "row", "is_write", "gap",
                              "dep")), st["mlp_window"], ranks)
        for i, (mix, got) in enumerate(zip(mixes, batch)):
            vmapped = {f.name: int(np.asarray(getattr(shared, f.name))[i])
                       for f in dataclasses.fields(SimResult)}
            assert counters(got.shared) == vmapped, i
            assert got.core_cycles.tolist() == np.asarray(
                core_cycles)[i].tolist(), i
            one = simulate_multicore(mix, policy, cfg)
            assert counters(one.shared) == vmapped, i
            assert one.core_cycles.tolist() == got.core_cycles.tolist(), i
