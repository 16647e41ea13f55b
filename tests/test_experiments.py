"""Sweep subsystem: grid expansion, shape bucketing, caching, and parity of
vectorized sweep results vs. per-config `simulate` loops."""
import dataclasses

import numpy as np
import pytest

from repro.core.dram import PAPER_WORKLOADS, Policy, SimConfig, simulate
from repro.core.dram.engine import SimResult
from repro.experiments import (ResultCache, SweepGrid, cell_key, run_sweep,
                               trace_for, write_artifact)
from repro.experiments import runner as runner_mod

WLS = tuple(p for p in PAPER_WORKLOADS if p.name in ("mcf", "lbm", "gamess"))
N = 200


def small_grid(**kw):
    defaults = dict(name="t", workloads=WLS,
                    policies=(Policy.BASELINE, Policy.SALP1, Policy.MASA),
                    n_requests=N, config_axes={"n_subarrays": (4, 8)})
    defaults.update(kw)
    return SweepGrid(**defaults)


class TestGridExpansion:
    def test_cross_product_count_and_order(self):
        g = small_grid()
        cells = g.expand()
        assert len(cells) == 3 * 3 * 2
        # canonical order: config point outermost, then workload, then policy
        assert cells[0].config.n_subarrays == 4 and cells[-1].config.n_subarrays == 8
        assert [c.policy for c in cells[:3]] == [Policy.BASELINE, Policy.SALP1,
                                                 Policy.MASA]
        assert cells[0].override_dict == {"n_subarrays": 4}

    def test_explicit_configs_and_where(self):
        g = SweepGrid(name="t", workloads=WLS[:1],
                      policies=(Policy.BASELINE, Policy.MASA), n_requests=N,
                      configs=({}, {"refresh": True, "dsarp": True}),
                      where=lambda pol, ov: not (pol == Policy.BASELINE
                                                 and ov.get("dsarp")))
        cells = g.expand()
        # 2 policies x 2 configs minus the pruned baseline+dsarp point
        assert len(cells) == 3
        # the shim canonicalized the boolean pair into refresh_policy
        assert not any(c.policy == Policy.BASELINE
                       and c.config.refresh_policy == "dsarp"
                       for c in cells)

    def test_axes_and_configs_mutually_exclusive(self):
        with pytest.raises(ValueError):
            SweepGrid(name="t", workloads=WLS, policies=(Policy.BASELINE,),
                      config_axes={"n_banks": (8,)}, configs=({},))

    def test_unknown_axis_rejected(self):
        with pytest.raises(ValueError):
            SweepGrid(name="t", workloads=WLS, policies=(Policy.BASELINE,),
                      config_axes={"n_banksss": (8,)})
        with pytest.raises(ValueError):
            SweepGrid(name="t", workloads=WLS, policies=(Policy.BASELINE,),
                      configs=({}, {"refres": True}))

    def test_describe_is_json_safe(self):
        import json
        json.dumps(small_grid().describe())


class TestBucketingAndCache:
    def test_one_batch_per_static_shape(self):
        calls = []
        orig = runner_mod._SIMULATE

        def counting(stacked, policy, config):
            calls.append((int(policy), config.n_banks, config.n_subarrays,
                          stacked["bank"].shape))
            return orig(stacked, policy, config)

        runner_mod._SIMULATE = counting
        try:
            sweep = run_sweep(small_grid(), ResultCache())
        finally:
            runner_mod._SIMULATE = orig
        # 3 policies x 2 geometries = 6 buckets, each one [W=3, N] batched call
        assert len(calls) == 6 == sweep.stats["sim_batches"]
        assert all(shape == (3, N) for *_, shape in calls)

    def test_cache_hits_skip_simulation(self):
        cache = ResultCache()
        s1 = run_sweep(small_grid(), cache)
        assert s1.stats["cache_hits"] == 0
        assert s1.stats["simulated_cells"] == s1.stats["n_cells"]
        s2 = run_sweep(small_grid(), cache)
        assert s2.stats["simulated_cells"] == 0
        assert s2.stats["sim_batches"] == 0
        assert s2.stats["cache_hits"] == s2.stats["n_cells"]
        for a, b in zip(s1.cells, s2.cells):
            assert a.counters == b.counters

    def test_lane_cells_count_lane_buckets(self):
        """``stats["lane_cells"]`` counts the simulated cells whose bucket
        ran the lane-batched scan: open-row buckets, refresh on or off, and
        not closed-row ones; cache hits are not simulated."""
        grid = small_grid(
            config_axes={"refresh_policy": ("none", "darp"),
                         "row_policy": ("open", "closed")})
        cache = ResultCache()
        sweep = run_sweep(grid, cache)
        per_point = len(WLS) * 3
        assert sweep.stats["simulated_cells"] == 4 * per_point
        assert sweep.stats["lane_cells"] == 2 * per_point
        again = run_sweep(small_grid(
            config_axes={"refresh_policy": ("darp", "sarp")}), cache)
        assert again.stats["simulated_cells"] == per_point   # sarp only
        assert again.stats["lane_cells"] == per_point

    def test_baseline_simulated_once_across_policy_comparisons(self):
        """The old sens_subarrays bug: baseline recomputed inside every gain()
        call, once per mechanism policy. With the cache, two back-to-back
        single-mechanism sweeps (each declaring BASELINE as its reference)
        simulate each baseline (workload, geometry) cell exactly once."""
        cache = ResultCache()
        baseline_calls = []
        orig = runner_mod._SIMULATE

        def counting(stacked, policy, config):
            if policy == Policy.BASELINE:
                baseline_calls.append(stacked["bank"].shape[0])
            return orig(stacked, policy, config)

        runner_mod._SIMULATE = counting
        try:
            for mech in (Policy.MASA, Policy.SALP1):   # old gain(pol) pattern
                run_sweep(small_grid(policies=(Policy.BASELINE, mech)), cache)
        finally:
            runner_mod._SIMULATE = orig
        # one call per geometry on the first sweep, zero on the second
        assert sum(baseline_calls) == len(WLS) * 2, baseline_calls

    def test_cell_key_is_content_addressed(self):
        cfg = SimConfig()
        tr = trace_for(WLS[0], N, cfg, seed=7)
        assert cell_key(tr, Policy.MASA, cfg) == cell_key(tr, Policy.MASA, cfg)
        assert cell_key(tr, Policy.MASA, cfg) != cell_key(tr, Policy.SALP1, cfg)
        assert (cell_key(tr, Policy.MASA, cfg)
                != cell_key(tr, Policy.MASA, SimConfig(refresh=True)))
        tr2 = dataclasses.replace(tr, row=np.ascontiguousarray(tr.row[::-1]))
        assert cell_key(tr, Policy.MASA, cfg) != cell_key(tr2, Policy.MASA, cfg)


class TestParity:
    def test_sweep_matches_per_config_simulate_bit_for_bit(self):
        grid = small_grid(policies=(Policy.BASELINE, Policy.SALP2, Policy.MASA,
                                    Policy.IDEAL))
        sweep = run_sweep(grid, ResultCache())
        fields = [f.name for f in dataclasses.fields(SimResult)]
        for cell in sweep.cells:
            tr = trace_for(cell.workload, grid.n_requests, cell.config,
                           grid.seed)
            ref = simulate(tr, cell.policy, cell.config)
            for f in fields:
                assert cell.counters[f] == int(np.asarray(getattr(ref, f))), (
                    cell.workload.name, cell.policy, f)

    def test_refresh_axis_parity(self):
        grid = SweepGrid(name="t", workloads=WLS[:2], policies=(Policy.MASA,),
                         n_requests=N,
                         configs=({"refresh": True}, {"refresh": True,
                                                      "dsarp": True}))
        sweep = run_sweep(grid, ResultCache())
        for cell in sweep.cells:
            tr = trace_for(cell.workload, N, cell.config, grid.seed)
            ref = simulate(tr, cell.policy, cell.config)
            assert cell.counters["total_cycles"] == int(ref.total_cycles)


class TestResultsApi:
    @pytest.fixture(scope="class")
    def sweep(self):
        return run_sweep(small_grid(), ResultCache())

    def test_metric_ordering_follows_grid(self, sweep):
        cyc = sweep.metric("total_cycles", policy=Policy.MASA, n_subarrays=8)
        assert cyc.shape == (len(WLS),)
        by_hand = [c.counters["total_cycles"] for w in WLS
                   for c in sweep.select(policy=Policy.MASA, workload=w.name,
                                         n_subarrays=8)]
        assert list(cyc) == by_hand

    def test_ambiguous_selection_raises(self, sweep):
        with pytest.raises(ValueError):
            sweep.metric("total_cycles", policy=Policy.MASA)  # 2 geometries

    def test_pruned_cell_raises_value_error(self):
        g = SweepGrid(name="t", workloads=WLS,
                      policies=(Policy.BASELINE, Policy.MASA), n_requests=N,
                      config_axes={"n_banks": (8, 16)},
                      where=lambda pol, ov: (pol == Policy.BASELINE
                                             or ov.get("n_banks") == 8))
        sweep = run_sweep(g, ResultCache())
        with pytest.raises(ValueError, match="where filter"):
            sweep.metric("total_cycles", policy=Policy.MASA, n_banks=16)
        assert sweep.metric("total_cycles", policy=Policy.MASA,
                            n_banks=8).shape == (len(WLS),)

    def test_speedup_and_derived_metrics(self, sweep):
        g = sweep.speedup_pct(Policy.SALP1, n_subarrays=8)
        assert (g > -1e-9).all()   # SALP-1 never slower than baseline
        ipc = sweep.metric("ipc", policy=Policy.BASELINE, n_subarrays=8)
        assert (ipc > 0).all()

    def test_artifact_schema_roundtrip(self, sweep, tmp_path):
        import json
        doc = sweep.to_json()
        assert doc["schema_version"] == "repro.sweep/v1"
        assert doc["grid"]["n_cells"] == len(doc["cells"]) == 18
        cell = doc["cells"][0]
        for k in ("workload", "policy", "overrides", "counters", "derived",
                  "cache_hit", "key"):
            assert k in cell
        path = write_artifact(str(tmp_path / "sweep.json"), doc)
        assert json.load(open(path))["grid"]["name"] == "t"


class TestMulticoreBatch:
    def test_batched_mixes_match_sequential(self):
        from repro.core.dram import generate_trace
        from repro.core.dram.multicore import (simulate_multicore,
                                               simulate_multicore_batch)
        by = {p.name: p for p in PAPER_WORKLOADS}
        mixes = [[generate_trace(by[n], 150, seed=7, row_space_offset=4096 * i)
                  for i, n in enumerate(mix)]
                 for mix in (("mcf", "lbm"), ("gups", "gamess"))]
        batch = simulate_multicore_batch(mixes, Policy.MASA)
        for mix, got in zip(mixes, batch):
            ref = simulate_multicore(mix, Policy.MASA)
            assert np.array_equal(ref.core_cycles, got.core_cycles)
            assert np.array_equal(ref.alone_cycles, got.alone_cycles)
            assert ref.weighted_speedup == pytest.approx(got.weighted_speedup)

    def test_mix_lane_cells_count_every_scan_cell(self):
        """``stats["mix_lane_cells"]`` counts the cells whose bucket runs
        the lane-major C-core scan: on the scan backend, every cell, under
        every scheduler and refresh policy; the Pallas backends run the
        fused mix kernel instead."""
        from repro.core.dram import Scheduler, workload
        from repro.core.dram.multicore import runs_mix_lanes
        from repro.experiments import MixGrid, run_mix_sweep
        grid = MixGrid(
            name="t", mixes=[(workload("mcf"), workload("lbm"))],
            policies=(Policy.BASELINE, Policy.MASA), n_requests=100,
            config_axes={"scheduler": (Scheduler.FRFCFS, Scheduler.TCM),
                         "refresh_policy": ("none", "darp")})
        sweep = run_mix_sweep(grid)
        assert sweep.stats["n_cells"] == 8
        assert sweep.stats["mix_lane_cells"] == sweep.stats["n_cells"]
        assert not runs_mix_lanes(SimConfig(backend="pallas-interpret"))
