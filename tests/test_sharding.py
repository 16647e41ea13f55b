"""Sharded multi-device sweep execution.

Locks down the sharding subsystem end to end:

* :class:`ShardPlan` semantics — mesh resolution, contiguous ragged
  partitioning, round-robin device assignment, submission order;
* the ``repro.sweep-fragment/v1`` merge contract — ordering, coverage proof,
  fingerprint isolation, determinism — against synthetic fragments (no JAX);
* **parity**: sharded ``run_sweep`` / ``run_mix_sweep`` counters are
  bit-identical to the single-device run for 1/2/3/4-shard plans, including
  ragged last shards and duplicate-key riders, and the streamed fragment
  directory re-merges to the exact single-device artifact;
* **fault x shard composition**: an injected fault strands only the poisoned
  shard's cell(s), quarantine provenance matches the unsharded run, and the
  merged fragments still account for every grid index;
* **kill-at-every-shard-boundary resume**: a journal-backed sharded run
  killed between any two shard submissions resumes with zero re-execution of
  committed cells (they stream out through the prologue fragment);
* the committed golden fragment fixtures in ``tests/data/shard_fragments/``
  merge byte-for-byte, and a live run still reproduces them;
* quarantine-aware ``benchmarks.smoke`` checks (ladder pairs skip per-CELL,
  never per-workload, under a fault drill);
* true multi-device parity in a subprocess forced to 4 host devices
  (``tests/conftest.py``) — the only place ``XLA_FLAGS`` can still take
  effect.
"""
import json
import os

import pytest

import make_golden_shard_fragments as golden
from repro.core.dram import PAPER_WORKLOADS, Policy, Scheduler, workload
from repro.experiments import (FRAGMENT_SCHEMA, FaultPlan, MixGrid,
                               PersistentResultCache, ResiliencePolicy,
                               ResultCache, ShardPlan, SweepGrid, SweepKilled,
                               install_global_cache, load_fragments,
                               merge_fragment_dir, merge_fragments,
                               read_artifact, run_mix_sweep, run_sweep,
                               write_artifact)
from repro.experiments import runner as runner_mod
from repro.experiments.sharding import fragment_fingerprint

WLS = tuple(p for p in PAPER_WORKLOADS if p.name in ("mcf", "lbm"))
N = 96

#: Retries without wall-clock cost: zero backoff, no-op sleep.
FAST = ResiliencePolicy(backoff_base_s=0.0, sleep=lambda s: None)


def tiny_grid(n_geoms=1, **kw):
    """2 workloads x 2 policies x ``n_geoms`` geometries.

    With one geometry: cells 0..3 in expand order (lbm/BASE, lbm/SALP1,
    mcf/BASE, mcf/SALP1 — PAPER_WORKLOADS lists lbm first), bucketed by
    policy into b0=[0,2], b1=[1,3] — at 2 shards the submission order is
    [0],[2],[1],[3] (bucket-major).
    """
    defaults = dict(name="t_shard", workloads=WLS,
                    policies=(Policy.BASELINE, Policy.SALP1),
                    n_requests=N,
                    config_axes={"n_subarrays": (4, 8)[:n_geoms]})
    defaults.update(kw)
    return SweepGrid(**defaults)


def mix_grid():
    return MixGrid(name="t_shard_mix",
                   mixes=[(workload("mcf"), workload("lbm")),
                          (workload("gups"), workload("stream_copy"))],
                   policies=(Policy.BASELINE, Policy.MASA),
                   n_requests=64,
                   configs=({"scheduler": Scheduler.FRFCFS},))


def cells_json(sweep):
    return [c.to_json() for c in sweep.cells]


# ---------------------------------------------------------------------------
# ShardPlan: mesh resolution, partitioning, submission order
# ---------------------------------------------------------------------------

class TestShardPlan:
    def test_partition_is_contiguous_and_ragged(self):
        assert ShardPlan(3).partition(range(7)) == [[0, 1, 2], [3, 4], [5, 6]]
        assert ShardPlan(2).partition([4, 7, 9]) == [[4, 7], [9]]
        assert ShardPlan(1).partition([1, 2, 3]) == [[1, 2, 3]]

    def test_partition_drops_empty_chunks(self):
        # more shards than cells: every cell still lands exactly once
        assert ShardPlan(4).partition([5, 9]) == [[5], [9]]

    def test_shards_for_submission_order_is_bucket_major(self):
        shards = ShardPlan(2).shards_for([[0, 2], [1, 3]])
        assert [(s.bucket, s.shard, s.cells) for s in shards] == [
            (0, 0, (0,)), (0, 1, (2,)), (1, 0, (1,)), (1, 1, (3,))]

    def test_device_assignment_round_robins(self):
        plan = ShardPlan(5)
        n = len(plan.devices)
        for s in range(5):
            assert plan.device_for(s) is plan.devices[s % n]

    def test_resolve_specs(self):
        import jax
        assert ShardPlan.resolve().n_shards == len(jax.devices())
        assert ShardPlan.resolve(3).n_shards == 3
        assert ShardPlan.resolve(None, "cpu:1").devices == (jax.devices()[0],)
        assert ShardPlan.resolve(None, "1").devices == (jax.devices()[0],)
        assert (ShardPlan.resolve(None, "cpu").devices
                == tuple(jax.devices("cpu")))

    def test_invalid_plans_raise(self):
        with pytest.raises(ValueError, match="n_shards"):
            ShardPlan(0)
        with pytest.raises(ValueError, match="selects no devices"):
            ShardPlan.resolve(None, "0")

    def test_describe_names_mesh_and_devices(self):
        d = ShardPlan(2).describe()
        assert d["n_shards"] == 2 and d["n_devices"] >= 1
        assert len(d["devices"]) == d["n_devices"]
        assert d["mesh_axes"] == {"shards": d["n_devices"]}


# ---------------------------------------------------------------------------
# Fragment merge contract (synthetic fragments, no JAX)
# ---------------------------------------------------------------------------

GRID_DOC = {"name": "g", "n_requests": 8}


def frag(seq, cells, quarantined=(), n_cells=4, fp=None, grid=None):
    grid = grid if grid is not None else GRID_DOC
    return {"schema_version": FRAGMENT_SCHEMA, "kind": None,
            "fingerprint": fp or fragment_fingerprint(grid, None, n_cells),
            "n_cells": n_cells, "grid": grid,
            "shard": {"role": "shard", "bucket": 0, "shard": seq,
                      "cells": list(cells)},
            "seq": seq,
            "cells": [{"index": i, "payload": i * 10} for i in cells],
            "quarantined": [{"index": i, "bucket": b} for i, b in quarantined]}


class TestMergeContract:
    def test_merge_orders_cells_by_index_and_strips_bookkeeping(self):
        merged = merge_fragments([frag(0, [2, 0]), frag(1, [3, 1])])
        assert merged["schema_version"] == "repro.sweep/v1"
        assert merged["cells"] == [{"payload": 0}, {"payload": 10},
                                   {"payload": 20}, {"payload": 30}]
        assert merged["stats"] == {"n_cells": 4, "merged_cells": 4,
                                   "quarantined_cells": 0, "n_fragments": 2,
                                   "n_shards": 2}
        assert merged["grid"] == GRID_DOC

    def test_quarantined_sorted_by_bucket_then_index(self):
        merged = merge_fragments([frag(0, [0], quarantined=[(3, 1)]),
                                  frag(1, [1], quarantined=[(2, 0)])])
        assert [(q["bucket"], q["index"]) for q in merged["quarantined"]] \
            == [(0, 2), (1, 3)]
        assert merged["stats"]["quarantined_cells"] == 2

    def test_duplicate_commit_raises(self):
        with pytest.raises(ValueError, match="more than one"):
            merge_fragments([frag(0, [0, 1]), frag(1, [1, 2, 3])])

    def test_commit_quarantine_conflict_raises(self):
        with pytest.raises(ValueError, match="both committed and quarantined"):
            merge_fragments([frag(0, [0, 1, 2]),
                             frag(1, [3], quarantined=[(2, 0)])])

    def test_fingerprint_mismatch_raises(self):
        other = frag(1, [2, 3], grid={"name": "OTHER", "n_requests": 8})
        with pytest.raises(ValueError, match="fingerprint mismatch"):
            merge_fragments([frag(0, [0, 1]), other])

    def test_incomplete_coverage_raises_unless_partial_allowed(self):
        with pytest.raises(ValueError, match="2/4"):
            merge_fragments([frag(0, [0, 3])])
        partial = merge_fragments([frag(0, [0, 3])], require_full=False)
        assert partial["stats"]["merged_cells"] == 2

    def test_out_of_range_index_raises(self):
        with pytest.raises(ValueError, match="out of range"):
            merge_fragments([frag(0, [0, 1, 2, 4])])

    def test_non_fragment_document_raises(self):
        bad = dict(frag(0, [0, 1, 2, 3]), schema_version="repro.sweep/v1")
        with pytest.raises(ValueError, match="not a sweep fragment"):
            merge_fragments([bad])
        with pytest.raises(ValueError, match="no fragments"):
            merge_fragments([])

    def test_merge_is_deterministic_in_input_order(self):
        frags = [frag(0, [1]), frag(1, [0], quarantined=[(3, 1)]),
                 frag(2, [2])]
        a = merge_fragments(frags, require_full=True)
        b = merge_fragments(list(reversed(frags)), require_full=True)
        assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)


# ---------------------------------------------------------------------------
# Parity: sharded execution is bit-identical to single-device
# ---------------------------------------------------------------------------

class TestShardedParity:
    def test_sweep_parity_across_shard_counts(self):
        ref = run_sweep(tiny_grid(n_geoms=2), ResultCache())
        for s in (1, 2, 3, 4):
            sharded = run_sweep(tiny_grid(n_geoms=2), ResultCache(), shards=s)
            assert cells_json(sharded) == cells_json(ref), f"shards={s}"
            assert not sharded.quarantined
            assert sharded.stats["sharding"]["n_shards"] == s
            # a shard is never larger than ceil(bucket / n_shards)
            assert sharded.stats["sim_batches"] >= ref.stats["sim_batches"]

    def test_ragged_last_shard_parity(self):
        # 3-cell buckets at 2 shards: [2, 1] ragged split in every bucket
        wls = tuple(p for p in PAPER_WORKLOADS
                    if p.name in ("mcf", "lbm", "gups"))
        ref = run_sweep(tiny_grid(workloads=wls), ResultCache())
        sharded = run_sweep(tiny_grid(workloads=wls), ResultCache(), shards=2)
        assert cells_json(sharded) == cells_json(ref)
        sizes = sorted(len(f["shard"]["cells"]) for f in sharded.fragments)
        assert sizes == [1, 1, 2, 2]

    def test_mix_sweep_parity(self):
        ref = run_mix_sweep(mix_grid())
        sharded = run_mix_sweep(mix_grid(), shards=2)
        assert cells_json(sharded) == cells_json(ref)
        assert sharded.stats["sharding"]["n_shards"] == 2
        assert [f["kind"] for f in sharded.fragments] == ["mix_sweep"] * 4

    def test_fragment_dir_remerges_to_single_device_artifact(self, tmp_path):
        ref = run_sweep(tiny_grid(n_geoms=2), ResultCache())
        d = tmp_path / "frags"
        sharded = run_sweep(tiny_grid(n_geoms=2), ResultCache(),
                            shards=2, fragment_dir=str(d))
        names = sorted(os.listdir(d))
        assert names == [f"fragment-{i:04d}.json" for i in range(len(names))]
        assert load_fragments(d) == sharded.fragments
        merged = merge_fragment_dir(d)
        assert merged["cells"] == [c.to_json() for c in ref.cells]
        assert merged["quarantined"] == []
        assert merged["stats"]["n_cells"] == ref.stats["n_cells"]
        assert merged["grid"] == tiny_grid(n_geoms=2).describe()

    def test_fragments_stay_out_of_the_sweep_artifact(self):
        sharded = run_sweep(tiny_grid(), ResultCache(), shards=2)
        doc = sharded.to_json()
        assert "fragments" not in doc
        assert doc["stats"]["sharding"]["fragment_dir"] is None
        json.dumps(doc)   # artifact stays JSON-serializable

    def test_warm_cache_streams_everything_through_prologue(self, tmp_path):
        cache = ResultCache()
        run_sweep(tiny_grid(), cache)                       # warm every key
        d = tmp_path / "frags"
        sharded = run_sweep(tiny_grid(), cache, shards=2, fragment_dir=str(d))
        assert sharded.stats["cache_hits"] == 4
        assert sharded.stats["sim_batches"] == 0
        (prologue,) = sharded.fragments
        assert prologue["shard"]["role"] == "prologue"
        assert sorted(prologue["shard"]["cells"]) == [0, 1, 2, 3]
        assert merge_fragment_dir(d)["stats"]["merged_cells"] == 4

    def test_duplicate_key_cells_ride_with_the_resolving_shard(self):
        # duplicated policy => cells 1/3 share cell 0/2's content-hash key;
        # only one representative simulates, the twin rides in its fragment
        grid = tiny_grid(policies=(Policy.BASELINE, Policy.BASELINE))
        ref = run_sweep(grid, ResultCache())
        sharded = run_sweep(grid, ResultCache(), shards=2)
        assert cells_json(sharded) == cells_json(ref)
        assert sharded.stats["n_unique"] == 2
        covered = sorted(i for f in sharded.fragments
                         for i in (c["index"] for c in f["cells"]))
        assert covered == [0, 1, 2, 3]
        assert merge_fragments(sharded.fragments)["stats"]["merged_cells"] == 4


# ---------------------------------------------------------------------------
# Fault x shard composition
# ---------------------------------------------------------------------------

class TestFaultShardComposition:
    @pytest.mark.parametrize("kind", ["raise", "oom"])
    def test_persistent_cell_fault_strands_only_its_shard(self, kind, tmp_path):
        ref = run_sweep(tiny_grid(), ResultCache())
        d = tmp_path / "frags"
        sweep = run_sweep(tiny_grid(), ResultCache(), resilience=FAST,
                          fault_plan=FaultPlan.parse(f"{kind}@c2:p"),
                          shards=2, fragment_dir=str(d))
        (q,) = sweep.quarantined
        assert (q["index"], q["bucket"]) == (2, 0)
        assert (q["workload"], q["policy"]) == ("mcf", "BASELINE")
        # every OTHER cell is bit-identical to the clean single-device run
        assert cells_json(sweep) == [c for c in cells_json(ref)
                                     if c["workload"] != "mcf"
                                     or c["policy"] != "BASELINE"]
        merged = merge_fragment_dir(d)
        assert merged["stats"]["merged_cells"] == 3
        assert merged["stats"]["quarantined_cells"] == 1
        assert merged["quarantined"] == sweep.quarantined

    def test_bucket_fault_strands_the_whole_logical_bucket(self):
        # b1 = SALP1 bucket = cells [1, 3]; both its shards inherit the
        # logical bucket id, so the bN target hits them all — same
        # provenance as the unsharded run
        sweep = run_sweep(tiny_grid(), ResultCache(), resilience=FAST,
                          fault_plan=FaultPlan.parse("raise@b1:p"), shards=2)
        assert [(q["index"], q["bucket"]) for q in sweep.quarantined] \
            == [(1, 1), (3, 1)]
        assert len(sweep.cells) + len(sweep.quarantined) \
            == sweep.stats["n_cells"]

    def test_transient_fault_recovers_within_its_shard(self):
        ref = run_sweep(tiny_grid(), ResultCache())
        plan = FaultPlan.parse("oom@b0:x1")
        sweep = run_sweep(tiny_grid(), ResultCache(), resilience=FAST,
                          fault_plan=plan, shards=2)
        assert cells_json(sweep) == cells_json(ref)
        assert not sweep.quarantined
        assert sweep.stats["retries"] >= 1
        assert plan.log and plan.log[0]["cells"] == [0]   # first shard only

    def test_delay_fault_never_quarantines(self):
        ref = run_sweep(tiny_grid(), ResultCache())
        plan = FaultPlan.parse("delay@b0:0.0")
        sweep = run_sweep(tiny_grid(), ResultCache(), resilience=FAST,
                          fault_plan=plan, shards=2)
        assert cells_json(sweep) == cells_json(ref)
        assert not sweep.quarantined and plan.summary()["fired"] == 1

    def test_mix_fault_composes_with_shards(self, tmp_path):
        d = tmp_path / "frags"
        mix = run_mix_sweep(mix_grid(), resilience=FAST,
                            fault_plan=FaultPlan.parse("raise@c1:p"),
                            shards=2, fragment_dir=str(d))
        (q,) = mix.quarantined
        assert q["index"] == 1 and q["mix"] == "mcf+lbm"
        merged = merge_fragment_dir(d)
        assert merged["kind"] == "mix_sweep"
        assert merged["stats"]["merged_cells"] == 3
        assert merged["quarantined"] == mix.quarantined


# ---------------------------------------------------------------------------
# Kill-at-every-shard-boundary crash resume
# ---------------------------------------------------------------------------

class TestKillResumeAtShardBoundaries:
    # submission order at 2 shards is [0], [2], [1], [3] (bucket-major);
    # killing at each boundary leaves exactly the preceding shards journaled
    BOUNDARIES = [("kill@c0", []), ("kill@c2", [0]),
                  ("kill@c1", [0, 2]), ("kill@c3", [0, 2, 1])]

    @pytest.mark.parametrize("kill,committed", BOUNDARIES)
    def test_resume_re_executes_zero_committed_cells(self, kill, committed,
                                                     tmp_path):
        ref = run_sweep(tiny_grid(), ResultCache())
        journal = tmp_path / "journal.jsonl"
        with pytest.raises(SweepKilled):
            run_sweep(tiny_grid(), PersistentResultCache(journal),
                      resilience=FAST, fault_plan=FaultPlan.parse(kill),
                      shards=2, fragment_dir=str(tmp_path / "frags_killed"))
        cache = PersistentResultCache(journal)     # "fresh process"
        assert cache.loaded == len(committed)
        calls = []
        orig = runner_mod._SIMULATE

        def counting(stacked, policy, config):
            calls.append(int(stacked["bank"].shape[0]))
            return orig(stacked, policy, config)

        runner_mod._SIMULATE = counting
        d = tmp_path / "frags_resume"              # clean dir per attempt
        try:
            resumed = run_sweep(tiny_grid(), cache, shards=2,
                                fragment_dir=str(d))
        finally:
            runner_mod._SIMULATE = orig
        # zero re-execution: one 1-cell shard per unjournaled cell, nothing else
        assert calls == [1] * (4 - len(committed))
        assert resumed.stats["cache_hits"] == len(committed)
        # bit-identical modulo the cache_hit flag (journal replay IS a hit)
        assert [dict(c, cache_hit=None) for c in cells_json(resumed)] \
            == [dict(c, cache_hit=None) for c in cells_json(ref)]
        # journaled cells stream out through the prologue fragment
        if committed:
            prologue = resumed.fragments[0]
            assert prologue["shard"]["role"] == "prologue"
            assert sorted(prologue["shard"]["cells"]) == sorted(committed)
        merged = merge_fragment_dir(d)
        assert [dict(c, cache_hit=None) for c in merged["cells"]] \
            == [dict(c.to_json(), cache_hit=None) for c in ref.cells]

    def test_resume_composes_with_a_fault_drill(self, tmp_path):
        journal = tmp_path / "journal.jsonl"
        with pytest.raises(SweepKilled):
            run_sweep(tiny_grid(), PersistentResultCache(journal),
                      resilience=FAST, fault_plan=FaultPlan.parse("kill@c2"),
                      shards=2)
        d = tmp_path / "frags"
        resumed = run_sweep(tiny_grid(), PersistentResultCache(journal),
                            resilience=FAST,
                            fault_plan=FaultPlan.parse("raise@c3:p"),
                            shards=2, fragment_dir=str(d))
        assert resumed.stats["cache_hits"] == 1
        assert [q["index"] for q in resumed.quarantined] == [3]
        merged = merge_fragment_dir(d)
        assert merged["stats"]["merged_cells"] == 3
        assert merged["stats"]["quarantined_cells"] == 1


# ---------------------------------------------------------------------------
# Golden shard-fragment fixtures
# ---------------------------------------------------------------------------

FIXTURE_DIR = os.path.join(os.path.dirname(__file__), "data",
                           "shard_fragments")


class TestGoldenFragments:
    def test_committed_fixtures_merge_byte_identical(self, tmp_path):
        merged = merge_fragments(load_fragments(FIXTURE_DIR))
        pinned = os.path.join(FIXTURE_DIR, "merged.json")
        assert merged == read_artifact(pinned)
        # byte-for-byte through the same writer that pinned the fixture
        out = write_artifact(str(tmp_path / "merged.json"), merged)
        with open(out, "rb") as a, open(pinned, "rb") as b:
            assert a.read() == b.read()

    def test_live_run_still_reproduces_the_fixtures(self, tmp_path):
        live = golden.run(str(tmp_path))
        committed = load_fragments(FIXTURE_DIR)

        def no_device(frags):
            # the shard's device name is the ONE host-dependent field (a
            # 4-device CI mesh places shard 1 on device 1, the fixture host
            # had only device 0); everything else must match exactly
            return [dict(f, shard=dict(f["shard"], device=None))
                    for f in frags]

        assert no_device(live.fragments) == no_device(committed), (
            "sharded execution no longer reproduces the committed fragment "
            "fixtures — if the change is intentional, regenerate them with "
            "`PYTHONPATH=src python tests/make_golden_shard_fragments.py`")


# ---------------------------------------------------------------------------
# Smoke harness: quarantine-aware ladder and conservation checks
# ---------------------------------------------------------------------------

class TestSmokeQuarantineAware:
    def test_smoke_passes_under_persistent_bucket_fault(self, monkeypatch,
                                                        tmp_path):
        """A fault drill that strands real cells must shrink the ladder
        comparison per CELL (pairs skip only against a quarantine record),
        never empty it or fake a pass — the regression this pins: a
        per-workload exclusion used to empty the ladder under raise@b0:p."""
        import benchmarks.common as common
        import benchmarks.smoke as smoke
        monkeypatch.chdir(tmp_path)   # keep the command dump out of the repo
        monkeypatch.setattr(common, "FAULT_PLAN",
                            FaultPlan.parse("raise@b0:p"))
        monkeypatch.setattr(common, "RESILIENCE", FAST)
        prev = install_global_cache(ResultCache())
        try:
            out = smoke.run()
        finally:
            install_global_cache(prev)
        assert out["ladder_ok"] and out["sched_ok"]
        assert out["fault_injection"] is True
        # b0 strands 3 sweep cells and 2 mix cells — accounted, not fatal
        assert out["quarantined"] == 5


# ---------------------------------------------------------------------------
# True multi-device parity (forced 4-device subprocess)
# ---------------------------------------------------------------------------

class TestMultiDevice:
    def test_sharded_parity_on_four_devices(self, multi_device_run):
        out = multi_device_run("""
import json
from repro.core.dram import PAPER_WORKLOADS, Policy
from repro.experiments import (ResultCache, ShardPlan, SweepGrid,
                               merge_fragment_dir, run_sweep)
import jax, tempfile, os

grid = lambda: SweepGrid(
    name="md", workloads=tuple(p for p in PAPER_WORKLOADS
                               if p.name in ("mcf", "lbm")),
    policies=(Policy.BASELINE, Policy.SALP1), n_requests=96,
    config_axes={"n_subarrays": (4, 8)})

ref = run_sweep(grid(), ResultCache())
ref_cells = [c.to_json() for c in ref.cells]
parity, devices_used, merged_ok = {}, set(), {}
for s in (1, 2, 4):
    with tempfile.TemporaryDirectory() as d:
        sw = run_sweep(grid(), ResultCache(), shards=ShardPlan(s),
                       fragment_dir=d)
        parity[str(s)] = [c.to_json() for c in sw.cells] == ref_cells
        merged_ok[str(s)] = (merge_fragment_dir(d)["cells"] == ref_cells)
        devices_used |= {f["shard"]["device"] for f in sw.fragments
                         if f["shard"]["role"] == "shard"}
print("RESULT:" + json.dumps({
    "n_devices": len(jax.devices()),
    "parity": parity, "merged_ok": merged_ok,
    "n_distinct_devices": len(devices_used)}))
""")
        assert out["n_devices"] == 4
        assert all(out["parity"].values()), out
        assert all(out["merged_ok"].values()), out
        # 2- and 4-shard plans really spread across distinct devices
        assert out["n_distinct_devices"] >= 2, out
