"""Spans (``repro.spans``): the totals' arithmetic, and the spans a sweep
reports in ``stats["spans"]`` and puts on the profiler's host plane."""
import glob
import os

import jax
import pytest

from repro import spans
from repro.core.dram import PAPER_WORKLOADS, Policy, workload
from repro.experiments import (FaultPlan, MixGrid, ResiliencePolicy,
                               ResultCache, SweepGrid, run_mix_sweep,
                               run_sweep)
from repro.experiments import runner as runner_mod

WLS = tuple(p for p in PAPER_WORKLOADS if p.name in ("mcf", "lbm"))
N = 64

#: Retries without wall-clock cost: zero backoff, no-op sleep.
FAST = ResiliencePolicy(backoff_base_s=0.0, sleep=lambda s: None)

BUCKET_SPANS = ("repro.bucket", "repro.bucket.stage",
                "repro.bucket.device_wait", "repro.bucket.readback")


def tiny_grid(**kw):
    defaults = dict(name="t_spans", workloads=WLS,
                    policies=(Policy.BASELINE, Policy.SALP1), n_requests=N,
                    config_axes={"n_subarrays": (4, 8)})
    defaults.update(kw)
    return SweepGrid(**defaults)


def tiny_mix_grid():
    return MixGrid(name="t_spans_mix",
                   mixes=[(workload("mcf"), workload("lbm")),
                          (workload("gups"), workload("stream_copy"))],
                   policies=(Policy.BASELINE, Policy.MASA), n_requests=N)


@pytest.fixture
def ticks(monkeypatch):
    """The spans' clock as a counter the test advances by hand."""
    now = [0]
    monkeypatch.setattr(spans, "clock", lambda: now[0])
    return now


class TestArithmetic:
    def test_nesting_and_self_time(self, ticks):
        with spans.recording() as rec:
            with spans.span("outer") as outer:
                ticks[0] += 5
                with spans.span("inner"):
                    ticks[0] += 3
                ticks[0] += 1
                with spans.span("inner"):
                    ticks[0] += 2
                    with spans.span("leaf"):
                        ticks[0] += 7
                ticks[0] += 4
        assert outer.elapsed_ns == 22
        assert rec.summary() == {
            "inner": {"n": 2, "total_s": 12e-9, "self_s": 5e-9},
            "leaf": {"n": 1, "total_s": 7e-9, "self_s": 7e-9},
            "outer": {"n": 1, "total_s": 22e-9, "self_s": 10e-9},
        }

    def test_outside_any_recording_nothing_is_recorded(self, ticks):
        with spans.span("alone") as s:
            ticks[0] += 4
        assert s.elapsed_s == 4e-9            # the handle still times itself
        with spans.recording() as rec:
            pass
        assert rec.summary() == {}

    def test_a_span_that_raises_is_recorded_and_not_swallowed(self, ticks):
        with spans.recording() as rec:
            with pytest.raises(ValueError):
                with spans.span("fails"):
                    ticks[0] += 2
                    raise ValueError("boom")
        assert rec.summary()["fails"] == {"n": 1, "total_s": 2e-9,
                                          "self_s": 2e-9}

    def test_a_nested_recording_is_seen_by_the_outer_one(self, ticks):
        with spans.recording() as outer_rec:
            with spans.span("caller"):
                ticks[0] += 1
                with spans.recording() as inner_rec:
                    with spans.span("work"):
                        ticks[0] += 6
        assert inner_rec.summary() == {
            "work": {"n": 1, "total_s": 6e-9, "self_s": 6e-9}}
        assert outer_rec.summary()["caller"] == {"n": 1, "total_s": 7e-9,
                                                 "self_s": 1e-9}
        assert outer_rec.summary()["work"]["n"] == 1


class TestSweepSpans:
    def test_run_sweep_reports_its_spans(self):
        runner_mod.clear_trace_cache()
        sweep = run_sweep(tiny_grid(), ResultCache())
        s, stats = sweep.stats["spans"], sweep.stats
        assert set(s) == {"repro.sweep", "repro.trace.generate",
                          "repro.cache.key", "repro.cache.lookup",
                          "repro.cache.commit", *BUCKET_SPANS}
        for name in BUCKET_SPANS + ("repro.cache.commit",):
            assert s[name]["n"] == stats["sim_batches"] == 4, name
        # two workloads x two geometries: four traces generated
        assert s["repro.trace.generate"]["n"] == len(runner_mod._TRACE_CACHE)
        assert s["repro.trace.generate"]["n"] == 4
        assert s["repro.sweep"]["n"] == 1
        assert stats["elapsed_s"] == round(s["repro.sweep"]["total_s"], 4)
        for v in s.values():
            assert 0 <= v["self_s"] <= v["total_s"]
        # the children of the sweep fit inside it
        assert s["repro.sweep"]["self_s"] < s["repro.sweep"]["total_s"]

    def test_memoized_traces_generate_nothing(self):
        runner_mod.clear_trace_cache()
        run_sweep(tiny_grid(), ResultCache())
        again = run_sweep(tiny_grid(), ResultCache())
        assert "repro.trace.generate" not in again.stats["spans"]

    def test_run_mix_sweep_reports_its_spans(self):
        runner_mod.clear_trace_cache()
        mix = run_mix_sweep(tiny_mix_grid())
        s, stats = mix.stats["spans"], mix.stats
        assert set(s) == {"repro.sweep", "repro.trace.generate",
                          "repro.mix.alone_baseline", *BUCKET_SPANS}
        for name in BUCKET_SPANS:
            assert s[name]["n"] == stats["sim_batches"] == 2, name
        # four distinct (profile, core offset) streams
        assert s["repro.trace.generate"]["n"] == len(runner_mod._TRACE_CACHE)
        assert s["repro.trace.generate"]["n"] == 4
        # one run-alone baseline per mix, shared by both policies
        assert s["repro.mix.alone_baseline"]["n"] == 2
        assert stats["elapsed_s"] == round(s["repro.sweep"]["total_s"], 4)

    def test_a_sweep_nests_under_an_open_recording(self):
        with spans.recording() as rec:
            with spans.span("caller"):
                sweep = run_sweep(tiny_grid(), ResultCache())
        outer = rec.summary()
        assert outer["repro.sweep"] == sweep.stats["spans"]["repro.sweep"]
        assert outer["caller"]["self_s"] == pytest.approx(
            outer["caller"]["total_s"] - outer["repro.sweep"]["total_s"])

    def test_backoff_spans_count_the_retries(self):
        slept = []
        policy = ResiliencePolicy(backoff_base_s=0.5, sleep=slept.append)
        sweep = run_sweep(tiny_grid(), ResultCache(), resilience=policy,
                          fault_plan=FaultPlan.parse("oom@b0:x2,raise@b2"))
        assert sweep.stats["retries"] == 3 == len(slept)
        s = sweep.stats["spans"]
        assert s["repro.resilience.backoff"]["n"] == sweep.stats["retries"]
        # a failed attempt is still an attempt: spanned, not a batch
        assert s["repro.bucket"]["n"] == sweep.stats["sim_batches"] + 3
        assert s["repro.bucket.readback"]["n"] == sweep.stats["sim_batches"]

    def test_delay_fault_is_inside_the_bucket_span(self):
        sweep = run_sweep(tiny_grid(), ResultCache(), resilience=FAST,
                          fault_plan=FaultPlan.parse("delay@b3:0.05"))
        # the sleep is the bucket span's own time, outside its children
        assert sweep.stats["spans"]["repro.bucket"]["self_s"] >= 0.05

    def test_a_sharded_run_carries_spans(self):
        sweep = run_sweep(tiny_grid(), ResultCache(), shards=2)
        s = sweep.stats["spans"]
        assert sweep.stats["sharding"]["n_shards"] == 2
        for name in BUCKET_SPANS:
            assert s[name]["n"] == sweep.stats["sim_batches"], name
        assert sweep.stats["elapsed_s"] == round(
            s["repro.sweep"]["total_s"], 4)


def test_spans_land_on_the_profilers_host_plane(tmp_path):
    from jax.profiler import ProfileData
    grid = tiny_grid(config_axes={"n_subarrays": (4,)})
    run_sweep(grid, ResultCache())          # compile outside the trace
    runner_mod.clear_trace_cache()
    with jax.profiler.trace(str(tmp_path)):
        sweep = run_sweep(grid, ResultCache())
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    names = {ev.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert {"repro.sweep", "repro.bucket.stage", "repro.bucket.readback",
            "repro.trace.generate"} <= names
    assert sweep.stats["spans"]["repro.bucket.stage"]["n"] == 2
