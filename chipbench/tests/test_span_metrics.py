"""The readers of the program's spans, on synthetic sweep records."""
import types

import pytest

import run
import sweeps

SPAN_METRICS = ("trace_gen_ns_per_req", "cache_ns_per_req",
                "stage_ns_per_req", "readback_ns_per_req",
                "alone_baseline_ns_per_req", "unspanned_pct")


def span(n, total_s, self_s=None):
    return {"n": n, "total_s": total_s,
            "self_s": total_s if self_s is None else self_s}


def record(index, requests, spans=None):
    stats = {"sim_batches": 5}
    if spans is not None:
        stats["spans"] = spans
    return sweeps.SweepRecord(index=index, seed=index, wall_s=1.0,
                              n_cells=160, requests=requests, stats=stats,
                              cells={}, quarantined=0)


#: Two single-core sweeps of 1,000 and 3,000 requests.
SWEEPS = [
    record(0, 1_000, {
        "repro.sweep": span(1, 0.010, 0.0002),
        "repro.trace.generate": span(32, 0.004),
        "repro.cache.key": span(1, 0.0005),
        "repro.cache.lookup": span(1, 0.0001),
        "repro.cache.commit": span(5, 0.0002),
        "repro.bucket.stage": span(5, 0.0003),
        "repro.bucket.readback": span(5, 0.0006)}),
    record(1, 3_000, {
        "repro.sweep": span(1, 0.030, 0.0003),
        "repro.trace.generate": span(32, 0.008),
        "repro.cache.key": span(1, 0.0015),
        "repro.cache.lookup": span(1, 0.0003),
        "repro.cache.commit": span(5, 0.0004),
        "repro.bucket.stage": span(5, 0.0009),
        "repro.bucket.readback": span(5, 0.0010),
        "repro.mix.alone_baseline": span(2, 0.002)}),
]


def read(name, records):
    return run.load_reader(name)(types.SimpleNamespace(records=records))


@pytest.mark.parametrize("name, want", [
    ("trace_gen_ns_per_req", (0.004 + 0.008) * 1e9 / 4_000),
    ("cache_ns_per_req",
     (0.0005 + 0.0001 + 0.0002 + 0.0015 + 0.0003 + 0.0004) * 1e9 / 4_000),
    ("stage_ns_per_req", (0.0003 + 0.0009) * 1e9 / 4_000),
    ("readback_ns_per_req", (0.0006 + 0.0010) * 1e9 / 4_000),
    # a span one sweep never closed counts 0 there
    ("alone_baseline_ns_per_req", 0.002 * 1e9 / 4_000),
    ("unspanned_pct", 100 * (0.0002 + 0.0003) / (0.010 + 0.030)),
])
def test_sums_over_the_window(name, want):
    assert read(name, SWEEPS) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_none_without_spans(name):
    """A program whose sweeps carry no spans gives nothing to read, and
    raises nothing."""
    assert read(name, [record(0, 1_000), record(1, 1_000)]) is None
    assert read(name, [SWEEPS[0], record(1, 1_000)]) is None
    assert read(name, []) is None


def test_layers_add_up_to_the_sweep():
    """The layers' ns/req and the unspanned share add up to the sweep's
    wall time per request, in a sweep whose only children are the spans
    the readers read."""
    spans = {"repro.sweep": span(1, 0.010, 0.001),
             "repro.trace.generate": span(4, 0.004),
             "repro.cache.key": span(1, 0.001),
             "repro.bucket.stage": span(2, 0.002),
             "repro.bucket.readback": span(2, 0.002)}
    recs = [record(0, 1_000, spans)]
    layers = sum(read(n, recs) for n in SPAN_METRICS[:-1])
    unspanned = read("unspanned_pct", recs) / 100 * 0.010 * 1e9 / 1_000
    assert layers + unspanned == pytest.approx(0.010 * 1e9 / 1_000)


BENCHMARK = sweeps.load_json(sweeps.ROOT / "BENCHMARK.json")
ENTRIES = {w["name"]: w for w in BENCHMARK["workloads"]}


def _entry_is(entry):
    return sweeps.load_traffic(ENTRIES[entry]["traffic"])["entry"]


#: What a cell needs for the metric to find something to read: the result
#: cache and the lane counter exist only in ``run_sweep``, the run-alone
#: baselines only in ``run_mix_sweep``, more than one busy chip only on
#: more than one chip.
LISTS_ONLY = {
    "cache_ns_per_req": lambda w: _entry_is(w) == "run_sweep",
    "lane_cells_pct": lambda w: _entry_is(w) == "run_sweep",
    "alone_baseline_ns_per_req": lambda w: _entry_is(w) == "run_mix_sweep",
    "busy_chips": lambda w: ENTRIES[w]["chips"] > 1,
}


def test_every_reader_is_a_benchmark_metric():
    per_layer = {m["name"]: m for m in BENCHMARK["per_layer"]}
    for name in SPAN_METRICS:
        assert per_layer[name]["source"] == "program_span"
        assert per_layer[name]["moves"] == "sim_req_per_s"
    # each rule of LISTS_ONLY is about a metric that lists its cells
    assert all("workloads" in per_layer[name] for name in LISTS_ONLY)


@pytest.mark.parametrize("metric", [m for m in BENCHMARK["per_layer"]
                                    if "workloads" in m],
                         ids=lambda m: m["name"])
def test_a_metric_lists_only_cells_that_have_it(metric):
    """A metric's ``workloads`` are cells of the benchmark, each one in
    which the metric's reader finds something to read."""
    assert metric["workloads"]
    assert set(metric["workloads"]) <= set(ENTRIES)
    fits = LISTS_ONLY.get(metric["name"], lambda w: True)
    assert all(fits(w) for w in metric["workloads"]), metric["workloads"]
