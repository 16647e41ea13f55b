"""``benchmarks/validate.py`` is the single artifact validator (CI runs the
same code), so drift between what benchmarks emit and what CI checks is
caught here, locally, not in a workflow run.

Two layers:

* **synthetic fixtures** — minimal valid documents per suite, built in
  memory, so every corruption/CLI test runs in ANY checkout
  (``artifacts/`` is gitignored; real artifacts may be absent);
* **local artifacts** — when a previous bench run left real artifacts on
  disk they must validate too (skipped per-file when absent).
"""
import copy
import json
from pathlib import Path

import pytest

from benchmarks import validate as V

REPO = Path(__file__).resolve().parent.parent
LOCAL_ARTIFACTS = {
    "smoke": REPO / "artifacts" / "smoke.json",
    "mapping": REPO / "artifacts" / "mapping_smoke.json",
    "refresh": REPO / "artifacts" / "refresh.json",
    "kernels": REPO / "artifacts" / "kernels.json",
    "memtech": REPO / "artifacts" / "memtech.json",
}

_COMMON = {"schema_version": "repro.bench/v1", "git_sha": "f" * 40, "seed": 7}


def _refresh_pens(pol: str) -> dict:
    pens = {"all_bank": 30.0, "per_bank": 10.0, "darp": 4.0, "sarp": 1.0}
    if pol == "MASA":
        pens["dsarp"] = 5.0
    return pens


def make_doc(suite: str) -> dict:
    """A minimal document the suite's checker accepts."""
    if suite == "smoke":
        return {**_COMMON,
                "results": {"smoke": {"ladder_ok": True, "sched_ok": True}},
                "sweeps": [{"schema_version": "repro.sweep/v1"},
                           {"schema_version": "repro.sweep/v1",
                            "kind": "mix_sweep"}]}
    if suite == "mapping":
        return {**_COMMON,
                "results": {"mapping": {
                    "collapse_ok": True, "recover_ok": True,
                    "gain_contiguous_MASA": 0.0, "gain_xor_MASA": 30.0,
                    "footprint_rows": 1024}},
                "sweeps": [{"grid": {"name": "mapping",
                                     "footprint_rows": 1024},
                            "cells": [{"overrides": {"mapping": m}}
                                      for m in ("contiguous", "golden",
                                                "xor")]}]}
    if suite == "refresh":
        return {**_COMMON,
                "results": {"refresh": {
                    "ladder_ok": True,
                    "table": {gb: {pol: _refresh_pens(pol)
                                   for pol in ("BASELINE", "MASA")}
                              for gb in ("8Gb", "16Gb", "32Gb")}}},
                "sweeps": [{"grid": {"name": "refresh"}}]}
    if suite == "memtech":
        return {**_COMMON,
                "results": {"memtech": {
                    "salp_ladder_ok": True,
                    "table": {t: {"SALP1": 5.0, "SALP2": 15.0, "MASA": 30.0}
                              for t in ("ddr3", "lpddr4", "pcm_palp")},
                    "ddr3_pin": {"ok": True,
                                 "got": [15410, 266], "want": [15410, 266]},
                    "palp": {"pcm_palp": {"frfcfs_read_lat": 97.3,
                                          "palp_rp_read_lat": 93.3,
                                          "improvement_pct": 4.3}},
                    "commands": {"checker_ok": True, "n_commands": 10,
                                 "sha256": "a" * 64,
                                 "ops": {"ACT": 3, "RD": 7}},
                    "commands_lpddr4": {"checker_ok": True, "n_commands": 12,
                                        "sha256": "b" * 64,
                                        "ops": {"ACT": 3, "RD": 7,
                                                "REF": 2}}}},
                "sweeps": [{"grid": {"name": "memtech"}}]}
    if suite == "kernels":
        return {**_COMMON,
                "results": {"kernels": {
                    "kernels_ok": True,
                    "errs": {"moe_gemm": 0.0, "masa_gemm": 5e-5,
                             "ssd_scan": 1e-7, "flash_attention": 4e-7,
                             "paged_attention/shared_prefix": 2e-7,
                             "paged_attention/private": 2e-7},
                    "ladder": {"baseline": 1.0, "salp1": 1.77,
                               "salp2": 1.77, "masa": 3.53}}},
                "sweeps": []}
    raise AssertionError(suite)


# ---------------------------------------------------------------------------
# Synthetic fixtures: always run.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("suite", sorted(V.SUITES))
def test_synthetic_doc_validates(suite):
    msg = V.SUITES[suite](make_doc(suite))
    assert msg.startswith(f"{suite} ok")


@pytest.mark.parametrize("suite", sorted(V.SUITES))
def test_detect_suite(suite):
    assert V.detect_suite(make_doc(suite)) == suite


@pytest.mark.parametrize("suite", sorted(V.SUITES))
def test_common_schema_rejections(suite):
    for field, bad in (("schema_version", "repro.bench/v0"),
                       ("git_sha", "unknown"), ("seed", None)):
        broken = copy.deepcopy(make_doc(suite))
        broken[field] = bad
        with pytest.raises(V.ValidationError):
            V.SUITES[suite](broken)


def test_smoke_rejects_broken_ladder():
    doc = make_doc("smoke")
    doc["results"]["smoke"]["ladder_ok"] = False
    with pytest.raises(V.ValidationError, match="ladder_ok"):
        V.validate_smoke(doc)


def test_mapping_rejects_collapse_regression():
    doc = make_doc("mapping")
    # contiguous "gains" as much as xor => the collapse story is broken
    doc["results"]["mapping"]["gain_contiguous_MASA"] = \
        doc["results"]["mapping"]["gain_xor_MASA"]
    with pytest.raises(V.ValidationError, match="contiguous"):
        V.validate_mapping(doc)


def test_refresh_rejects_inverted_ladder():
    doc = make_doc("refresh")
    pens = doc["results"]["refresh"]["table"]["32Gb"]["MASA"]
    pens["sarp"] = pens["all_bank"] + 1.0   # sarp "worse" than all_bank
    with pytest.raises(V.ValidationError, match="ladder violated"):
        V.validate_refresh(doc)


def test_refresh_rejects_summary_side_ladder_lie():
    """ladder_ok=True with a bad table must still fail: the checker
    re-derives the ordering from the raw table."""
    doc = make_doc("refresh")
    doc["results"]["refresh"]["ladder_ok"] = True
    for per_pol in doc["results"]["refresh"]["table"].values():
        for pens in per_pol.values():
            pens["darp"] = pens["all_bank"] + 5.0
    with pytest.raises(V.ValidationError, match="ladder violated"):
        V.validate_refresh(doc)


def test_memtech_rejects_pcm_refresh_commands():
    """The acceptance gate: a PCM command stream with ANY refresh command
    means the no-refresh technology refreshed — hard fail."""
    doc = make_doc("memtech")
    doc["results"]["memtech"]["commands"]["ops"]["REF"] = 3
    with pytest.raises(V.ValidationError, match="REF commands"):
        V.validate_memtech(doc)


def test_memtech_rejects_missing_lpddr4_refresh():
    """The control: LPDDR4 under per-bank refresh must emit REFs (proves
    the zero on PCM is a property, not a dead refresh path)."""
    doc = make_doc("memtech")
    del doc["results"]["memtech"]["commands_lpddr4"]["ops"]["REF"]
    with pytest.raises(V.ValidationError, match="no REF commands"):
        V.validate_memtech(doc)


def test_memtech_rejects_palp_regression():
    doc = make_doc("memtech")
    palp = doc["results"]["memtech"]["palp"]["pcm_palp"]
    palp["palp_rp_read_lat"] = palp["frfcfs_read_lat"] + 1.0
    with pytest.raises(V.ValidationError, match="PALP_RP"):
        V.validate_memtech(doc)


def test_memtech_rejects_ddr3_pin_drift():
    """salp_ladder_ok / pin ok flags cannot lie: the validator re-checks
    got == want from the raw record."""
    doc = make_doc("memtech")
    doc["results"]["memtech"]["ddr3_pin"]["got"] = [1, 2]
    with pytest.raises(V.ValidationError, match="ddr3 pin"):
        V.validate_memtech(doc)


def test_memtech_rejects_inverted_salp_ladder():
    doc = make_doc("memtech")
    doc["results"]["memtech"]["table"]["pcm_palp"]["MASA"] = 1.0
    with pytest.raises(V.ValidationError, match="SALP ladder"):
        V.validate_memtech(doc)


def test_kernels_rejects_oracle_disagreement():
    """An error at/above ERR_TOL must fail even when the bench-side
    kernels_ok flag lies — the validator re-checks from the raw errs."""
    from benchmarks.kernel_bench import ERR_TOL

    doc = make_doc("kernels")
    doc["results"]["kernels"]["errs"]["ssd_scan"] = ERR_TOL
    with pytest.raises(V.ValidationError, match="ssd_scan"):
        V.validate_kernels(doc)


def test_kernels_rejects_missing_kernel():
    doc = make_doc("kernels")
    del doc["results"]["kernels"]["errs"]["flash_attention"]
    with pytest.raises(V.ValidationError, match="covered"):
        V.validate_kernels(doc)


def test_kernels_rejects_broken_ladder():
    doc = make_doc("kernels")
    doc["results"]["kernels"]["ladder"]["masa"] = 0.9
    with pytest.raises(V.ValidationError, match="ladder"):
        V.validate_kernels(doc)


def test_cli_exit_codes(tmp_path, capsys):
    ok = tmp_path / "refresh.json"
    ok.write_text(json.dumps(make_doc("refresh")))
    assert V.main([str(ok)]) == 0                      # auto-detected suite

    broken = make_doc("refresh")
    broken["git_sha"] = "unknown"
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(broken))
    assert V.main([str(bad)]) == 1                     # invalid artifact
    assert V.main([str(tmp_path / "missing.json")]) == 1
    nosuite = tmp_path / "nosuite.json"
    nosuite.write_text(json.dumps({"results": {}}))
    assert V.main([str(nosuite)]) == 2                 # cannot detect suite
    capsys.readouterr()


def test_cli_maps_truncated_doc_to_exit_1(tmp_path, capsys):
    """A structurally-truncated artifact (killed bench run) must produce the
    clean INVALID line + exit 1, not an uncaught KeyError traceback."""
    doc = make_doc("mapping")
    del doc["results"]["mapping"]["gain_xor_MASA"]
    p = tmp_path / "truncated.json"
    p.write_text(json.dumps(doc))
    assert V.main([str(p)]) == 1
    assert "malformed document" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# Command-trace re-validation (--check-commands): the CI path end to end.
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def command_dump(tmp_path_factory):
    """A real (tiny) command-trace dump + the matching artifact record."""
    from benchmarks.common import command_slice
    from repro.core.dram import Policy, SimConfig, generate_trace, workload

    path = tmp_path_factory.mktemp("cmds") / "commands_smoke.trace"
    rec = command_slice(generate_trace(workload("mcf"), 96, seed=7),
                        Policy.MASA, SimConfig(refresh=True), str(path))
    return path, rec


def test_check_commands_file_ok(command_dump):
    path, rec = command_dump
    doc = make_doc("smoke")
    doc["results"]["smoke"]["commands"] = rec
    assert V.validate_smoke(doc).startswith("smoke ok")
    msg = V.check_commands_file(str(path), doc, "smoke")
    assert "legal" in msg and "sha pinned" in msg


def test_check_commands_cli_exit_codes(command_dump, tmp_path, capsys):
    path, rec = command_dump
    doc = make_doc("smoke")
    doc["results"]["smoke"]["commands"] = rec
    art = tmp_path / "smoke.json"
    art.write_text(json.dumps(doc))
    assert V.main([str(art), "--suite", "smoke",
                   "--check-commands", str(path)]) == 0
    # a trace whose bytes drifted from the artifact record must fail
    doc["results"]["smoke"]["commands"] = {**rec, "sha256": "0" * 64}
    art.write_text(json.dumps(doc))
    assert V.main([str(art), "--suite", "smoke",
                   "--check-commands", str(path)]) == 1
    assert V.main([str(art), "--suite", "smoke", "--check-commands",
                   str(tmp_path / "missing.trace")]) == 1
    capsys.readouterr()


def test_check_commands_catches_timing_violation(command_dump, tmp_path):
    """An illegal stream (a command rewound below its bound) must fail the
    re-check even when its sha is not pinned — the checker itself is the
    gate, not just the byte pin."""
    import numpy as np

    from repro.core.dram import min_legal_cycles
    from repro.core.dram import state_layout as L
    from repro.core.dram.commands import CommandTrace

    path, _ = command_dump
    ct = CommandTrace.load(str(path))
    bound = min_legal_cycles(ct)
    i = int(np.flatnonzero((ct.cycle > bound) & (bound > 0)
                           & (ct.op != L.OP_REF))[0])
    ct.cycle[i] = bound[i] - 1
    bad = tmp_path / "bad.trace"
    ct.dump(str(bad))
    with pytest.raises(V.ValidationError, match="violation"):
        V.check_commands_file(str(bad))


def test_broken_commands_record_rejected():
    doc = make_doc("smoke")
    doc["results"]["smoke"]["commands"] = {"checker_ok": False,
                                           "n_commands": 5,
                                           "sha256": "ab" * 32}
    with pytest.raises(V.ValidationError, match="commands"):
        V.validate_smoke(doc)


# ---------------------------------------------------------------------------
# Local artifacts from real bench runs: validate when present.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("suite", sorted(LOCAL_ARTIFACTS))
def test_local_artifact_validates(suite):
    path = LOCAL_ARTIFACTS[suite]
    if not path.exists():
        pytest.skip(f"{path.name} not present (artifacts/ is gitignored; "
                    f"run the {suite} suite to produce it)")
    with open(path) as f:
        doc = json.load(f)
    assert V.SUITES[suite](doc).startswith(f"{suite} ok")


LOCAL_COMMAND_TRACES = {
    "smoke": REPO / "artifacts" / "commands_smoke.trace",
    "refresh": REPO / "artifacts" / "commands_refresh.trace",
}


@pytest.mark.parametrize("suite", sorted(LOCAL_COMMAND_TRACES))
def test_local_command_trace_validates(suite):
    """Re-check command dumps a local bench run left behind, exactly as the
    CI --check-commands step does (sha pin included when the JSON artifact
    is present too)."""
    trace = LOCAL_COMMAND_TRACES[suite]
    if not trace.exists():
        pytest.skip(f"{trace.name} not present (artifacts/ is gitignored; "
                    f"run the {suite} suite to produce it)")
    art = LOCAL_ARTIFACTS[suite]
    doc = json.load(open(art)) if art.exists() else None
    assert "legal" in V.check_commands_file(str(trace), doc, suite)


def _quarantined_smoke_doc():
    """A smoke doc whose first sweep stranded one of two cells (fault drill)."""
    doc = copy.deepcopy(make_doc("smoke"))
    doc["fault_injection"] = "raise@c1:p"
    doc["sweeps"][0].update({
        "grid": {"name": "t"},
        "stats": {"n_cells": 2, "quarantined_cells": 1},
        "cells": [{"workload": "lbm", "policy": "BASELINE"}],
        "quarantined": [{"index": 1, "workload": "mcf", "policy": "BASELINE",
                         "bucket": 0, "error": "RuntimeError: injected fault",
                         "attempts": 3}],
    })
    return doc


def test_quarantine_bookkeeping_must_add_up():
    doc = _quarantined_smoke_doc()
    V.SUITES["smoke"](doc)  # consistent counts pass
    broken = copy.deepcopy(doc)
    broken["sweeps"][0]["stats"]["n_cells"] = 3  # a cell silently vanished
    with pytest.raises(V.ValidationError, match="n_cells"):
        V.SUITES["smoke"](broken)
    broken = copy.deepcopy(doc)
    del broken["sweeps"][0]["quarantined"][0]["error"]
    with pytest.raises(V.ValidationError, match="record"):
        V.SUITES["smoke"](broken)


def test_expect_quarantine_mode():
    with pytest.raises(V.ValidationError, match="found none"):
        V.expect_quarantine(make_doc("smoke"))
    doc = _quarantined_smoke_doc()
    assert "quarantined" in V.expect_quarantine(doc)
    dead = copy.deepcopy(doc)
    dead["sweeps"][0]["stats"] = {"n_cells": 1, "quarantined_cells": 1}
    dead["sweeps"][0]["cells"] = []
    with pytest.raises(V.ValidationError, match="every"):
        V.expect_quarantine(dead)


def test_expect_resume_mode():
    doc = copy.deepcopy(make_doc("smoke"))
    with pytest.raises(V.ValidationError, match="journal"):
        V.expect_resume(doc)
    doc["cache_stats"] = {"journal": "j.jsonl", "loaded": 4, "hits": 4,
                          "misses": 0}
    assert "resumed" in V.expect_resume(doc)
    doc["cache_stats"]["hits"] = 0  # journal present but nothing replayed
    with pytest.raises(V.ValidationError, match="replayed"):
        V.expect_resume(doc)
