"""The command-line entry points fail loudly and place the compile cache.

* ``benchmarks.run`` runs every selected bench, then exits non-zero when
  any of them printed ``FAILED`` or ``SKIPPED``;
* ``repro.compile_cache`` leaves ``JAX_COMPILATION_CACHE_DIR`` to JAX and
  otherwise uses one fixed, gitignored directory in the checkout;
* ``chip_smoke.py`` refuses to report a run without a TPU.

No test here switches the persistent compilation cache on.
"""
import json
import sys
import types
from pathlib import Path

import jax
import pytest

from benchmarks import run as R
from repro import compile_cache

REPO = Path(__file__).resolve().parent.parent


def _fake_bench(monkeypatch, name: str, fn) -> str:
    mod = types.ModuleType(name)
    mod.run = fn
    monkeypatch.setitem(sys.modules, name, mod)
    return name


def _boom() -> dict:
    raise RuntimeError("boom")


@pytest.mark.parametrize("broken", [(), ("boom",), ("missing",),
                                    ("boom", "missing")])
def test_bench_runner_exit_code(monkeypatch, capsys, broken):
    monkeypatch.setattr(compile_cache, "enable", lambda: "")
    ran = []
    benches = {
        "ok": _fake_bench(monkeypatch, "fake_ok_bench",
                          lambda: ran.append("ok") or {"x": 1}),
        "boom": _fake_bench(monkeypatch, "fake_boom_bench", _boom),
        "missing": "fake_bench_that_does_not_exist",
    }
    keys = list(broken) + ["ok"]    # the healthy bench runs last
    monkeypatch.setattr(R, "REGISTRY", tuple(
        R.Bench(k, benches[k], ("t",), k) for k in keys))
    if broken:
        with pytest.raises(SystemExit) as ei:
            R.main(["--suite", "t", "--out", ""])
        assert ei.value.code not in (0, None)
        assert all(k in str(ei.value.code) for k in broken)
    else:
        assert R.main(["--suite", "t", "--out", ""])["results"] == {
            "ok": {"x": 1}}
    out = capsys.readouterr().out
    assert ran == ["ok"] and "ok.TOTAL" in out
    assert ("boom.FAILED" in out) == ("boom" in broken)
    assert ("missing.SKIPPED" in out) == ("missing" in broken)


def test_compile_cache_fixed_dir_is_in_checkout_and_ignored():
    assert compile_cache.CACHE_DIR == REPO / ".jax_cache"
    ignored = (REPO / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored


def test_compile_cache_leaves_env_dir_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    min_s = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        assert compile_cache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_s)


def test_chip_smoke_refuses_without_tpu(monkeypatch, capsys):
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached: chip_smoke would run for real")
    monkeypatch.syspath_prepend(str(REPO))
    import chip_smoke
    assert chip_smoke.main([]) == 1
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["ok"] is False and "no TPU found" in last["error"]
