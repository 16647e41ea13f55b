"""The four-chip cell on four CPU devices: a sound run is correct, its
shards ran on four distinct devices, and every fault reads not correct.

``four_devices.py`` runs the cases in one process of its own, started with
four forced host devices (JAX fixes its device count when it starts).
"""
import json
import os
import subprocess
import sys

import pytest

from conftest import HERE, ROOT

sys.path.insert(0, str(HERE))
import four_devices  # noqa: E402


@pytest.fixture(scope="module")
def cases():
    env = dict(os.environ)
    flags = [t for t in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in t]
    env["XLA_FLAGS"] = " ".join(
        flags + ["--xla_force_host_platform_device_count=4"])
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, str(HERE / "four_devices.py")],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = [json.loads(ln) for ln in p.stdout.splitlines()
           if ln.startswith("{")]
    return {c["case"]: c for c in out}


def test_sound_sharded_run_is_correct(cases):
    case = cases["sound"]
    result = case["result"]
    assert result["correct"] is True
    assert result["checks"]["mismatched_cells"] == {"value": 0, "limit": 0}
    assert result["attempted"] == 16 and result["failed"] == 0
    assert result["device"]["count"] == 4
    # the shards' results lived on four distinct devices
    assert len(case["result_devices"]) == 4
    assert all("4 shards on 4 devices" in ln for ln in case["sweep_lines"])


@pytest.mark.parametrize("kind", four_devices.SIMULATE_FAULTS
                         + four_devices.FRAGMENT_FAULTS)
def test_broken_sharded_path_is_not_correct(cases, kind):
    result = cases[kind]["result"]
    assert result["correct"] is False
    assert result["checks"]["mismatched_cells"]["value"] > 0
