"""Without a TPU the run refuses, with exit code 2 and no result line."""
import os
import shutil
import subprocess
import sys

import run
from conftest import BENCH, ROOT


def test_no_tpu_no_result(capsys):
    rc = run.main(["--workload", "ddr3_1core.fig4", "--seed", "5",
                   "--seconds", "1", "--trace", "0"])
    assert rc == 2
    assert capsys.readouterr().out == ""


def test_only_the_benchmark_files(tmp_path):
    """In a directory with BENCHMARK.json and chipbench/ alone the run
    exits non-zero and prints no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "ddr3_1core.fig4", "--seed", "5", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
