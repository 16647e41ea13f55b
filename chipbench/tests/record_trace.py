"""Record the small chip trace the trace-reduction test reads.

    python3 chipbench/tests/record_trace.py [--out PATH]

On the chip: two sweeps of a cut Fig. 4 grid (two workloads x BASELINE and
MASA, 64 requests each) after a warm-up, under JAX's profiler with the
harness's own sweep annotations, exactly as a ``--trace 1`` run records
its window. Writes the trace to ``--out`` (by default
``chipbench/tests/data/small.xplane.pb``) and prints its reduction.
"""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=str(HERE / "data" / "small.xplane.pb"))
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
    import xplane
    os.environ["LIBTPU_INIT_ARGS"] = xplane.with_trace_flags(
        os.environ.get("LIBTPU_INIT_ARGS"))
    import jax
    import run
    import sweeps
    devices = run.find_chips(1)
    cell = sweeps.load_cell("ddr3_1core.fig4")
    t = dict(cell.traffic, workloads=cell.traffic["workloads"][-2:],
             policies=["BASELINE", "MASA"], n_requests=64)
    program = sweeps.Program(dataclasses.replace(cell, traffic=t))
    program.sweep(sweeps.sweep_seed(1, -1))
    out = tempfile.mkdtemp(prefix="chipbench-record-")
    try:
        jax.profiler.start_trace(out, profiler_options=xplane.profile_options())
        try:
            for i in range(2):
                with jax.profiler.TraceAnnotation(f"{xplane.SWEEP_SPAN} {i}"):
                    program.sweep(sweeps.sweep_seed(1, i))
        finally:
            jax.profiler.stop_trace()
        path = xplane.find(out)
        dest = Path(args.out)
        dest.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(path, dest)
    finally:
        shutil.rmtree(out, ignore_errors=True)
    print(json.dumps({"device": devices[0].device_kind,
                      "bytes": dest.stat().st_size,
                      "reduced": xplane.reduce(str(dest), 1)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
