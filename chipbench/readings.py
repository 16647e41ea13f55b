"""Read the compared number's two readings for one cell, in one process.

    python3 chipbench/readings.py --workload <cell> --seed <n> \
        [--seeds 12] [--control-seeds 3]

The lower reading: the program at the cell's own size, one sweep on each of
``--seeds`` seeds (``n``, ``n + 1``, ...) after one warm-up, each compared
with the reference as a run compares its window. The upper reading: the
control (``check.control``, the reference without tFAW) in the program's
place on ``--control-seeds`` of those seeds; both come from the reference
the cell's configuration names (``check.reference_for``). A sharded cell
compares its sweeps' merged fragments, as a run does. Prints one line per
seed and a JSON summary last. Needs the chip, as a run does; the
benchmark's runs never call this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(HERE)]
    import check
    import run
    import sweeps
    cell = sweeps.load_cell(args.workload)
    try:
        devices = run.find_chips(cell.entry["chips"])
    except run.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    from repro import compile_cache
    compile_cache.enable()
    program = sweeps.Program(cell, devices)
    program.sweep(sweeps.sweep_seed(args.seed, -1))
    lower, upper = [], []
    for k in range(args.seeds):
        seed = args.seed + k
        records, _, _ = sweeps.run_window(program, seed, 0)
        got = check.compare(cell, records, seed)
        lower.append(got["mismatched_cells"])
        line = (f"# seed {seed}: program mismatched_cells "
                f"{got['mismatched_cells']} of {got['sampled_cells']}")
        if k < args.control_seeds:
            ctl = check.control(cell, records, seed)
            upper.append(ctl["mismatched_cells"])
            line += (f"; control mismatched_cells "
                     f"{ctl['mismatched_cells']} of {ctl['sampled_cells']}")
        print(line, flush=True)
    print(json.dumps({"workload": cell.name, "mismatched_cells": {
        "program": lower, "control": upper,
        "lower": max(lower), "upper": min(upper) if upper else None}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
