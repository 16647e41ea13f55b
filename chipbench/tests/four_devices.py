"""Drive the four-chip cell on four CPU devices: sound, and with faults.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python3 chipbench/tests/four_devices.py

JAX fixes its device count when it starts, so ``test_sharded.py`` runs this
in a process of its own. Each case runs ``run.main`` on
``ddr3_1core.fig4_shard4``, cut to eight workloads x BASELINE/MASA (two
buckets of eight cells, two cells a shard), one sweep, every cell compared,
with the look for a chip replaced by the four CPU devices. The faults break
what the sweep produces: the batched simulation behind ``run_sweep``
(``runner._SIMULATE``, as ``per_cell.py`` breaks it) or the fragments
the shards stream (``runner.StreamingAggregator``): one shard's fragment
left out of the merge (the exchange between chips lost), two shards'
counters swapped, one fragment delivered twice. Prints one JSON line per
case: the case, its result line, and the devices the shards' results
lived on.
"""
from __future__ import annotations

import contextlib
import dataclasses
import io
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent), str(HERE.parent.parent / "src")]

CELL = "ddr3_1core.fig4_shard4"
SEED = 3_000_000_019
SIMULATE_FAULTS = ("unchanged", "half_batch", "altered")
FRAGMENT_FAULTS = ("dropped", "swapped", "duplicated")
CASES = ("sound",) + SIMULATE_FAULTS + FRAGMENT_FAULTS


def shrink(cell):
    t = dict(cell.traffic, workloads=cell.traffic["workloads"][-8:],
             policies=["BASELINE", "MASA"], n_requests=240, sample=16)
    return dataclasses.replace(cell, traffic=t)


def faulty_aggregator(kind, base):
    """The program's fragment aggregator, breaking shard 1 of every
    bucket as it is emitted."""
    class Faulty(base):
        def _emit(self, meta, cells, quarantined):
            super()._emit(meta, cells, quarantined)
            if meta["role"] != "shard" or meta["shard"] != 1:
                return
            frag = self.fragments[-1]
            if kind == "dropped":
                self.fragments.pop()
            elif kind == "duplicated":
                self.fragments.append(frag)
            else:
                first = next(f for f in self.fragments
                             if f["shard"]["role"] == "shard"
                             and f["shard"]["bucket"] == meta["bucket"]
                             and f["shard"]["shard"] == 0)
                for a, b in zip(first["cells"], frag["cells"]):
                    a["counters"], b["counters"] = b["counters"], a["counters"]
    return Faulty


def main() -> int:
    import jax
    import run
    from repro.experiments import runner
    from per_cell import single_fault

    orig_simulate, orig_agg = runner._SIMULATE, runner.StreamingAggregator
    for case in CASES:
        seen = set()
        simulate = (single_fault(case, orig_simulate)
                    if case in SIMULATE_FAULTS else orig_simulate)

        def spy(stacked, policy, config, simulate=simulate):
            res = simulate(stacked, policy, config)
            leaf = jax.tree_util.tree_leaves(res)[0]
            if isinstance(leaf, jax.Array):
                seen.update(str(d) for d in leaf.devices())
            return res

        runner._SIMULATE = spy
        runner.StreamingAggregator = (faulty_aggregator(case, orig_agg)
                                      if case in FRAGMENT_FAULTS else orig_agg)
        out = io.StringIO()
        try:
            with contextlib.redirect_stdout(out):
                rc = run.main(["--workload", CELL, "--seed", str(SEED),
                               "--seconds", "0", "--trace", "0"],
                              chips=lambda n: jax.devices("cpu")[:n],
                              shrink=shrink)
        finally:
            runner._SIMULATE = orig_simulate
            runner.StreamingAggregator = orig_agg
        lines = out.getvalue().strip().splitlines()
        print(json.dumps({"case": case, "rc": rc,
                          "sweep_lines": [ln for ln in lines
                                          if ln.startswith("# sweep")],
                          "result": json.loads(lines[-1]) if rc == 0 else None,
                          "result_devices": sorted(seen)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
