"""Run one benchmark cell once on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

One process holds the chip. Set-up enables the program's persistent
compilation cache (kept in ``.jax_cache`` at the root of the checkout),
loads the cell's files and runs one warm-up sweep on a seed the window never
uses, which compiles the cell's programs or loads them from the cache. The
measured window then runs whole sweeps of the cell's grid back to back
through the program's entry, each with a fresh seed and a fresh result
cache, until ``--seconds`` have passed; a traffic mix that names
``shards`` shards every sweep over the cell's chips. ``--trace 1`` records
the window with JAX's profiler, its programs compiled without
per-operation trace points (``xplane.LIBTPU_TRACE_FLAGS``), and reports
the per-layer metrics instead of the end-to-end ones.

After the window closes, the device's peak memory is read, the program's
state is let go, and a sample of the window's grid cells, drawn from the
seed, is recomputed by the plain reference (``check.py``): ``correct`` is
true when every sampled cell is bit-identical. Each sweep prints a line
before the result; the compared numbers, each beside its limit, are the
last lines on standard error and the last key of the result line, which is
the last line of standard output. Without a TPU, or with fewer chips than
the cell asks for, the run exits with code 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent

#: JAX monitoring events of compilation: tracing and lowering, and XLA
#: compiling the program or loading it from the persistent cache.
COMPILE_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "lower",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
                  "/jax/core/compile/backend_compile_duration": "xla"}


class NoChip(Exception):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def find_chips(n: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's default device is {devices[0].platform}"
                     f" ({devices[0].device_kind})")
    if len(devices) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX found "
                     f"{len(devices)}")
    return devices[:n]


def load_reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_for(bench: dict, cell_name: str, trace: bool) -> list[dict]:
    """The cell's end-to-end metrics (``trace`` off) or per-layer ones."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or cell_name in m["workloads"]]


def main(argv=None, *, chips=find_chips, shrink=None) -> int:
    """Run one cell. ``chips`` finds the devices (the tests replace it to
    drive a run on the CPU); ``shrink(cell)``, where given, cuts the cell
    to a size a test can hold."""
    args = parse(argv)
    # the compilation cache lives inside the checkout, at a fixed path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(HERE)]
    import sweeps
    import xplane
    if args.trace:
        os.environ["LIBTPU_INIT_ARGS"] = xplane.with_trace_flags(
            os.environ.get("LIBTPU_INIT_ARGS"))
    cell = sweeps.load_cell(args.workload)
    if shrink is not None:
        cell = shrink(cell)
    try:
        devices = chips(cell.entry["chips"])
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    import jax
    from repro import compile_cache
    import check

    compile_cache.enable()
    events: list[tuple[float, str, float]] = []

    def on_event(event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            events.append((time.perf_counter(), COMPILE_EVENTS[event],
                           duration))

    jax.monitoring.register_event_duration_secs_listener(on_event)

    # ---- set-up: the warm-up sweep compiles or loads every program
    program = sweeps.Program(cell, devices)
    program.sweep(sweeps.sweep_seed(args.seed, -1))
    setup_s = time.perf_counter() - T_START
    setup_events = list(events)

    # ---- the measured window
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if args.trace \
        else None
    annotate = None
    if trace_dir:
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=xplane.profile_options())

        def annotate(index):
            return jax.profiler.TraceAnnotation(f"{xplane.SWEEP_SPAN} {index}")
    n_setup_events = len(events)
    try:
        records, window_s, first = sweeps.run_window(
            program, args.seed, args.seconds, annotate=annotate)
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    window_events = events[n_setup_events:]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    for rec in records:
        print(rec.line(), flush=True)
    acc = sweeps.accuracy_line(cell, first)
    if acc:
        print(acc, flush=True)
    reduced = None
    if trace_dir:
        try:
            reduced = xplane.reduce(
                xplane.find(trace_dir), len(devices),
                programs={r.index: r.chip_programs for r in records})
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    # ---- the program's state goes; the reference checks the window
    del program, first
    from repro.experiments import runner
    runner.clear_trace_cache()
    gc.collect()
    t_ref = time.perf_counter()
    readings = check.compare(cell, records, args.seed)
    t_ref = time.perf_counter() - t_ref

    run = types.SimpleNamespace(
        cell=cell, records=records, window_s=window_s, setup_s=setup_s,
        setup_events=setup_events, window_events=window_events,
        trace=reduced)
    bench = sweeps.load_json(ROOT / "BENCHMARK.json")
    metrics = {}
    for m in metrics_for(bench, cell.name, bool(args.trace)):
        value = load_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    correct = all(readings[k] <= lim for k, lim in check.LIMITS.items())
    checks = {k: {"value": readings[k], "limit": lim}
              for k, lim in check.LIMITS.items()}
    result = {"correct": correct,
              "attempted": sum(r.n_cells for r in records),
              "failed": sum(r.quarantined for r in records),
              "metrics": metrics, "device": device}
    if reduced is not None:
        result["breakdown"] = reduced["breakdown"]
    result["checks"] = checks

    if readings["first_mismatch"]:
        print(f"chipbench: first mismatch: {readings['first_mismatch']}",
              file=sys.stderr)
    print(f"chipbench: {readings['sampled_cells']} of "
          f"{readings['window_cells']} window cells compared with the "
          f"reference in {t_ref:.3f} s", file=sys.stderr)
    for k, v in checks.items():
        print(f"check {k} = {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
