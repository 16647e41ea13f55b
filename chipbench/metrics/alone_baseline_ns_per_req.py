"""Nanoseconds of run-alone baselines per simulated request: the
``repro.mix.alone_baseline`` spans (``multicore.alone_baseline_cycles`` on a
memo miss of ``run_mix_sweep``, its own device call included) over the
window's sweeps."""
from program_spans import ns_per_req


def read(run):
    return ns_per_req(run, ("repro.mix.alone_baseline",))
