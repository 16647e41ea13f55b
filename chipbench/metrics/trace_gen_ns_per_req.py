"""Nanoseconds of host trace generation per simulated request: the
``repro.trace.generate`` spans (``runner.trace_for`` on a memo miss, around
``generate_trace``) over the window's sweeps, per simulated request."""
from program_spans import ns_per_req


def read(run):
    return ns_per_req(run, ("repro.trace.generate",))
