"""Nanoseconds of stacking and dispatch per simulated request: the
``repro.bucket.stage`` spans (stacking a bucket's traces, the host-to-device
puts and the dispatch of the scan, ending before anything blocks) over the
window's sweeps."""
from program_spans import ns_per_req


def read(run):
    return ns_per_req(run, ("repro.bucket.stage",))
