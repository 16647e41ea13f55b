"""Nanoseconds of readback per simulated request: the
``repro.bucket.readback`` spans (device-to-host copies of a bucket's
counters, after ``repro.bucket.device_wait`` has waited for the scan, and
their unpacking into per-cell results) over the window's sweeps."""
from program_spans import ns_per_req


def read(run):
    return ns_per_req(run, ("repro.bucket.readback",))
