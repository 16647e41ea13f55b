"""Simulated DRAM requests per second of the window (host clock).

Every request of every sweep completed in the window, over the window's
wall seconds: trace generation, bucketing, the scans on the device,
readback and the sweep's bookkeeping all count.
"""


def read(run):
    return sum(r.requests for r in run.records) / run.window_s
