"""Chips busy at once, on average over the traced window: the sum over the
device planes of each plane's busy time in the window (``xplane.reduce``'s
``busy_per_chip_s``), over the window. It runs from 0 to the number of
chips: shards that run one after another keep it at 1 or below, shards
that overlap on their chips raise it."""


def read(run):
    if run.trace is None or run.trace["window_s"] <= 0:
        return None
    return sum(run.trace["busy_per_chip_s"]) / run.trace["window_s"]
