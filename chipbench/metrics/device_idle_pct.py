"""Share of the traced window in which no operation ran on the device,
percent: 100 * (1 - busy / window), from the profiler's trace
(``xplane.reduce``). On more than one chip, busy is the mean of the chips'
busy times, so this is the chips' mean idle share. The spans of the
program's host layers say what the host did in that time."""


def read(run):
    if run.trace is None or run.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
