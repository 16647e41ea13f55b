"""Device busy nanoseconds per simulated request in the traced window: the
union of device operation intervals over the requests of the sweeps the
trace covers. On more than one chip, busy is the mean of the chips' busy
times, so this is a chip's mean. Keyed on no program name, so it survives
a change of the scan programs."""


def read(run):
    if run.trace is None:
        return None
    requests = sum(r.requests for r in run.records)
    if requests == 0 or run.trace["busy_s"] <= 0:
        return None
    return run.trace["busy_s"] * 1e9 / requests
