"""Share of the window's simulated grid cells whose bucket ran the
lane-batched scan (``sweep.stats["lane_cells"]`` over
``sweep.stats["simulated_cells"]``, summed over the window's sweeps),
percent. A window in which some sweep does not report ``lane_cells`` (a
program without the counter) reads ``None``."""


def read(run):
    if not run.records or any("lane_cells" not in r.stats
                              for r in run.records):
        return None
    simulated = sum(r.stats["simulated_cells"] for r in run.records)
    if simulated == 0:
        return None
    return 100.0 * sum(r.stats["lane_cells"] for r in run.records) / simulated
