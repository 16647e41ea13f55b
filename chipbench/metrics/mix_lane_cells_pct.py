"""Share of the window's mix-grid cells whose bucket ran the lane-major
C-core scan (``sweep.stats["mix_lane_cells"]`` over
``sweep.stats["n_cells"]``, summed over the window's sweeps), percent. A
window in which some sweep does not report ``mix_lane_cells`` (a program
without the counter) reads ``None``."""


def read(run):
    if not run.records or any("mix_lane_cells" not in r.stats
                              for r in run.records):
        return None
    cells = sum(r.stats["n_cells"] for r in run.records)
    if cells == 0:
        return None
    return 100.0 * sum(r.stats["mix_lane_cells"] for r in run.records) / cells
