"""Seconds from process start to the first sweep of the window (host
clock): imports, the compilation cache, loading the cell's files and the
warm-up sweep, which compiles the cell's programs or loads them."""


def read(run):
    return run.setup_s
