"""Seconds of set-up spent compiling, from JAX's monitoring events: tracing
and lowering to MLIR, plus XLA compiling or loading each program from the
persistent cache."""


def read(run):
    return sum(d for _, _, d in run.setup_events)
