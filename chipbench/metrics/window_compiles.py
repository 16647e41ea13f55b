"""Programs compiled or loaded from the persistent cache inside the
window: JAX's ``backend_compile_duration`` events between the window's
start and end. Every program should be in memory by then, so this reads
0."""


def read(run):
    return sum(1 for _, kind, _ in run.window_events if kind == "xla")
