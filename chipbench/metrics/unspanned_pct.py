"""Share of the sweeps' wall time that no span inside them names, percent:
the self time of the ``repro.sweep`` spans over their total, over the
window's sweeps. What the per-layer spans still cannot see."""
from program_spans import self_pct


def read(run):
    return self_pct(run, "repro.sweep")
