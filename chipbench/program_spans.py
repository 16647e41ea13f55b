"""Read the program's spans from the window's sweeps.

Every sweep of the program reports its spans in ``sweep.stats["spans"]``
as ``{name: {"n", "total_s", "self_s"}}`` (``repro.spans``). The per-layer
metrics that read them sum a span's seconds over the window's sweeps; a
window in which some sweep carries no spans (a program without them) reads
``None``.
"""
from __future__ import annotations


def _spans(run) -> list[dict] | None:
    if not run.records or any("spans" not in r.stats for r in run.records):
        return None
    return [r.stats["spans"] for r in run.records]


def ns_per_req(run, names: tuple[str, ...]) -> float | None:
    """Total nanoseconds of the spans ``names``, summed over the window's
    sweeps, per simulated request of those sweeps. A span a sweep never
    closed counts 0."""
    per_sweep = _spans(run)
    requests = sum(r.requests for r in run.records)
    if per_sweep is None or requests == 0:
        return None
    total_s = sum(s[n]["total_s"] for s in per_sweep for n in names if n in s)
    return total_s * 1e9 / requests


def self_pct(run, name: str) -> float | None:
    """Self time of span ``name`` as a share of its total, percent, over
    the window's sweeps."""
    per_sweep = _spans(run)
    if per_sweep is None:
        return None
    total = sum(s[name]["total_s"] for s in per_sweep if name in s)
    if total <= 0:
        return None
    return 100.0 * sum(s[name]["self_s"] for s in per_sweep
                       if name in s) / total
