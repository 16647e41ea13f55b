"""Nanoseconds in the result cache per simulated request: the
``repro.cache.key`` (content hash of every cell), ``repro.cache.lookup``
(``cache.get`` and bucketing) and ``repro.cache.commit`` (``cache.put`` and
``flush``) spans of ``run_sweep`` over the window's sweeps. Mix sweeps have
no cache."""
from program_spans import ns_per_req


def read(run):
    return ns_per_req(run, ("repro.cache.key", "repro.cache.lookup",
                            "repro.cache.commit"))
