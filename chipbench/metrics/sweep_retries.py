"""Retries and bisections of the resilience layer over the window's sweeps
(``sweep.stats``). A sound run reads 0."""


def read(run):
    return sum(r.stats.get("retries", 0) + r.stats.get("bisections", 0)
               for r in run.records)
