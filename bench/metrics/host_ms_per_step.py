"""Scheduler: host milliseconds per engine step — the harness's step span
less the device-busy time inside it (step-plan build, admission, uploads,
the arg-max read-back)."""

from bench.metrics._trace import busy_in_steps


def read(ctx):
    r = busy_in_steps(ctx)
    if r is None:
        return None
    busy, span, n = r
    return (span - busy) / n * 1e3
