"""Model step: device-busy milliseconds per engine step, from the trace."""

from bench.metrics._trace import busy_in_steps


def read(ctx):
    r = busy_in_steps(ctx)
    if r is None or r[0] <= 0:
        return None
    return r[0] / r[2] * 1e3
