"""Shared trace arithmetic of the per-layer metrics: each ``bench.step``
span of the harness and the device-busy time inside it."""

from bench.trace_reduce import covered


def step_spans(ctx):
    tr = ctx.trace
    if tr is None or not tr.ops:
        return None, []
    return tr.busy_union(0), tr.host.get("bench.step", [])


def busy_in_steps(ctx):
    """(device seconds inside step spans, host seconds of those spans,
    number of spans), or None without a device trace."""
    u, spans = step_spans(ctx)
    if u is None or not spans:
        return None
    busy = sum(covered(u, s, e) for s, e in spans)
    return busy, sum(e - s for s, e in spans), len(spans)
