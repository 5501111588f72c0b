"""Shared arithmetic of the metrics that read the engine's own spans: the
``engine.<name>`` profiler annotations its tracer opens on the main
thread when tracing is on, which the trace reduction keeps in
``host_events``."""


def ms_per_step(ctx, name, less=()):
    """Summed milliseconds of the main thread's ``name`` events over the
    window's steps, less the events named in ``less`` that nest inside
    them (a span's self time); None where the trace holds no ``engine.*``
    event at all (a program without the spans), 0.0 where it holds others
    only."""
    tr, steps = ctx.trace, len(ctx.window.steps)
    if tr is None or not steps:
        return None
    secs, seen, open_end = 0.0, False, float("-inf")
    # start-sorted and nested, so an event inside an open ``name`` event
    # starts before that event's end
    for s, e, n in tr.host_events:
        if n.startswith("engine."):
            seen = True
            if n == name:
                secs += e - s
                open_end = e
            elif n in less and s < open_end:
                secs -= e - s
    return secs / steps * 1e3 if seen else None
