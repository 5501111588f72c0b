"""Scheduler: host seconds of the engine's ``admit`` span (queue, the
dispatcher's LP, page allocation) in the window over the requests it
admitted.  Needs the engine's tracer, which the traced run turns on."""


def read(ctx):
    n = sum(s.admitted for s in ctx.window.steps)
    if n <= 0 or ctx.admit_s <= 0:
        return None
    return ctx.admit_s / n * 1e3
