"""Scheduler: mean milliseconds from a request's submit to the step that
first schedules a chunk of it (the engine queue and the list of admitted
requests waiting for prefill), over the requests first scheduled in the
window: the engine's wall-clock counters ``queue/wait_s`` and
``queue/served``."""


def read(ctx):
    n = ctx.counters.get("queue/served", 0.0)
    if n <= 0:
        return None
    return ctx.counters["queue/wait_s"] / n * 1e3
