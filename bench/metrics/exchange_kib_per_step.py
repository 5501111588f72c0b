"""KV pools: bytes the staging exchange moved between pool shards
(``fastpath/gather_d2d_bytes``, gathers and writebacks) per window step,
in KiB.  A count computed from page sizes, not a device measurement."""


def read(ctx):
    steps = len(ctx.window.steps)
    if not steps:
        return None
    return ctx.counters.get("fastpath/gather_d2d_bytes", 0.0) / steps / 1024
