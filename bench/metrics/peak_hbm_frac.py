"""Device: the process's peak device memory over the chip's limit, both
as the runtime reports them after the window."""


def read(ctx):
    peak = ctx.memory.get("peak_bytes_in_use")
    limit = ctx.memory.get("bytes_limit")
    if not peak or not limit:
        return None
    return peak / limit
