"""Output tokens the window's steps emitted, requests still in flight
included, over the window's seconds."""


def read(ctx):
    w = ctx.window
    if not w.steps or w.seconds <= 0:
        return None
    return sum(s.emitted for s in w.steps) / w.seconds
