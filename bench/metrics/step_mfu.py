"""Model step: model operations of every prefill and decode token the
window's steps processed (``bench/counts.py``) over the window's seconds,
on the host's clock, times the chip's bf16 peak, in percent: the whole
step's share of the peak, idle time included."""


def read(ctx):
    peak = ctx.peaks.get("bf16_flops")
    w = ctx.window
    if not peak or not w.steps or w.seconds <= 0:
        return None
    flops = sum(ctx.counts.step_flops(ctx.cfg, s.rows) for s in w.steps)
    return flops / (w.seconds * peak) * 100.0
