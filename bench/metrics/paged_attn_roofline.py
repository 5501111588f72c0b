"""Kernels: the paged attention kernel's share of its roofline.  The
least time the chip needs for the window's attention (the larger of its
algorithmic operations over the bf16 peak and its least K/V, query and
output bytes over HBM bandwidth, ``bench/counts.py``) over the kernel's
device time in the trace, in percent."""

import re

# how the kernel appears in a trace of the fused step: a custom call
# named after the wrapper, "%paged_prefill_attention.5 = bf16[...]
# custom-call(...)" (the decode kernel would be "%paged_attention.N")
KERNEL = re.compile(r"^%?paged_(?:prefill_)?attention(?:\.\d+)? = ")


def read(ctx):
    tr, w = ctx.trace, ctx.window
    if tr is None or not ctx.peaks or not w.steps:
        return None
    secs, n = tr.op_seconds(lambda o: bool(KERNEL.search(o.name)))
    if n == 0 or secs <= 0:
        return None
    rows = [r for s in w.steps for r in s.rows]
    c = ctx.counts
    flops = c.attention_flops(ctx.cfg, rows)
    nbytes = c.attention_bytes(ctx.cfg, rows, 2)
    bound = max(flops / ctx.peaks["bf16_flops"],
                nbytes / ctx.peaks["hbm_bytes_s"])
    return bound / secs * 100.0
