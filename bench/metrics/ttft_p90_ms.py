"""90th percentile of time to first token over every request due in the
window, from its due time to the end of the step that first returned a
token for it.  A request still without a token when the drain ends counts
with its wait so far."""

import numpy as np


def read(ctx):
    w = ctx.window
    if not w.due:
        return None
    end = max(s.end_s for s in ctx.window.steps) if w.steps else w.end_s
    waits = [(t.first_s if t.first_s is not None else max(end, t.due_s))
             - t.due_s for t in w.due]
    return float(np.percentile(waits, 90)) * 1e3
