"""Load generator: 99th percentile of how late a request was submitted
after its due time (the harness submits at step boundaries)."""

import numpy as np


def read(ctx):
    lags = [t.submit_s - t.due_s for t in ctx.window.submitted]
    return float(np.percentile(lags, 99)) * 1e3 if lags else None
