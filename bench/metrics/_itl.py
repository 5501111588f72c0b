"""Shared arithmetic of the inter-token-gap metrics."""

import numpy as np


def gap_percentile(ctx, q):
    gaps = [g for s in ctx.window.steps for g in s.gaps_s]
    return float(np.percentile(gaps, q)) * 1e3 if gaps else None
