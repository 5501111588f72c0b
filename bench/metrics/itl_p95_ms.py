"""95th percentile of every inter-token gap in the window."""

from bench.metrics._itl import gap_percentile


def read(ctx):
    return gap_percentile(ctx, 95)
