"""Median of every inter-token gap in the window: the time between
successive steps that extended one request's output."""

from bench.metrics._itl import gap_percentile


def read(ctx):
    return gap_percentile(ctx, 50)
