"""Device: share of the traced steps' span (first step start to last step
end) in which no operation ran on the chip."""

from bench.metrics._trace import step_spans
from bench.trace_reduce import covered


def read(ctx):
    u, spans = step_spans(ctx)
    if u is None or not spans:
        return None
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    return 1.0 - covered(u, lo, hi) / (hi - lo)
