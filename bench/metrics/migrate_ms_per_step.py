"""KV pools: host milliseconds per window step inside the engine's
``engine.migrate`` spans — head-group chains copied between pool shards
by rebalancing or memory-exhaustion handling, dispatched as eager device
copies — from the trace; 0.0 in a window without a migration."""

from bench.metrics._engine_spans import ms_per_step


def read(ctx):
    return ms_per_step(ctx, "engine.migrate")
