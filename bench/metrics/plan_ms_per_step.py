"""Scheduler: host milliseconds per window step inside the engine's
``engine.plan`` span — decode-row reservation, the chunk packer, the step
plan's block tables and scatter indices, token packing — from the trace.
A migration that a full pool shard forces during the reservation nests
inside it and is left out: ``migrate_ms_per_step`` counts it."""

from bench.metrics._engine_spans import ms_per_step


def read(ctx):
    return ms_per_step(ctx, "engine.plan", less=("engine.migrate",))
