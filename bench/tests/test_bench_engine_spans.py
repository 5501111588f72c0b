"""The engine's own spans in a profiler trace, and the metrics that read
them: a small traced engine run under ``jax.profiler.trace`` on the CPU,
reduced by ``trace_reduce``, and the three readers on hand-built
summaries and counters."""

import glob
import os
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench_cpu_sizes import ROOT  # noqa: F401  (puts src/ on the path)
from bench import trace_reduce
from bench.metrics import (migrate_ms_per_step, plan_ms_per_step,
                           queue_wait_ms_per_req)

STEP_SPANS = ("engine.admit", "engine.plan", "engine.upload",
              "engine.fused_step", "engine.readback")


def _tiny_engine():
    from repro.core.cluster import ClusterSpec
    from repro.models import transformer as T
    from repro.models.config import ModelConfig
    from repro.serving import EngineConfig, InferenceEngine
    cfg = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
                      head_dim=16, dtype="float32", remat=False,
                      scan_q_chunk=64, loss_chunk=64)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    cl = ClusterSpec.build([("A100", 1), ("3090", 1), ("P100", 1)])
    return InferenceEngine(cfg, params, cl, primary_ids=[0],
                           pool_ids=[1, 2],
                           engine_cfg=EngineConfig(max_batch=4, max_seq=64,
                                                   telemetry=True))


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(trace summary, xplane path, migrated rid, steps) of a small run:
    every step inside a ``bench.step`` annotation, as the harness does,
    and one request's head groups moved to another pool shard."""
    from repro.serving import Request
    eng = _tiny_engine()
    rng = np.random.default_rng(0)
    for i in range(3):
        eng.submit(Request(rid=i, prompt=[int(x) for x in rng.integers(
            0, 128, 5 + 3 * i)], max_new_tokens=4))
    eng.step()                  # compiles outside the trace
    d = str(tmp_path_factory.mktemp("trace"))
    moved, steps = None, 0
    # TraceMe spans only, as in the harness's traced run
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(d, profiler_options=opts):
        while eng.queue or eng.running or eng.prefilling:
            with jax.profiler.TraceAnnotation("bench.step"):
                if moved is None and eng.running:
                    r = eng.running[0]
                    dst = 1 if r.placement.get(1, 0) < eng.cfg.n_heads else 2
                    eng._apply_migration(r.rid, {dst: eng.cfg.n_heads})
                    moved = r.rid
                eng.step()
            steps += 1
            assert steps < 100
    path = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    return trace_reduce.reduce_dir(d), path[0], moved, steps


def test_engine_spans_nest_inside_bench_steps(traced):
    summary, _, _, steps = traced
    outer = summary.host["bench.step"]
    assert len(outer) == steps
    by_name = {}
    for s, e, name in summary.host_events:
        by_name.setdefault(name, []).append((s, e))
    for name in STEP_SPANS + ("engine.step", "engine.rebalance"):
        evs = by_name.get(name, [])
        assert evs, name
        assert all(any(bs <= s and e <= be for bs, be in outer)
                   for s, e in evs), name
    # the engine's step is one span per bench step, inside it
    assert len(by_name["engine.step"]) == steps
    # an idle gap inside a step reads the innermost engine span
    mids = [0.5 * (s + e) for s, e in by_name["engine.plan"]]
    assert set(summary.host_activity(mids)) == {"engine.plan"}


def test_migration_annotation_carries_its_request(traced):
    from jax.profiler import ProfileData
    _, path, moved, _ = traced
    stats = [dict(e.stats) for p in ProfileData.from_file(path).planes
             for ln in p.lines for e in ln.events
             if e.name == "engine.migrate"]
    assert moved is not None and stats
    assert stats[0]["rid"] == moved
    assert stats[0]["groups"] == 2 and stats[0]["bytes"] > 0


def test_readers_on_the_traced_run(traced):
    summary, _, _, steps = traced
    ctx = SimpleNamespace(trace=summary, window=SimpleNamespace(
        steps=[None] * steps))
    assert plan_ms_per_step.read(ctx) > 0
    assert migrate_ms_per_step.read(ctx) > 0


def _ctx(events, steps=4, counters=None):
    summary = trace_reduce.TraceSummary(
        ops=[], host={"bench.step": [(0.0, 1.0)]}, host_events=events)
    return SimpleNamespace(trace=summary, counters=counters or {},
                           window=SimpleNamespace(steps=[None] * steps))


def test_span_readers_by_hand():
    ev = [(0.0, 1.0, "bench.step"), (0.1, 0.9, "engine.step"),
          (0.1, 0.3, "engine.plan"), (0.4, 0.5, "engine.plan"),
          (0.6, 0.8, "engine.rebalance"), (0.61, 0.71, "engine.migrate")]
    ctx = _ctx(ev)
    assert plan_ms_per_step.read(ctx) == pytest.approx(300.0 / 4)
    assert migrate_ms_per_step.read(ctx) == pytest.approx(100.0 / 4)
    # a migration nested in a plan (a full shard) counts as migration
    # only; the one under rebalance was never in the plan
    nested = ev + [(0.42, 0.46, "engine.migrate")]
    nested.sort()
    ctx = _ctx(nested)
    assert plan_ms_per_step.read(ctx) == pytest.approx(260.0 / 4)
    assert migrate_ms_per_step.read(ctx) == pytest.approx(140.0 / 4)
    # engine spans but no migration: a measured zero
    ctx = _ctx(ev[:4])
    assert migrate_ms_per_step.read(ctx) == 0.0
    # a program without the engine's spans, or no trace: nothing to read
    for ctx in (_ctx([(0.0, 1.0, "bench.step"), (0.2, 0.3, "DevicePut")]),
                SimpleNamespace(trace=None,
                                window=SimpleNamespace(steps=[None]))):
        assert plan_ms_per_step.read(ctx) is None
        assert migrate_ms_per_step.read(ctx) is None
    assert plan_ms_per_step.read(_ctx(ev, steps=0)) is None


def test_queue_wait_reader_by_hand():
    read = queue_wait_ms_per_req.read
    assert read(_ctx([], counters={"queue/wait_s": 3.0,
                                   "queue/served": 4.0})) == 750.0
    # a program without the counters, or a window that served none
    assert read(_ctx([], counters={})) is None
    assert read(_ctx([], counters={"queue/wait_s": 0.0,
                                   "queue/served": 0.0})) is None
