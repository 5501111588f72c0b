"""Telemetry: tracer nesting, typed metrics, Chrome export schema, the
engine's instrumented spans, the recompile tripwire, and the measured-
snapshot calibration that flips redispatch decisions."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cluster import ClusterSpec
from repro.core.dispatcher import (ATTN_SNAPSHOT_PREFIX, AttnRequest,
                                   WorkerState, apply_placement,
                                   maybe_rebalance)
from repro.core.profiler import (AttentionModel,
                                 fit_attention_model_from_tracer)
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.serving import EngineConfig, InferenceEngine, Request
from repro.telemetry import (Gauge, Histogram, MetricsRegistry, Tracer,
                             validate_chrome_trace)

CFG = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
                  head_dim=16, dtype="float32", remat=False,
                  scan_q_chunk=64, loss_chunk=64)
KEY = jax.random.PRNGKey(0)
PARAMS = T.init_params(CFG, KEY)


def make_engine(max_seq=64, telemetry=False, trace_modules=False):
    cl = ClusterSpec.build([("A100", 1), ("3090", 1), ("P100", 1)])
    return InferenceEngine(CFG, PARAMS, cl, primary_ids=[0],
                           pool_ids=[1, 2],
                           engine_cfg=EngineConfig(
                               max_batch=8, max_seq=max_seq,
                               telemetry=telemetry,
                               trace_modules=trace_modules))


def random_prompts(n, seed=0, lo=4, hi=12):
    rng = np.random.default_rng(seed)
    return [[int(x) for x in rng.integers(0, 128, rng.integers(lo, hi))]
            for _ in range(n)]


def ref_decode(prompt, n, max_seq=64):
    toks = jnp.asarray(prompt, jnp.int32)[None]
    logits, cache = T.prefill(CFG, PARAMS, {"tokens": toks},
                              max_seq=max_seq)
    out = [int(jnp.argmax(logits[0]))]
    for _ in range(n - 1):
        l2, cache = T.decode_step(CFG, PARAMS, cache,
                                  jnp.asarray([[out[-1]]], jnp.int32))
        out.append(int(jnp.argmax(l2[0])))
    return out


@pytest.fixture(scope="module")
def traced_engine():
    """One engine run with full telemetry + the eager module probe."""
    eng = make_engine(telemetry=True, trace_modules=True)
    for i, p in enumerate(random_prompts(3, seed=5)):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=4))
    eng.run_until_drained()
    assert len(eng.finished) == 3
    return eng


# ------------------------------------------------------------------ tracer
def test_span_nesting_and_ordering():
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    tr = Tracer(enabled=True, time_fn=clock)
    with tr.span("outer"):
        with tr.span("inner", args={"k": 1}):
            pass
        with tr.span("inner2"):
            pass
    spans = tr.spans()
    # children complete (and record) before the parent
    assert [s.name for s in spans] == ["inner", "inner2", "outer"]
    by = {s.name: s for s in spans}
    assert by["outer"].depth == 0
    assert by["inner"].depth == 1 and by["inner2"].depth == 1
    assert by["inner"].args == {"k": 1}
    # children lie inside the parent's window, siblings don't overlap
    assert by["outer"].ts <= by["inner"].ts
    assert by["inner"].ts + by["inner"].dur <= by["inner2"].ts
    assert (by["inner2"].ts + by["inner2"].dur
            <= by["outer"].ts + by["outer"].dur)
    assert tr.count("inner") == 1 and tr.total("outer") > 0


def test_disabled_tracer_is_shared_noop():
    tr = Tracer(enabled=False)
    a = tr.span("x")
    b = tr.span("y", args={"k": 1})
    assert a is b                       # shared singleton, no allocation
    with a:
        pass
    tr.sync(None)
    tr.add_span("z", 0.0, 1.0)
    assert len(tr) == 0 and tr.count("x") == 0


def test_profiler_annotation_only_with_a_prefix(monkeypatch):
    """Spans are profiler annotations named ``<prefix><name>`` only on a
    tracer built with a prefix (the engine's ``engine.``); the telemetry
    layer names no caller."""
    import repro.telemetry.tracer as tracer_mod
    opened = []

    class Ann:
        def __init__(self, name, **args):
            opened.append((name, args))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def set_metadata(self, **args):
            opened.append(("set", args))

    monkeypatch.setattr(tracer_mod, "TraceAnnotation", Ann)
    with Tracer(enabled=True).span("x", args={"k": 1}) as sp:
        sp.set(n=2)
    assert opened == []
    tr = Tracer(enabled=True, profiler_prefix="engine.")
    with tr.span("x", args={"k": 1}) as sp:
        sp.set(n=2)
    assert opened == [("engine.x", {"k": 1}), ("set", {"n": 2})]
    assert tr.spans("x")[0].args == {"k": 1, "n": 2}


def test_ring_buffer_totals_survive_overflow():
    tr = Tracer(enabled=True, capacity=4)
    for i in range(10):
        tr.add_span("s", float(i), 1.0)
    assert len(tr) == 4                 # ring holds the most recent
    assert tr.count("s") == 10          # aggregates survive overflow
    assert tr.total("s") == pytest.approx(10.0)


# ----------------------------------------------------------------- metrics
def test_histogram_percentiles_match_numpy_oracle():
    rng = np.random.default_rng(0)
    vals = rng.lognormal(size=500)
    h = Histogram("lat")
    for v in vals:
        h.observe(float(v))
    for q in (50, 95, 99):
        assert h.percentile(q) == pytest.approx(np.percentile(vals, q))
    s = h.summary()
    assert s["count"] == 500
    assert s["min"] == pytest.approx(vals.min())
    assert s["max"] == pytest.approx(vals.max())
    assert s["p95"] == pytest.approx(np.percentile(vals, 95))


def test_gauge_ewma_smoothing():
    g = Gauge("x")
    assert g.ewma(1.0) == pytest.approx(1.0)     # first sample adopted
    assert g.ewma(2.0) == pytest.approx(1.25)    # 0.75*1 + 0.25*2
    fn_backed = Gauge("y", fn=lambda: 3.0)
    assert fn_backed.value == 3.0
    with pytest.raises(ValueError):
        fn_backed.ewma(1.0)


def test_registry_type_clash_and_prefix_snapshot():
    reg = MetricsRegistry()
    reg.counter("a/n")
    reg.gauge("b/g").set(2.0)
    reg.histogram("b/h").observe(1.0)
    with pytest.raises(TypeError):
        reg.gauge("a/n")
    snap = reg.snapshot("b/")
    assert "a/n" not in snap
    assert snap["b/g"] == 2.0 and snap["b/h/p50"] == 1.0


# ----------------------------------------------------------- chrome export
def test_chrome_export_schema(traced_engine):
    obj = traced_engine.tracer.export_chrome()
    n = validate_chrome_trace(obj)
    assert n > 0
    for ev in obj["traceEvents"]:
        for key in ("name", "ph", "ts", "pid", "tid"):
            assert key in ev, ev


def test_validator_rejects_malformed_traces():
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": []})
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"ph": "X", "ts": 0}]})
    with pytest.raises(ValueError):
        validate_chrome_trace({})


# ------------------------------------------------------------ engine spans
def test_engine_trace_has_nested_module_spans(traced_engine):
    tr = traced_engine.tracer
    names = {s.name for s in tr.spans()}
    assert {"step", "admit", "lp", "alloc", "plan", "upload", "fused_step",
            "readback", "rebalance", "attention", "mlp"} <= names
    assert all(s.depth == 0 for s in tr.spans("step"))
    for name in ("admit", "plan", "upload", "fused_step", "readback",
                 "rebalance"):
        assert tr.spans(name)
        assert all(s.depth == 1 for s in tr.spans(name)), name
    # admission's LP and page allocation name the request they serve
    for name in ("lp", "alloc"):
        assert len(tr.spans(name)) == 3
        assert all(s.depth == 2 and s.args["rid"] in (0, 1, 2)
                   for s in tr.spans(name)), name
    # module spans nest below the fused span they ran in
    assert all(s.depth >= 2 for s in tr.spans("attention", track="main"))
    # attention spans carry the (h, g) annotation the profiler fit reads
    assert all("heads" in s.args for s in tr.spans("attention"))
    # the live engine records no modeled-clock spans
    assert not tr.spans(track="sim")
    assert not names & {"fused/decode", "fused/prefill", "attention_model",
                        "dense_model"}


def test_profiler_fit_consumes_engine_spans(traced_engine):
    out = fit_attention_model_from_tracer(traced_engine.tracer)
    assert out is not None
    model, _ = out
    assert isinstance(model, AttentionModel)


def test_traced_engine_tokens_exact():
    """The eager instrumented twins produce the same tokens as the
    reference prefill+decode (the probe must not perturb serving)."""
    eng = make_engine(telemetry=True, trace_modules=True)
    prompts = random_prompts(2, seed=11)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=5))
    eng.run_until_drained()
    assert len(eng.finished) == 2
    for r in eng.finished:
        assert r.output == ref_decode(prompts[r.rid], 5)


# --------------------------------------------------------------- snapshot
def test_snapshot_exposes_latency_and_occupancy(traced_engine):
    snap = traced_engine.snapshot()
    assert snap["ttft_s/p95"] >= snap["ttft_s/p50"] > 0
    assert snap["tpot_s/count"] > 0
    # the step's wall time is the ``step`` span's, not a histogram
    assert not any(k.startswith("step_latency_s") for k in snap)
    # every request waited once from submit to its first chunk
    assert snap["queue/served"] == 3
    assert 0 < snap["queue/wait_s"] == pytest.approx(
        sum(r.queue_wait_s for r in traced_engine.finished))
    assert "kv/occupancy" in snap
    assert any(k.startswith("kv/device/") and k.endswith("used_bytes")
               for k in snap)
    assert "jit/recompiles" in snap
    # the module probe attributed measured attention time per device
    assert any(k.startswith(ATTN_SNAPSHOT_PREFIX) for k in snap)


def test_metrics_view_backcompat(traced_engine):
    m = traced_engine.metrics
    assert m["steps"] > 0
    assert m["prefill_chunks"] > 0
    assert m["ttft_p95"] >= m["ttft_p50"] > 0
    assert set(m) >= {"h2d_bytes", "d2h_bytes", "evictions",
                      "migrated_bytes", "redispatches"}
    assert dict(m)                       # Mapping protocol round-trips
    with pytest.raises(TypeError):
        m["steps"] = 5                   # read-only view


def test_recompile_counter_bounded_by_buckets():
    """50-step trickle-arrival run: the jit-recompile counter stays within
    the pow2 bucket bound (the shape-bucketing contract, now measured by
    the registry instead of inferred from cache sizes)."""
    eng = make_engine(telemetry=True)
    rng = np.random.default_rng(7)
    rid = 0
    for step in range(50):
        if rid < 12 and step % 4 == 0:
            for _ in range(int(rng.integers(1, 3))):
                eng.submit(Request(
                    rid=rid,
                    prompt=[int(x) for x in
                            rng.integers(0, 128, rng.integers(4, 10))],
                    max_new_tokens=int(rng.integers(3, 7))))
                rid += 1
        eng.step()
    rec = eng.registry.counter("jit/recompiles").value
    # the fused default dispatches ONE jitted fn, so its bucket universe
    # is the whole recompile bound
    assert 0 < rec <= eng.fused_bucket_count()
    assert eng.fused_compile_count() <= eng.fused_bucket_count()
    assert eng.decode_compile_count() <= eng.bucket_count()
    assert eng.prefill_compile_count() <= eng.prefill_bucket_count()


# ------------------------------------------------- measured redispatching
def _worker(did):
    return WorkerState(did, AttentionModel(a=1e-4, b=0.0, c=0.0), None,
                       capacity_bytes=1e12)


def test_redispatch_flips_on_measured_snapshot():
    """Balanced placement, identical analytic models: no rebalance.  A
    snapshot showing one device 5x slower than modeled recalibrates the
    workers and flips the decision, shifting heads off the slow device."""
    workers = [_worker(0), _worker(1)]
    ar = AttnRequest(rid=0, ctx_len=8, n_heads=8, group_ratio=2,
                     head_dim=16, dtype_bytes=4)
    apply_placement(workers, [ar], {0: {0: 4, 1: 4}})
    assert maybe_rebalance(workers, [ar], theta=0.5) is None
    f0 = workers[0].f_time(ar.group_ratio, ar.head_dim, ar.dtype_bytes)
    snap = {f"{ATTN_SNAPSHOT_PREFIX}0": 5.0 * f0}
    d = maybe_rebalance(workers, [ar], theta=0.5, snapshot=snap)
    assert d is not None
    assert d.new_placement.get(0, 0) < 4
    assert workers[0].calib > workers[1].calib


def _migrating_run(eng, prompts, annotate=None):
    """Submit ``prompts``, step the engine, and move the first running
    request's every head group to a pool device that does not hold them
    all at the second step boundary; returns the migrated rid.
    ``annotate`` wraps each step (e.g. a profiler annotation)."""
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=6))
    wrap = annotate or (lambda: contextlib.nullcontext())
    moved, n = None, 0
    while eng.queue or eng.running or eng.prefilling:
        with wrap():
            if n == 2 and eng.running:
                r = eng.running[0]
                dst = 1 if r.placement.get(1, 0) < CFG.n_heads else 2
                eng._apply_migration(r.rid, {dst: CFG.n_heads})
                moved = r.rid
            eng.step()
        n += 1
        assert n < 200
    return moved


def test_disabled_tracer_records_nothing_and_allocates_no_span(
        monkeypatch):
    """With telemetry off every span call site of the engine — admission,
    plan, upload, the jitted call, read-back, rebalance, migration — gets
    the shared no-op span: no span object, no profiler annotation, nothing
    recorded.  The wall-clock counters still count."""
    import repro.telemetry.tracer as tracer_mod

    def forbidden(*a, **k):
        raise AssertionError("span object allocated with tracing off")

    monkeypatch.setattr(tracer_mod, "_SpanCtx", forbidden)
    monkeypatch.setattr(tracer_mod, "TraceAnnotation", forbidden)
    eng = make_engine()
    moved = _migrating_run(eng, random_prompts(3, seed=2))
    assert moved is not None and len(eng.finished) == 3
    assert len(eng.tracer) == 0 and not eng.tracer.totals
    snap = eng.snapshot()
    assert snap["queue/served"] == 3 and snap["queue/wait_s"] > 0
    assert snap["migrated_bytes"] > 0


def test_queue_wait_stamped_once_across_preemption_replay():
    """``queue/wait_s`` runs from submit to the first step that schedules
    a chunk of the request; a preempted request replays its prompt but
    keeps its first wait, and counts once."""
    eng = make_engine()
    prompt = random_prompts(1, seed=4)[0]
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=6))
    eng.step()
    (r,) = eng.running + eng.prefilling
    wait = r.queue_wait_s
    assert wait is not None and wait > 0
    snap = eng.snapshot()
    assert snap["queue/served"] == 1
    assert snap["queue/wait_s"] == pytest.approx(wait)
    eng.step()
    eng._preempt(r)
    assert r in eng.queue and r.prefill_pos == 0
    eng.run_until_drained()
    assert eng.finished == [r] and r.queue_wait_s == wait
    snap = eng.snapshot()
    assert snap["queue/served"] == 1
    assert snap["queue/wait_s"] == pytest.approx(wait)
    assert snap["evictions"] == 1
    assert r.output == ref_decode(prompt, 6)


# ------------------------------------------------------------- sim tracer
def test_sim_emits_module_spans_for_fig13():
    from repro.core.cluster import ClusterSpec as CS
    from repro.core.costmodel import LLAMA_70B
    from repro.sim import HetisSystem, make_trace, simulate

    cl = CS.paper_testbed()
    trace = make_trace("sharegpt", 1.0, 5.0, seed=3)
    res = simulate(HetisSystem(LLAMA_70B, cl), trace, "sharegpt", 1.0,
                   max_sim_seconds=30.0)
    spans = res.tracer.spans("attention", track="sim")
    assert spans and all("rids" in s.args for s in spans)
    assert res.p95_module("attention") > 0
    assert res.p95_module("mlp") > 0
