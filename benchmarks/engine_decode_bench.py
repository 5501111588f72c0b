"""Decode-path benchmark: paged fast path vs the dense reference, and the
fused one-call schedule vs the split two-call schedule.

For each decode mode the same workload runs through the engine; we report

  engine/decode_step_<mode>     median wall time of one engine step (us)
  engine/h2d_per_step_<mode>    host->device bytes moved per decode step
  engine/d2h_per_step_<mode>    device->host bytes moved per decode step
  engine/compiles_<mode>        jit compilations of the decode function
  engine/telemetry_overhead_pct paged-step median with the tracer enabled
                                vs disabled (disabled tracing must stay
                                near zero cost)

``--mode fused|split|both`` (default both) additionally runs the step-
scheduling comparison: the same mixed prefill+decode workload through the
fused packer (ONE jitted call per step, chunk autotuned against a TPOT
SLO) and the split schedule (prefill-chunk call + decode call), reporting

  engine/step_warm_<sched>            median warm (compile-free) step (us)
  engine/dispatches_per_step_<sched>  jitted model calls per engine step
                                      (asserted == 1 for fused)
  engine/tpot_slo_violation_rate_<sched>  fraction of steady-state warm
                                      steps over the TPOT SLO (SLO =
                                      3x the calibrated warm median)

``--trace-out PATH`` writes the telemetry run's Chrome trace.  The dense
path re-gathers every request's pages into a host tensor each step and
re-uploads it (and downloads the whole written cache back); the paged path
ships tokens + block tables only, with compile count bounded by the shape
buckets.  ``--smoke`` shrinks the workload for CI.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from benchmarks.common import emit
from repro.core.cluster import ClusterSpec
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.serving import EngineConfig, InferenceEngine, Request


def build_model(smoke: bool):
    cfg = ModelConfig(name="bench", family="dense",
                      n_layers=2 if smoke else 4,
                      d_model=64 if smoke else 128,
                      n_heads=4 if smoke else 8,
                      n_kv_heads=2 if smoke else 4,
                      d_ff=128 if smoke else 256,
                      vocab_size=128 if smoke else 512,
                      head_dim=16, dtype="float32", remat=False,
                      scan_q_chunk=64, loss_chunk=64)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def run_mode(mode: str, cfg, params, prompts, new_tokens: int,
             telemetry: bool = False, trace_out=None, quiet: bool = False):
    cl = ClusterSpec.build([("A100", 1), ("3090", 1), ("P100", 1)])
    # pinned to the split schedule so decode_step_<mode> keeps measuring
    # the decode call itself (the fused schedule is benchmarked below)
    eng = InferenceEngine(cfg, params, cl, primary_ids=[0], pool_ids=[1, 2],
                          engine_cfg=EngineConfig(
                              max_batch=8, max_seq=128, decode_mode=mode,
                              step_mode="split", telemetry=telemetry))
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=new_tokens))
    step_times = []
    warm_times = []
    h2d0 = rec0 = 0.0
    decode_steps = 0
    recompiles = eng.registry.counter("jit/recompiles")
    while eng.queue or eng.running or eng.prefilling:
        t0 = time.perf_counter()
        eng.step()
        dt = (time.perf_counter() - t0) * 1e6
        if eng.metrics["h2d_bytes"] > h2d0:      # a decode batch ran
            step_times.append(dt)
            decode_steps += 1
            if recompiles.value == rec0:         # no jit compile this step
                warm_times.append(dt)
        h2d0, rec0 = eng.metrics["h2d_bytes"], recompiles.value
        if eng.metrics["steps"] > 2000:
            break
    # median over compile-free steps (fallback: drop the first step)
    warm = sorted(warm_times) or sorted(step_times[1:]) or step_times
    med = warm[len(warm) // 2]
    try:
        compiles = int(eng._paged_fn._cache_size()) if mode == "paged" \
            else int(eng._decode_fn._cache_size())
    except Exception:
        compiles = -1
    if trace_out:
        n_ev = eng.tracer.write_chrome(trace_out)
        emit("engine/trace_events", n_ev, trace_out)
    if quiet:
        return med
    n = max(1, decode_steps)
    emit(f"engine/decode_step_{mode}", med,
         f"decode_steps={decode_steps} finished={len(eng.finished)}")
    emit(f"engine/h2d_per_step_{mode}", eng.metrics["h2d_bytes"] / n,
         "bytes")
    emit(f"engine/d2h_per_step_{mode}", eng.metrics["d2h_bytes"] / n,
         "bytes")
    emit(f"engine/compiles_{mode}", compiles,
         f"bucket_bound={eng.bucket_count() if mode == 'paged' else 'n/a'}")
    return med


def run_sched(sched: str, cfg, params, prompts, new_tokens: int,
              slo_s: float):
    """One mixed prefill+decode workload through a step schedule; returns
    warm-step stats + dispatch counts + SLO violation rate."""
    cl = ClusterSpec.build([("A100", 1), ("3090", 1), ("P100", 1)])
    eng = InferenceEngine(cfg, params, cl, primary_ids=[0], pool_ids=[1, 2],
                          engine_cfg=EngineConfig(
                              max_batch=8, max_seq=128, step_mode=sched,
                              prefill_chunk=16,
                              tpot_slo_s=slo_s if sched == "fused" else 0.0))
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=new_tokens))
    warm_times = []
    rec0 = 0.0
    recompiles = eng.registry.counter("jit/recompiles")
    while eng.queue or eng.running or eng.prefilling:
        t0 = time.perf_counter()
        eng.step()
        dt = time.perf_counter() - t0
        if recompiles.value == rec0:         # no jit compile this step
            warm_times.append(dt)
        rec0 = recompiles.value
        if eng.metrics["steps"] > 2000:
            break
    steps = max(1.0, eng.metrics["steps"])
    warm = sorted(warm_times) or [0.0]
    # steady state = the last half of warm steps (the autotuner has had
    # its shrink/grow rounds by then)
    steady = warm_times[len(warm_times) // 2:] or [0.0]
    viol = sum(1 for t in steady if t > slo_s) / max(1, len(steady))
    return {"med_warm_s": warm[len(warm) // 2],
            "dispatches_per_step": eng.metrics["model_calls"] / steps,
            "slo_violation_rate": viol,
            "chunk_now": eng._chunk_now,
            "finished": len(eng.finished)}


def compare_schedules(cfg, params, prompts, new_tokens: int,
                      modes) -> None:
    # calibrate the TPOT SLO from a fused decode-heavy warm median: 3x
    # headroom keeps the smoke check about the autotuner, not CPU noise
    cal = run_sched("fused", cfg, params, prompts, new_tokens, slo_s=0.0)
    slo_s = 3.0 * max(cal["med_warm_s"], 1e-6)
    emit("engine/tpot_slo_s", slo_s, "3x calibrated fused warm median")
    stats = {m: run_sched(m, cfg, params, prompts, new_tokens, slo_s)
             for m in modes}
    for m, s in stats.items():
        emit(f"engine/step_warm_{m}", s["med_warm_s"] * 1e6,
             f"us, finished={s['finished']}")
        emit(f"engine/dispatches_per_step_{m}", s["dispatches_per_step"],
             "jitted model calls / engine step")
        emit(f"engine/tpot_slo_violation_rate_{m}", s["slo_violation_rate"],
             f"steady-state warm steps over SLO (chunk_now={s['chunk_now']})")
    if "fused" in stats:
        # the acceptance contract: ONE jitted call per fused step, and the
        # autotuner holds steady-state latency within the configured SLO
        assert stats["fused"]["dispatches_per_step"] == 1.0, stats["fused"]
        assert stats["fused"]["slo_violation_rate"] <= 0.5, stats["fused"]


def main(argv=()) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes / few tokens for CI")
    ap.add_argument("--mode", default="both",
                    choices=("fused", "split", "both"),
                    help="step schedules to benchmark side by side")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the telemetry run's Chrome trace here")
    args = ap.parse_args(list(argv))
    cfg, params = build_model(args.smoke)
    rng = np.random.default_rng(0)
    n_req = 4 if args.smoke else 8
    new_tokens = 4 if args.smoke else 24
    prompts = [[int(x) for x in rng.integers(0, cfg.vocab_size,
                                             rng.integers(6, 16))]
               for _ in range(n_req)]
    paged = run_mode("paged", cfg, params, prompts, new_tokens)
    dense = run_mode("dense", cfg, params, prompts, new_tokens)
    emit("engine/decode_speedup_dense_over_paged", dense / max(paged, 1e-9),
         "ratio (interpret-mode CPU; architectural, not TPU-grade)")
    # fused vs split step scheduling on a mixed prefill+decode workload:
    # longer prompts so chunked prefill actually overlaps running decode
    sched_prompts = [[int(x) for x in rng.integers(0, cfg.vocab_size,
                                                   rng.integers(8, 40))]
                     for _ in range(n_req)]
    modes = ("fused", "split") if args.mode == "both" else (args.mode,)
    compare_schedules(cfg, params, sched_prompts, new_tokens, modes)
    # telemetry overhead: a longer decode run so warm (compile-free) steps
    # dominate, tracer off vs on, same workload
    ot = new_tokens * 4
    base = run_mode("paged", cfg, params, prompts, ot, quiet=True)
    traced = run_mode("paged", cfg, params, prompts, ot,
                      telemetry=True, trace_out=args.trace_out, quiet=True)
    emit("engine/telemetry_overhead_pct",
         (traced - base) / max(base, 1e-9) * 100.0,
         "paged median warm step, tracer on vs off")


if __name__ == "__main__":
    import sys

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main(sys.argv[1:])
