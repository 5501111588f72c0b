"""Prefill-path benchmark: chunked paged fast path vs the dense reference.

For each prefill mode the same prompt-heavy workload runs through the
engine; we report

  engine/prefill_ttft_p50_<mode>        modeled TTFT p50 (engine clock, us)
  engine/prefill_ttft_p95_<mode>        modeled TTFT p95 (engine clock, us)
  engine/prefill_chunk_latency_<mode>   median wall time of one prefill
                                        call (us): a batched chunk on the
                                        paged path, one whole prompt on
                                        the dense path
  engine/prefill_compiles_<mode>        jit compilations of the prefill fn
  engine/prefill_h2d_per_token_<mode>   host->device bytes per prompt token
  engine/prefill_intermediate_<mode>    bytes of dense (L, 1, max_seq, ...)
                                        K/V intermediate materialized per
                                        request — 0 on the paged path
                                        (verified: store_prompt_request is
                                        never called)

The dense path runs one serial ``prefill`` per request, materializes the
max_seq-padded cache and rescatters it via ``store_prompt_request``; the
paged path writes each pow2-bucketed chunk straight into the pools, with
compile count bounded by ``prefill_bucket_count()``.  ``--smoke`` shrinks
the workload for CI.
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
             max_seq: int, chunk: int,
             telemetry: bool = False, trace_out=None, quiet: bool = False):
    cl = ClusterSpec.build([("A100", 1), ("3090", 1), ("P100", 1)])
    # pinned to the split schedule: this bench isolates the prefill call
    # itself (the fused schedule is covered by engine_decode_bench --mode)
    eng = InferenceEngine(cfg, params, cl, primary_ids=[0], pool_ids=[1, 2],
                          engine_cfg=EngineConfig(
                              max_batch=8, max_seq=max_seq,
                              prefill_mode=mode, prefill_chunk=chunk,
                              step_mode="split", telemetry=telemetry))
    dense_stores = {"n": 0}
    orig_store = eng.kv.store_prompt_request

    def counting_store(rid, k, v):
        dense_stores["n"] += 1
        return orig_store(rid, k, v)

    eng.kv.store_prompt_request = counting_store
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=new_tokens))
    prefill_times = []
    chunks0 = 0
    while eng.queue or eng.running or eng.prefilling:
        admits = len(eng.queue)
        t0 = time.perf_counter()
        eng.step()
        dt = (time.perf_counter() - t0) * 1e6
        if mode == "paged":
            if eng.metrics["prefill_chunks"] > chunks0:  # a chunk ran
                prefill_times.append(dt)
            chunks0 = eng.metrics["prefill_chunks"]
        elif admits > len(eng.queue):                    # a prefill ran
            prefill_times.append(dt)
        if eng.metrics["steps"] > 4000:
            break
    # drop the first (compile-laden) call; median of the rest
    warm = sorted(prefill_times[1:]) or prefill_times
    med = warm[len(warm) // 2]
    n_tok = sum(len(p) for p in prompts)
    # dense (L, 1, max_seq, Hkv, dh) fp32 K+V intermediate per request
    per_req = (2 * cfg.n_layers * max_seq * cfg.n_kv_heads
               * cfg.head_dim * 4)
    if mode == "paged":
        assert dense_stores["n"] == 0, \
            "paged prefill must not round-trip through store_prompt_request"
        intermediate = 0
    else:
        intermediate = dense_stores["n"] * per_req
    if trace_out:
        n_ev = eng.tracer.write_chrome(trace_out)
        emit("engine/prefill_trace_events", n_ev, trace_out)
    if quiet:
        return med
    emit(f"engine/prefill_ttft_p50_{mode}", eng.metrics["ttft_p50"] * 1e6,
         f"modeled clock us, finished={len(eng.finished)}")
    emit(f"engine/prefill_ttft_p95_{mode}", eng.metrics["ttft_p95"] * 1e6,
         "modeled clock us")
    emit(f"engine/prefill_chunk_latency_{mode}", med,
         f"us, n={len(prefill_times)} "
         + ("batched chunks" if mode == "paged" else "serial prompts"))
    emit(f"engine/prefill_compiles_{mode}",
         eng.prefill_compile_count() if mode == "paged" else -1,
         f"bucket_bound={eng.prefill_bucket_count()}"
         if mode == "paged" else "n/a (dense reference)")
    emit(f"engine/prefill_h2d_per_token_{mode}",
         eng.metrics["prefill_h2d_bytes"] / max(1, n_tok), "bytes")
    emit(f"engine/prefill_intermediate_{mode}", intermediate,
         "bytes of max_seq-padded dense K/V materialized (0 = direct-to-"
         "pool)")
    return med


def main(argv=()) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes / few tokens for CI")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="re-run the paged mode with telemetry on and "
                         "write its Chrome trace here")
    args = ap.parse_args(list(argv))
    cfg, params = build_model(args.smoke)
    rng = np.random.default_rng(0)
    n_req = 6 if args.smoke else 16
    new_tokens = 2 if args.smoke else 8
    max_seq = 128 if args.smoke else 256
    chunk = 16 if args.smoke else 32
    lo, hi = (8, 48) if args.smoke else (16, 160)
    prompts = [[int(x) for x in rng.integers(0, cfg.vocab_size,
                                             rng.integers(lo, hi))]
               for _ in range(n_req)]
    paged = run_mode("paged", cfg, params, prompts, new_tokens, max_seq,
                     chunk)
    dense = run_mode("dense", cfg, params, prompts, new_tokens, max_seq,
                     chunk)
    emit("engine/prefill_speedup_dense_over_paged",
         dense / max(paged, 1e-9),
         "per-call ratio (interpret-mode CPU; architectural, not TPU-grade)")
    if args.trace_out:
        run_mode("paged", cfg, params, prompts, new_tokens, max_seq, chunk,
                 telemetry=True, trace_out=args.trace_out, quiet=True)


if __name__ == "__main__":
    import sys

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main(sys.argv[1:])
