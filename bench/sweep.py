"""Find a cell's knee: the open loop at several fixed rates, one after
another in one process, each from an empty engine.

  python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
      --rates 0.5,1,2,4

For each rate it prints one JSON line: the waiting queue at the window's
start and end and its growth per second, output tokens per second, and
the TTFT and inter-token-gap percentiles of the window.  The knee is the
highest rate at which the queue does not grow across the window; a cell
fixes its rate from it in ``bench/cells/<cell>.json``.  Needs a TPU.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from bench import harness
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU found", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    sp = harness.load_spec(args.workload)
    ref = harness.reference_module(sp.config)
    cfg = harness.model_config(sp.config, ref)
    params = ref.make_params(sp.config, args.seed)
    eng = harness.build_engine(cfg, params, sp.serve, telemetry=False)
    lead_in = float(sp.cell["lead_in_s"])
    for rate in (float(r) for r in args.rates.split(",")):
        t0 = time.perf_counter()
        loop, n_buckets = harness.warmed_loop(eng, sp, args.seed, rate,
                                              lead_in + args.seconds)
        loop.run(lead_in)
        w0, n0 = loop.now(), len(loop.steps)
        rc0 = eng._c_recompiles.value
        loop.run(w0 + args.seconds)
        steps = loop.steps[n0:]
        w1 = steps[-1].end_s
        q0 = steps[0].queued
        q1 = steps[-1].queued
        due = [t for t in loop.tracks
               if lead_in <= t.due_s < lead_in + args.seconds]
        ttft = [t.first_s - t.due_s for t in due if t.first_s is not None]
        gaps = [g for s in steps for g in s.gaps_s]
        pct = (lambda xs, q: float(np.percentile(xs, q)) * 1e3 if xs
               else None)
        print(json.dumps({
            "rate": rate, "window_s": w1 - w0, "steps": len(steps),
            "queue_start": q0, "queue_end": q1,
            "queue_growth_per_s": (q1 - q0) / (w1 - w0),
            "running_mean": float(np.mean([len(s.rows) for s in steps])),
            "output_tokens_per_s": sum(s.emitted for s in steps) / (w1 - w0),
            "due": len(due), "first_token": len(ttft),
            "ttft_p50_ms": pct(ttft, 50), "ttft_p90_ms": pct(ttft, 90),
            "itl_p50_ms": pct(gaps, 50), "itl_p95_ms": pct(gaps, 95),
            "buckets": n_buckets,
            "summary": harness.window_summary(steps),
            "window_recompiles": eng._c_recompiles.value - rc0,
            "rate_seconds": time.perf_counter() - t0}), flush=True)
        harness.reset_engine(eng)
    return 0


if __name__ == "__main__":
    sys.exit(main())
