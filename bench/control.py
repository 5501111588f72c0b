"""Readings that set a cell's ``gap_limit``: the program's widest logit
gap and the fp8 control's, on several seeds, in one process.

  python3 bench/control.py --workload <cell> --seconds <s> \
      --seeds 11,12,13

For each seed it serves the cell's own traffic at the cell's rate through
the engine, exactly as a run does (weights from the seed, dry run, warm
buckets, lead-in, a window of ``--seconds``), draws the same sample of
served requests, and prints one JSON line with the program's widest gap
(``bench/oracle.py: widest_gap``) and the control's (``control_gap``: the
reference in fp8, the precision below the configuration's bfloat16), each
with the verdict ``harness.judge`` gives it under the cell's limits, as a
run would.  The last line gives the largest program reading (the lower end
of the limit) and the smallest control reading (the upper end).  One
engine serves every seed, so its programs compile once.  Needs a TPU; the
benchmark's own runs never run it.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def serve_and_compare(eng, sp, ref, seed: int, seconds: float,
                      quant: str = "fp8") -> dict:
    """One seed: the engine serves the cell's traffic with that seed's
    weights; returns the program's and the control's widest gaps."""
    import jax
    from bench import harness, oracle
    eng.params = None
    gc.collect()
    params = ref.make_params(sp.config, seed)
    jax.block_until_ready(params)
    eng.params = params
    cellp = sp.cell
    horizon = float(cellp["lead_in_s"]) + seconds
    loop, _ = harness.warmed_loop(eng, sp, seed, float(cellp["rate"]),
                                  horizon)
    loop.run(horizon)
    samples = oracle.served_samples(loop.tracks, seed,
                                    int(cellp["check_tokens"]))
    bad = sum(len(t.req.output) != t.req.max_new_tokens
              for t in loop.tracks if t.req.done)
    harness.reset_engine(eng)
    max_seq = int(sp.serve["max_seq"])
    prog = oracle.widest_gap(ref, sp.config, params, samples, max_seq)
    ctrl = oracle.control_gap(ref, sp.config, params, samples, max_seq,
                              quant)
    return {"seed": seed, "program_gap": prog["gap"],
            "program_correct": harness.judge(cellp, prog, bad)[1],
            "control_gap": ctrl["gap"],
            "control_correct": harness.judge(cellp, ctrl, bad)[1],
            "tokens": prog["tokens"], "requests": len(samples)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    import jax
    from bench import harness
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU found", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    sp = harness.load_spec(args.workload)
    ref = harness.reference_module(sp.config)
    cfg = harness.model_config(sp.config, ref)
    seeds = [int(s) for s in args.seeds.split(",")]
    params = ref.make_params(sp.config, seeds[0])
    eng = harness.build_engine(cfg, params, sp.serve, telemetry=False)
    del params
    rows = []
    for seed in seeds:
        t0 = time.perf_counter()
        row = serve_and_compare(eng, sp, ref, seed, args.seconds)
        row["seconds"] = time.perf_counter() - t0
        rows.append(row)
        print(json.dumps(row), flush=True)
    lower = max(r["program_gap"] for r in rows)
    upper = min(r["control_gap"] for r in rows)
    print(json.dumps({"workload": args.workload, "seeds": len(rows),
                      "lower": lower, "upper": upper,
                      "ratio": upper / lower if lower > 0 else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
