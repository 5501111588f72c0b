"""Run one benchmark cell once, on the accelerator this process finds.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
      --trace <0|1>

The cell's configuration, traffic and metrics are read from
``BENCHMARK.json`` and the files under ``bench/``.  The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (end-to-end with ``--trace 0``, per-layer with ``--trace 1``),
``device`` and, traced, ``breakdown``; ``checks`` comes last, each number
compared with its limit, and the same numbers end standard error.  Without
a TPU, or with fewer chips than the cell asks for, it exits 1 and prints
no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax
    from bench import harness

    bench = harness.load_json(ROOT / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"]
                  if w["name"] == args.workload), None)
    if entry is None:
        log(f"run: no workload {args.workload!r} in BENCHMARK.json")
        return 2
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < int(entry["chips"]):
        log(f"run: the cell needs {entry['chips']} TPU chip(s); JAX found "
            f"{len(devices)} {devices[0].platform} device(s)")
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    # every program of the window, small ones too, comes from the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"run: {args.workload} seed {args.seed} on {len(devices)} x "
        f"{devices[0].device_kind}; compile cache {cache}")
    sp = harness.load_spec(args.workload)
    result = harness.run_cell(sp, args.seed, args.seconds, bool(args.trace),
                              t_start=T_START, log=log)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
