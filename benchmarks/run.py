"""Benchmark harness entry point — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (brief).  Usage:
  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run fig12 fig16  # substring filter
"""

from __future__ import annotations

import sys
import time
import traceback

MODULES = [
    "table1_device_gap",
    "fig2_module_gap",
    "fig5_comm_overhead",
    "fig7_linearity",
    "fig8_10_end_to_end",
    "fig11_cache_space",
    "fig12_ttft_tpot",
    "fig13_module_latency",
    "fig14_dynamic_usage",
    "fig15_redispatch",
    "fig16_robustness",
    "search_overhead",
    "kernels_bench",
    "engine_decode_bench",
    "roofline_report",
]


def main() -> int:
    """Run every selected module; returns 1 when any of them raised."""
    filters = sys.argv[1:]
    failed = []
    print("name,us_per_call,derived")
    for mod_name in MODULES:
        if filters and not any(f in mod_name for f in filters):
            continue
        t0 = time.perf_counter()
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["main"])
            mod.main()
            print(f"# {mod_name} done in {time.perf_counter()-t0:.1f}s",
                  file=sys.stderr)
        except Exception:
            failed.append(mod_name)
            traceback.print_exc()
            print(f"{mod_name}/ERROR,0,{traceback.format_exc(limit=1)!r}")
    if failed:
        print(f"# failed: {', '.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
