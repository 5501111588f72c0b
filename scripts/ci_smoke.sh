#!/usr/bin/env bash
# Tier-1 verification + perf-bench smoke: the benches run in CI so the
# decode fast path and kernel wrappers cannot silently rot.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

echo "== docs lint =="
python scripts/check_docs.py

echo "== tier-1 tests =="
python -m pytest -x -q

echo "== kernels bench (smoke) =="
python -m benchmarks.kernels_bench --smoke

echo "== engine decode bench (smoke) =="
python -m benchmarks.engine_decode_bench --smoke

echo "== fused-step smoke: 1 jitted call/step + SLO autotuner =="
python -m benchmarks.engine_decode_bench --smoke --mode fused

echo "== engine prefill bench (smoke) =="
python -m benchmarks.engine_prefill_bench --smoke

echo "== telemetry smoke: traced engine session -> Chrome trace =="
TRACE_OUT="${TRACE_OUT:-/tmp/hetis_ci_trace.json}"
python -m repro.launch.serve --smoke --requests 4 --max-new-tokens 6 \
    --trace-out "$TRACE_OUT" --trace-modules
python -m repro.telemetry.export "$TRACE_OUT"
