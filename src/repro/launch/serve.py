"""Serving launcher: run the Hetis engine end-to-end.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
      --requests 8 --rate 2.0

Without ``--smoke`` the model is the published config of ``--arch`` in
bf16, with random weights drawn from ``--seed`` (no checkpoint ships with
the repo); the defaults ``--max-seq 512 --max-batch 16`` size the KV pools
so that weights, pools and the largest fused step fit one 16 GB v5e chip.
``--smoke`` serves the reduced float32 sibling of the same family, the
size CPU runs use.  An arch the paged, fused path cannot serve is refused.
"""

from __future__ import annotations

import argparse
from typing import Optional, Sequence, Tuple

import jax
import numpy as np

from repro.configs import ALIASES, get_config, smoke_config
from repro.core.cluster import ClusterSpec
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.serving import EngineConfig, InferenceEngine, Request


def load_model(arch: str, smoke: bool, seed: int) -> Tuple[ModelConfig,
                                                             dict]:
    """The served config (published, or its reduced sibling) and random
    weights from ``seed``; raises ValueError for an arch the served path
    cannot run."""
    cfg = smoke_config(arch) if smoke else get_config(arch)
    why = T.fused_step_blocker(cfg)
    if why:
        raise ValueError(f"{arch} cannot be served: {why}")
    return cfg, T.init_params(cfg, jax.random.PRNGKey(seed))


# The served deployment: an A100-class primary plus three pool devices
# (two 3090s, one P100) that hold offloaded head groups.
CLUSTER = (("A100", 1), ("3090", 2), ("P100", 1))
PRIMARY_IDS, POOL_IDS = (0,), (1, 2, 3)
# Largest defaults whose weights, pools and largest fused-step temporaries
# fit one 16 GB v5e chip at qwen1.5-0.5b width (``tests/test_tpu_compile.py``
# compiles that step for the chip and checks the sum).
MAX_SEQ, MAX_BATCH = 512, 16


def build_engine(cfg: ModelConfig, params, *, max_seq: int = MAX_SEQ,
                 max_batch: int = MAX_BATCH, telemetry: bool = False,
                 trace_modules: bool = False) -> InferenceEngine:
    """The served engine on the deployment above."""
    return InferenceEngine(cfg, params, ClusterSpec.build(CLUSTER),
                           primary_ids=PRIMARY_IDS, pool_ids=POOL_IDS,
                           engine_cfg=EngineConfig(
                               max_batch=max_batch, max_seq=max_seq,
                               telemetry=telemetry,
                               trace_modules=trace_modules))


def main(argv: Optional[Sequence[str]] = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b", choices=sorted(ALIASES))
    ap.add_argument("--smoke", action="store_true",
                    help="serve the reduced float32 sibling (CPU size)")
    ap.add_argument("--max-seq", type=int, default=MAX_SEQ)
    ap.add_argument("--max-batch", type=int, default=MAX_BATCH)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--rate", type=float, default=2.0)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="enable telemetry and write a Chrome trace_event "
                         "JSON (chrome://tracing / ui.perfetto.dev)")
    ap.add_argument("--trace-modules", action="store_true",
                    help="also run the eager per-module probe (device-"
                         "sync'd Attention/MLP spans feeding dispatcher/"
                         "hauler/costmodel calibration)")
    args = ap.parse_args(argv)

    enable_compile_cache()
    try:
        cfg, params = load_model(args.arch, args.smoke, args.seed)
    except ValueError as e:
        ap.error(str(e))
    print(f"# {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.dtype}, backend {jax.default_backend()}")

    eng = build_engine(cfg, params, max_seq=args.max_seq,
                       max_batch=args.max_batch,
                       telemetry=bool(args.trace_out) or args.trace_modules,
                       trace_modules=args.trace_modules)

    rng = np.random.default_rng(args.seed)
    t = 0.0
    for i in range(args.requests):
        t += rng.exponential(1.0 / args.rate)
        prompt = [int(x) for x in
                  rng.integers(0, cfg.vocab_size, int(rng.integers(4, 24)))]
        eng.submit(Request(rid=i, prompt=prompt,
                           max_new_tokens=args.max_new_tokens, arrival=t))
    eng.run_until_drained()
    print(f"served {len(eng.finished)} requests, "
          f"modeled clock {eng.clock*1e3:.2f} ms, metrics {eng.metrics}")
    for r in eng.finished[:4]:
        print(f"  rid={r.rid} modeled ttft={r.ttft*1e3:.2f}ms "
              f"tokens={r.output[:8]}...")
    snap = eng.snapshot()
    print(f"snapshot: modeled ttft_p95={snap['ttft_s/p95']*1e3:.3f}ms "
          f"kv_occupancy={snap['kv/occupancy']:.3f} "
          f"recompiles={snap['jit/recompiles']:.0f}")
    if args.trace_out:
        n = eng.tracer.write_chrome(args.trace_out)
        print(f"wrote {n} trace events to {args.trace_out}")


if __name__ == "__main__":
    main()
