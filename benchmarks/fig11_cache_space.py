"""Fig 11: maximum available KV-cache space (blocks of 16 tokens) across
systems and models.  Paper: Hetis provides up to 1.87x more cache blocks.

Plus a live-engine section: per-device pool-shard capacity and peak
occupancy from the ``kv/device/<id>/used_slots`` gauges of a real
sharded `InferenceEngine` run (tiny model, CPU).
"""

from __future__ import annotations

import numpy as np

from benchmarks.common import emit
from repro.core.cluster import ClusterSpec
from repro.core.costmodel import LLAMA_13B, LLAMA_70B, OPT_30B
from repro.sim import HetisSystem, HexgenSystem, SplitwiseSystem

BLOCK_TOKENS = 16


def live_pool_section() -> None:
    """Drive the sharded engine and report each device shard's capacity
    and peak used_slots — the per-device gauge feed behind this figure."""
    import jax

    from repro.models import transformer as T
    from repro.models.config import ModelConfig
    from repro.serving import EngineConfig, InferenceEngine, Request

    cfg = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
                      head_dim=16, dtype="float32", remat=False,
                      scan_q_chunk=64, loss_chunk=64)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    cl = ClusterSpec.build([("A100", 1), ("3090", 1), ("P100", 1)])
    eng = InferenceEngine(cfg, params, cl, primary_ids=[0],
                          pool_ids=[1, 2],
                          engine_cfg=EngineConfig(max_batch=4, max_seq=64))
    rng = np.random.default_rng(11)
    for i in range(6):
        eng.submit(Request(
            rid=i,
            prompt=[int(x) for x in rng.integers(0, 128,
                                                 rng.integers(6, 14))],
            max_new_tokens=6))
    peak = {d: 0.0 for d in eng.kv.partitions}
    for _ in range(80):
        if not (eng.running or eng.prefilling or eng.queue):
            break
        eng.step()
        snap = eng.snapshot("kv/device/")
        for d in peak:
            peak[d] = max(peak[d], snap[f"kv/device/{d}/used_slots"])
    for d, part in sorted(eng.kv.partitions.items()):
        emit(f"fig11/live/device{d}", 0.0,
             f"capacity_slots={part.total} peak_used_slots={peak[d]:.0f} "
             f"bytes_per_slot={eng.kv.bytes_per_slot()}")


def main() -> None:
    cl = ClusterSpec.paper_testbed()
    for prof in (LLAMA_13B, OPT_30B, LLAMA_70B):
        caps = {}
        for cls in (HetisSystem, HexgenSystem, SplitwiseSystem):
            sys_ = cls(prof, cl)
            caps[sys_.name] = sys_.kv_capacity_tokens() / BLOCK_TOKENS
            emit(f"fig11/{prof.name}/{sys_.name}", 0.0,
                 f"blocks={caps[sys_.name]:.0f}")
        best_base = max(caps["hexgen"], caps["splitwise"])
        emit(f"fig11/{prof.name}/advantage", 0.0,
             f"x{caps['hetis'] / best_base:.2f} vs best baseline "
             f"(paper up to 1.87x)")
    live_pool_section()


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
