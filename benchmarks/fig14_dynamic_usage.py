"""Fig 14: dynamic head / cache usage under time-varying arrivals —
Llama-13B on one A100 primary + two 3090 attention workers.  Shows (a) the
A100 consistently carrying more heads, (b) late pool engagement at light
load (network-overhead awareness), (c) full cache use at peak.
"""

from __future__ import annotations

import numpy as np

from benchmarks.common import emit
from repro.core.cluster import ClusterSpec
from repro.core.costmodel import LLAMA_13B
from repro.sim import HetisSystem, make_trace, simulate
from repro.sim.workloads import TraceRequest


def varying_trace(duration: float = 60.0, seed: int = 4):
    """Rate ramps 0.5 -> 2.5 -> 1.0 req/s (paper's fluctuating arrivals)."""
    rng = np.random.default_rng(seed)
    phases = [(0.0, 20.0, 0.5), (20.0, 40.0, 2.5), (40.0, duration, 1.0)]
    out, rid = [], 0
    for lo, hi, rate in phases:
        n = rng.poisson(rate * (hi - lo))
        for t in np.sort(rng.uniform(lo, hi, n)):
            ln = int(np.clip(rng.lognormal(np.log(300), 0.8), 16, 1500))
            on = int(np.clip(rng.lognormal(np.log(200), 0.7), 8, 700))
            out.append(TraceRequest(rid, float(t), ln, on))
            rid += 1
    return out


def main() -> None:
    cl = ClusterSpec.build([("A100", 1), ("3090", 2)])
    sys_ = HetisSystem(LLAMA_13B, cl)
    res = simulate(sys_, varying_trace(), "varying", 0.0,
                   max_sim_seconds=300.0, sample_every=5)
    # summarize the trace into phase buckets
    for lo, hi, label in ((0, 20, "light"), (20, 40, "peak"),
                          (40, 60, "cooldown")):
        snaps = [s for s in res.timeline if lo <= s["t"] < hi]
        if not snaps:
            continue
        heads = {k: np.mean([s[k] for s in snaps])
                 for k in snaps[0] if k.startswith("heads_")}
        cache = {k: np.mean([s[k] for s in snaps]) / 1e9
                 for k in snaps[0] if k.startswith("cache_")}
        emit(f"fig14/{label}/heads", 0.0,
             " ".join(f"{k}={v:.0f}" for k, v in sorted(heads.items())))
        emit(f"fig14/{label}/cache_gb", 0.0,
             " ".join(f"{k}={v:.2f}" for k, v in sorted(cache.items())))
    # primary carries more heads than pool devices (paper's observation)
    last = res.timeline[-1] if res.timeline else {}
    emit("fig14/served", 0.0, f"n={len(res.served)}")
    live_usage_section()


def live_usage_section() -> None:
    """Live-engine counterpart: per-device pool occupancy over a bursty
    run with a forced mid-run re-dispatch, read from the
    ``kv/device/<id>/used_slots`` gauges and the ``migrate/d2d_bytes``
    counter (physical cross-shard migration traffic)."""
    import jax

    from repro.models import transformer as T
    from repro.models.config import ModelConfig
    from repro.serving import EngineConfig, InferenceEngine, Request

    cfg = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
                      head_dim=16, dtype="float32", remat=False,
                      scan_q_chunk=64, loss_chunk=64)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    cl = ClusterSpec.build([("A100", 1), ("3090", 2)])
    eng = InferenceEngine(cfg, params, cl, primary_ids=[0],
                          pool_ids=[1, 2],
                          engine_cfg=EngineConfig(max_batch=6, max_seq=64))
    rng = np.random.default_rng(14)
    samples: dict[int, list[float]] = {d: [] for d in eng.kv.partitions}
    rid, migrated = 0, False
    for step in range(100):
        # bursty arrivals: a light phase, then a burst, then drain
        if rid < 10 and (step % 8 == 0 or (20 <= step < 30)):
            eng.submit(Request(
                rid=rid,
                prompt=[int(x) for x in rng.integers(0, 128,
                                                     rng.integers(5, 12))],
                max_new_tokens=8))
            rid += 1
        if not (eng.running or eng.prefilling or eng.queue):
            break
        eng.step()
        # one forced re-dispatch mid-run so the migration path is real
        if not migrated and step > 25 and eng.running:
            eng._apply_migration(eng.running[0].rid, {1: cfg.n_heads})
            migrated = True
        snap = eng.snapshot("kv/device/")
        for d in samples:
            samples[d].append(snap[f"kv/device/{d}/used_slots"])
    for d in sorted(samples):
        s = np.asarray(samples[d]) if samples[d] else np.zeros(1)
        emit(f"fig14/live/device{d}/used_slots", 0.0,
             f"mean={s.mean():.1f} peak={s.max():.0f}")
    snap = eng.snapshot()
    emit("fig14/live/migrate_d2d_bytes", 0.0,
         f"bytes={snap['migrate/d2d_bytes']:.0f} "
         f"partial={snap['migrate/partial']:.0f} "
         f"gather={snap['fastpath/gather_d2d_bytes']:.0f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
