"""Bring-up check of the served path on one TPU chip.

  python chip_smoke.py [--seed N]

Three phases, in one process:

1. both paged-attention kernels, at qwen1.5-0.5b's head shape in bf16,
   against their float32 references (``kernels/paged_attention/ref.py``);
2. the engine that ``repro.launch.serve`` builds, serving qwen1.5-0.5b at
   its published width (24 layers, d_model 1024, bf16, random weights from
   ``--seed``): 8 requests with seeded prompts of 64-448 tokens and 32 new
   tokens each, run until drained, checked for finite logits, first-token
   logits that agree with ``transformer.prefill``, and a Pallas kernel in
   the compiled fused step;
3. the device's memory high-water mark against its limit.

Any failed check exits non-zero.  Without a TPU it exits non-zero before
any work: it never falls back to the CPU.  On success the last line of
stdout is one JSON object naming the device.  Times printed as "modeled"
come from the engine's cost-model clock and are not measurements.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path
from typing import Dict, List, Optional, Sequence

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels.paged_attention import (  # noqa: E402
    paged_attention, paged_attention_ref, paged_prefill_attention,
    paged_prefill_attention_ref)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import (  # noqa: E402
    MAX_BATCH, MAX_SEQ, build_engine, load_model)
from repro.models import transformer as T  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.serving import Request  # noqa: E402

ARCH = "qwen1.5-0.5b"
# First-token logits of the engine against ``transformer.prefill``: both
# run the same weights in the config's dtype, but with different kernels,
# chunking and summation order, so in bf16 they differ by rounding that
# compounds over the layers.  Bound: this share of the reference logits'
# largest magnitude, about six bf16 epsilons.
LOGITS_RTOL = 0.05
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class SmokeFailure(RuntimeError):
    """A check of the bring-up smoke did not hold."""


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# --------------------------------------------------------------- kernels
def check_kernels(cfg: ModelConfig, *, batch: int = 8, max_pages: int = 32,
                  page: int = 16, chunk: int = 32, seed: int = 0
                  ) -> Dict[str, float]:
    """Run both paged kernels at ``cfg``'s head shape and dtype against
    their float32 references on the same (rounded) inputs; returns each
    kernel's max abs error and the tolerance, raising above it.

    Tolerance: the kernels score and accumulate in float32 but round the
    softmax weights to the pool dtype before the P.V product and round
    their output to it.  Each rounding moves a convex combination of V
    rows by at most eps/2 * max|v| (eps = the dtype's machine epsilon,
    2**-7 for bf16), so the two together stay under eps * max|v|; the
    bound is doubled for the float32 exp/accumulation order."""
    Hkv, r, dh = cfg.n_kv_heads, cfg.gqa_ratio, cfg.head_dim
    dtype = jnp.dtype(cfg.kv_dtype)
    n_slots = batch * Hkv * max_pages + 1
    S = max_pages * page
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    kpool = jax.random.normal(ks[0], (n_slots, page, dh), jnp.float32)
    vpool = jax.random.normal(ks[1], (n_slots, page, dh), jnp.float32)
    kpool, vpool = kpool.astype(dtype), vpool.astype(dtype)
    tables = jax.random.permutation(ks[2], n_slots - 1)[
        :batch * Hkv * max_pages].reshape(batch, Hkv, max_pages)
    tables = tables.astype(jnp.int32)
    lengths = jax.random.randint(ks[3], (batch,), 1, S + 1, jnp.int32)
    q = jax.random.normal(ks[4], (batch, Hkv, r, dh)).astype(dtype)
    starts = jax.random.randint(ks[5], (batch,), 0, S - chunk + 1,
                                jnp.int32)
    qc = jax.random.normal(ks[6], (batch, Hkv, chunk, r, dh)).astype(dtype)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731

    dec = paged_attention(q, kpool, vpool, tables, lengths)
    dec_ref = paged_attention_ref(f32(q), f32(kpool), f32(vpool), tables,
                                  lengths)
    pre = paged_prefill_attention(qc, kpool, vpool, tables, starts + chunk,
                                  starts)
    pre_ref = paged_prefill_attention_ref(f32(qc), f32(kpool), f32(vpool),
                                          tables, starts + chunk, starts)
    tol = 2.0 * float(jnp.finfo(dtype).eps) * float(jnp.abs(f32(vpool)).max())
    out = {"decode_err": float(jnp.abs(f32(dec) - dec_ref).max()),
           "prefill_err": float(jnp.abs(f32(pre) - pre_ref).max()),
           "tol": tol}
    for name in ("decode_err", "prefill_err"):
        _require(np.isfinite(out[name]) and out[name] <= tol,
                 f"paged kernel {name} {out[name]:.3g} > tolerance {tol:.3g}")
    return out


# ---------------------------------------------------------------- engine
class _FusedStepProbe:
    """Stands in for the engine's jitted fused step: forwards every call,
    folds each call's logits into one on-device finiteness flag, keeps the
    argument shapes of the first call (to lower that bucket again), and
    keeps the logits row that completes the watched prompt's prefill."""

    def __init__(self, fn, watched: Sequence[int]):
        self.fn = fn
        self.watched = np.asarray(watched, np.int32)
        self.finite = jnp.asarray(True)
        self.first_shapes = None
        self.first_logits: Optional[jax.Array] = None

    def __getattr__(self, name):            # _cache_size, lower
        return getattr(self.fn, name)

    def __call__(self, params, kps, vps, *dev):
        if self.first_shapes is None:
            self.first_shapes = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                (params, kps, vps) + dev)
        logits, kps, vps = self.fn(params, kps, vps, *dev)
        self.finite = self.finite & jnp.isfinite(logits).all()
        if self.first_logits is None:
            self._match_watched(logits, dev)
        return logits, kps, vps

    def _match_watched(self, logits, dev) -> None:
        # operand order of the fused step after the six exchange lanes:
        # tables, lengths, starts, write slots, write offsets, tokens, last
        lengths, starts, toks, last = (np.asarray(dev[i])
                                       for i in (7, 8, 11, 12))
        n = len(self.watched)
        for i in np.flatnonzero(lengths == n):
            s0 = int(starts[i])
            if (s0 + int(last[i]) + 1 == n and np.array_equal(
                    toks[i, :n - s0], self.watched[s0:])):
                self.first_logits = logits[i]
                return


def _dense_greedy(cfg: ModelConfig, params, prompts: List[List[int]],
                  new_tokens: int, max_seq: int) -> List[List[int]]:
    """Greedy tokens of every prompt from the dense reference path
    (``transformer.prefill`` + ``decode_step``, no pools), batched with
    right padding."""
    B = len(prompts)
    lens = np.asarray([len(p) for p in prompts], np.int32)
    toks = np.zeros((B, int(lens.max())), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    prefill = jax.jit(lambda p, t, n: T.prefill(cfg, p, {"tokens": t},
                                                max_seq=max_seq, lengths=n))
    decode = jax.jit(lambda p, c, t: T.decode_step(cfg, p, c, t))
    logits, cache = prefill(params, jnp.asarray(toks), jnp.asarray(lens))
    out = [np.asarray(jnp.argmax(logits, axis=-1))]
    for _ in range(new_tokens - 1):
        logits, cache = decode(params, cache,
                               jnp.asarray(out[-1][:, None], jnp.int32))
        out.append(np.asarray(jnp.argmax(logits, axis=-1)))
    return np.stack(out, axis=1).tolist()


def serve_requests(cfg: ModelConfig, params, *, n_requests: int = 8,
                   prompt_lens=(64, 448), new_tokens: int = 32,
                   max_seq: int = MAX_SEQ, max_batch: int = MAX_BATCH,
                   seed: int = 0) -> Dict[str, float]:
    """Serve ``n_requests`` seeded prompts through the engine
    ``repro.launch.serve`` builds and check what comes out; raises
    :class:`SmokeFailure` on the first check that fails.  Request 0's
    first-token logits are compared with ``transformer.prefill`` on the
    same prompt, within ``LOGITS_RTOL``."""
    rng = np.random.default_rng(seed)
    lo, hi = prompt_lens
    prompts = [[int(t) for t in rng.integers(0, cfg.vocab_size, int(n))]
               for n in rng.integers(lo, hi + 1, n_requests)]
    eng = build_engine(cfg, params, max_seq=max_seq, max_batch=max_batch)
    _require(eng.use_fused, "the engine did not take the fused paged step")
    probe = _FusedStepProbe(eng._fused_fn, prompts[0])
    eng._fused_fn = probe
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=new_tokens,
                           arrival=0.0))

    compile_s = [0.0]

    def on_event(event: str, secs: float, **_) -> None:
        if event in COMPILE_EVENTS:
            compile_s[0] += secs

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        t0 = time.perf_counter()
        drained = eng.run_until_drained(max_steps=20 * (hi + new_tokens))
        jax.block_until_ready((eng.kv.pools(), probe.finite))
        wall_s = time.perf_counter() - t0
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)

    _require(drained, "the engine did not drain")
    _require(len(eng.finished) == n_requests,
             f"{len(eng.finished)} of {n_requests} requests finished")
    counts = sorted(len(r.output) for r in eng.finished)
    _require(counts == [new_tokens] * n_requests,
             f"tokens per request {counts}, expected {new_tokens} each")
    _require(bool(probe.finite), "the fused step produced non-finite logits")
    _require(probe.first_logits is not None,
             "no fused-step row completed request 0's prompt")
    ref = jax.jit(lambda p, t: T.prefill(cfg, p, {"tokens": t},
                                         max_seq=t.shape[1])[0])(
        params, jnp.asarray(prompts[0], jnp.int32)[None])[0]
    err = float(jnp.abs(probe.first_logits - ref).max())
    tol = LOGITS_RTOL * float(jnp.abs(ref).max())
    _require(err <= tol, f"first-token logits differ from prefill by "
             f"{err:.4g} > {tol:.4g}")
    lowered = eng._fused_fn.lower(*probe.first_shapes)
    custom_calls = lowered.compile().as_text().count("tpu_custom_call")

    by_rid = {r.rid: r.output for r in eng.finished}
    dense = _dense_greedy(cfg, params, prompts, new_tokens, max_seq)
    agree = sum(int(a == b) for i, d in enumerate(dense)
                for a, b in zip(d, by_rid[i]))
    return {"requests": n_requests, "finished": len(eng.finished),
            "tokens_each": new_tokens, "prompt_tokens": sum(map(len, prompts)),
            "steps": eng.metrics["steps"], "wall_s": wall_s,
            "compile_s": compile_s[0],
            "fused_compiles": eng.fused_compile_count(),
            "fused_bucket_count": eng.fused_bucket_count(),
            "first_logits_err": err, "first_logits_tol": tol,
            "tpu_custom_calls": custom_calls,
            "dense_agree": agree, "dense_total": n_requests * new_tokens,
            "modeled_clock_s": eng.clock}


# ------------------------------------------------------------------ main
def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
              f"this check does not run on another backend", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache {cache}")
    try:
        cfg, params = load_model(ARCH, smoke=False, seed=args.seed)
        print(f"model: {cfg.name} {cfg.n_layers} layers, d_model "
              f"{cfg.d_model}, {cfg.n_heads} heads / {cfg.n_kv_heads} kv "
              f"heads, head_dim {cfg.head_dim}, vocab {cfg.vocab_size}, "
              f"{cfg.dtype}")
        k = check_kernels(cfg, seed=args.seed)
        print(f"kernels: decode max err {k['decode_err']:.4g}, prefill max "
              f"err {k['prefill_err']:.4g}, tolerance {k['tol']:.4g}")
        s = serve_requests(cfg, params, seed=args.seed)
        print(f"engine: {s['finished']} of {s['requests']} requests drained "
              f"in {s['steps']:.0f} steps, {s['tokens_each']} tokens each, "
              f"{s['prompt_tokens']} prompt tokens")
        print(f"engine: served window {s['wall_s']:.3f} s wall (measured, "
              f"ends in block_until_ready), of which compile "
              f"{s['compile_s']:.3f} s")
        print(f"engine: fused-step compiles {s['fused_compiles']} of "
              f"{s['fused_bucket_count']} buckets; tpu_custom_call in fused "
              f"step: {s['tpu_custom_calls']}")
        print(f"engine: first-token logits max err "
              f"{s['first_logits_err']:.4g} (tolerance "
              f"{s['first_logits_tol']:.4g}); greedy tokens equal to the "
              f"dense path: {s['dense_agree']} of {s['dense_total']} "
              f"(information only)")
        print(f"engine: modeled clock {s['modeled_clock_s'] * 1e3:.3f} ms "
              f"(cost model, not measured)")
        _require(s["tpu_custom_calls"] >= 1,
                 "no tpu_custom_call in the compiled fused step")
        mem = dev.memory_stats() or {}
        peak, limit = mem.get("peak_bytes_in_use"), mem.get("bytes_limit")
        _require(peak is not None and limit is not None,
                 "the device reports no memory stats")
        print(f"memory: peak_bytes_in_use {peak} of bytes_limit {limit} "
              f"({100.0 * peak / limit:.1f}%)")
        _require(peak < limit, "peak device memory reached the limit")
    except Exception as e:  # every failure, of any phase, fails the run
        traceback.print_exc()
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
