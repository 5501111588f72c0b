"""Model assembly: layer groups, scan-over-layers, prefill/decode/train.

A config is compiled into an ordered list of homogeneous **layer groups**
(e.g. deepseek-v3 = 3 dense layers then 58 MoE layers; xlstm = 12
(mLSTM,sLSTM) pairs; hymba = 32 hybrid layers with a per-layer window flag).
Each group is initialized with stacked parameters (leading layer axis) and
executed with ``lax.scan`` so HLO size is depth-independent — essential for
the 61-layer/256-expert dry-runs (DESIGN §5).

Caches: ``{"groups": [per-group pytree with leading (n_layers, B, ...)],
"pos": (B,) int32}``.  Decode scans each group with its cache slice as scan
xs and emits the updated slice as ys.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import logical
from repro.models import attention as attn
from repro.models import mlp as mlp_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.common import cdtype, dense_init, embed_init, rmsnorm
from repro.models.config import ModelConfig

Params = Dict[str, Any]
Cache = Dict[str, Any]


# ---------------------------------------------------------------------------
# Group layout
# ---------------------------------------------------------------------------

def layer_groups(cfg: ModelConfig) -> List[Tuple[str, int, int]]:
    """Ordered (kind, n_layers, window) list; each group is scanned
    homogeneously with a STATIC attention window (0 = full) so windowed
    groups can use the sliced-strip attention path (§Perf hymba)."""
    if cfg.xlstm_pattern:
        period = len(cfg.xlstm_pattern)
        assert cfg.n_layers % period == 0, "xlstm pattern must tile layers"
        return [("xlstm_pair", cfg.n_layers // period, 0)]
    if cfg.attn_type == "none":
        raise ValueError("attention-free non-xlstm archs not supported")

    def window_of(i: int) -> int:
        if not cfg.sliding_window or i in cfg.global_layers:
            return 0
        return cfg.sliding_window

    def kind_of(i: int) -> str:
        if cfg.ssm_state and cfg.attn_type == "gqa":
            return "hybrid"
        a = cfg.attn_type
        if cfg.n_experts and i >= cfg.first_dense_layers:
            return f"{a}_moe"
        return f"{a}_mlp"

    out: List[Tuple[str, int, int]] = []
    for i in range(cfg.n_layers):
        k, w = kind_of(i), window_of(i)
        if out and out[-1][0] == k and out[-1][2] == w:
            out[-1] = (k, out[-1][1] + 1, w)
        else:
            out.append((k, 1, w))
    return out


# ---------------------------------------------------------------------------
# Per-layer init / apply by kind
# ---------------------------------------------------------------------------

def _layer_init(cfg: ModelConfig, kind: str, key) -> Params:
    dt = cdtype(cfg)
    d = cfg.d_model
    ks = jax.random.split(key, 6)
    p: Params = {}
    if kind == "xlstm_pair":
        p["m_norm"] = jnp.ones((d,), jnp.float32)
        p["mlstm"] = xlstm_mod.mlstm_init(cfg, ks[0])
        p["s_norm"] = jnp.ones((d,), jnp.float32)
        p["slstm"] = xlstm_mod.slstm_init(cfg, ks[1])
        return p
    p["attn_norm"] = jnp.ones((d,), jnp.float32)
    if kind.startswith("mla"):
        p["attn"] = attn.mla_init(cfg, ks[0])
    else:
        p["attn"] = attn.gqa_init(cfg, ks[0])
    if kind == "hybrid":
        p["ssm"] = ssm_mod.ssm_init(cfg, ks[1])
        p["attn_out_norm"] = jnp.ones((d,), jnp.float32)
        p["ssm_out_norm"] = jnp.ones((d,), jnp.float32)
    p["mlp_norm"] = jnp.ones((d,), jnp.float32)
    if kind.endswith("moe"):
        p["mlp"] = mlp_mod.moe_init(cfg, ks[2])
    elif kind == "hybrid" and cfg.d_ff:
        p["mlp"] = mlp_mod.mlp_init(cfg, ks[2])
    elif cfg.d_ff:
        p["mlp"] = mlp_mod.mlp_init(cfg, ks[2])
    return p


def _layer_cache_init(cfg: ModelConfig, kind: str, batch: int, max_seq: int
                      ) -> Cache:
    if kind == "xlstm_pair":
        return {"m": xlstm_mod.mlstm_cache_init(cfg, batch),
                "s": xlstm_mod.slstm_cache_init(cfg, batch)}
    if kind.startswith("mla"):
        c: Cache = attn.mla_cache_init(cfg, batch, max_seq)
    else:
        c = attn.gqa_cache_init(cfg, batch, max_seq)
    if kind == "hybrid":
        c.update(ssm_mod.ssm_cache_init(cfg, batch))
    return c


def _apply_full(cfg: ModelConfig, kind: str, p: Params, x, positions, window
                ) -> Tuple[jax.Array, Cache, jax.Array]:
    """Train/prefill body for one layer.  Returns (x, cache_entries, aux)."""
    aux = jnp.zeros((), jnp.float32)
    if kind == "xlstm_pair":
        h, m_cache = xlstm_mod.mlstm_forward(
            cfg, p["mlstm"], rmsnorm(x, p["m_norm"], cfg.norm_eps))
        x = x + h
        h, s_cache = xlstm_mod.slstm_forward(
            cfg, p["slstm"], rmsnorm(x, p["s_norm"], cfg.norm_eps))
        x = x + h
        return x, {"m": m_cache, "s": s_cache}, aux

    xn = rmsnorm(x, p["attn_norm"], cfg.norm_eps)
    if kind.startswith("mla"):
        a_out, cache = attn.mla_full(cfg, p["attn"], xn, positions, window)
    else:
        a_out, cache = attn.gqa_full(cfg, p["attn"], xn, positions, window)
    if kind == "hybrid":
        s_out, s_cache = ssm_mod.ssm_forward(cfg, p["ssm"], xn)
        a_out = 0.5 * (rmsnorm(a_out, p["attn_out_norm"], cfg.norm_eps)
                       + rmsnorm(s_out, p["ssm_out_norm"], cfg.norm_eps))
        cache.update(s_cache)
    x = x + a_out
    if "mlp" in p:
        xn = rmsnorm(x, p["mlp_norm"], cfg.norm_eps)
        if kind.endswith("moe"):
            m_out, aux = mlp_mod.moe_apply(cfg, p["mlp"], xn)
        else:
            m_out = mlp_mod.mlp_apply(cfg, p["mlp"], xn)
        x = x + m_out
    return x, cache, aux


def _apply_decode(cfg: ModelConfig, kind: str, p: Params, cache: Cache, x,
                  pos, window) -> Tuple[jax.Array, Cache]:
    if kind == "xlstm_pair":
        h, m_cache = xlstm_mod.mlstm_decode(
            cfg, p["mlstm"], rmsnorm(x, p["m_norm"], cfg.norm_eps),
            cache["m"])
        x = x + h
        h, s_cache = xlstm_mod.slstm_decode(
            cfg, p["slstm"], rmsnorm(x, p["s_norm"], cfg.norm_eps),
            cache["s"])
        x = x + h
        return x, {"m": m_cache, "s": s_cache}

    xn = rmsnorm(x, p["attn_norm"], cfg.norm_eps)
    if kind.startswith("mla"):
        a_out, new_cache = attn.mla_decode(cfg, p["attn"], xn, cache, pos,
                                           window)
    else:
        a_out, new_cache = attn.gqa_decode(
            cfg, p["attn"], xn,
            {"k": cache["k"], "v": cache["v"]}, pos, window)
    if kind == "hybrid":
        s_out, s_cache = ssm_mod.ssm_decode(
            cfg, p["ssm"], xn, {"conv": cache["conv"], "ssm": cache["ssm"]})
        a_out = 0.5 * (rmsnorm(a_out, p["attn_out_norm"], cfg.norm_eps)
                       + rmsnorm(s_out, p["ssm_out_norm"], cfg.norm_eps))
        new_cache.update(s_cache)
    x = x + a_out
    if "mlp" in p:
        xn = rmsnorm(x, p["mlp_norm"], cfg.norm_eps)
        if kind.endswith("moe"):
            m_out, _ = mlp_mod.moe_apply(cfg, p["mlp"], xn)
        else:
            m_out = mlp_mod.mlp_apply(cfg, p["mlp"], xn)
        x = x + m_out
    return x, new_cache


# ---------------------------------------------------------------------------
# Whole-model init
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key) -> Params:
    dt = cdtype(cfg)
    groups = layer_groups(cfg)
    keys = jax.random.split(key, len(groups) + 4)
    params: Params = {"groups": []}
    for gi, (kind, n, _win) in enumerate(groups):
        gkeys = jax.random.split(keys[gi], n)
        stacked = jax.vmap(lambda k: _layer_init(cfg, kind, k))(gkeys)
        params["groups"].append(stacked)
    if cfg.frontend == "audio_stub":
        params["in_proj"] = dense_init(keys[-4], cfg.d_model, (cfg.d_model,),
                                       dt)
        if cfg.max_pos_embed:
            params["pos_embed"] = (jax.random.normal(
                keys[-1], (cfg.max_pos_embed, cfg.d_model), jnp.float32)
                * 0.02).astype(dt)
    else:
        params["embed"] = embed_init(keys[-1], cfg.vocab_size, cfg.d_model, dt)
        if cfg.frontend == "vision_stub":
            params["img_proj"] = dense_init(keys[-3], cfg.d_model,
                                            (cfg.d_model,), dt)
    params["final_norm"] = jnp.ones((cfg.d_model,), jnp.float32)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[-2], cfg.d_model,
                                       (cfg.vocab_size,), dt)
    return params


def init_cache(cfg: ModelConfig, batch: int, max_seq: int) -> Cache:
    caches = []
    for kind, n, _win in layer_groups(cfg):
        one = lambda _: _layer_cache_init(cfg, kind, batch, max_seq)
        caches.append(jax.vmap(one)(jnp.arange(n)))
    return {"groups": caches, "pos": jnp.zeros((batch,), jnp.int32)}


# ---------------------------------------------------------------------------
# Frontends
# ---------------------------------------------------------------------------

def embed_inputs(cfg: ModelConfig, params: Params, batch: Dict) -> jax.Array:
    """Turn raw model inputs into the (B, S, d) hidden-state stream."""
    dt = cdtype(cfg)
    if cfg.frontend == "audio_stub":
        x = batch["frames"].astype(dt) @ params["in_proj"]
        if cfg.max_pos_embed:
            x = x + params["pos_embed"][None, :x.shape[1]]
    elif cfg.frontend == "vision_stub":
        tok = jnp.take(params["embed"], batch["tokens"], axis=0)
        img = batch["image_embeds"].astype(dt) @ params["img_proj"]
        x = jnp.concatenate([img, tok], axis=1)
    else:
        x = jnp.take(params["embed"], batch["tokens"], axis=0)
    return logical(x, "batch", "seq", "embed")


def _lm_head(cfg: ModelConfig, params: Params) -> jax.Array:
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["lm_head"]


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------

def backbone(cfg: ModelConfig, params: Params, x: jax.Array,
             positions: jax.Array, *, want_cache: bool, remat: bool
             ) -> Tuple[jax.Array, Optional[List], jax.Array]:
    """Run all layer groups; optionally collect prefill caches."""
    groups = layer_groups(cfg)
    caches: List = []
    aux_total = jnp.zeros((), jnp.float32)
    for gi, (kind, n, win) in enumerate(groups):

        def body(carry, p_l, _kind=kind, _win=win):
            xx, aux = carry
            xx, cache_l, a = _apply_full(cfg, _kind, p_l, xx, positions,
                                         _win)
            out = cache_l if want_cache else None
            return (xx, aux + a), out

        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        (x, aux_total), ys = jax.lax.scan(body, (x, aux_total),
                                          params["groups"][gi])
        if want_cache:
            caches.append(ys)
    return x, (caches if want_cache else None), aux_total


def forward_hidden(cfg: ModelConfig, params: Params, batch: Dict,
                   remat: Optional[bool] = None) -> Tuple[jax.Array, jax.Array]:
    """(B,S,d) final hidden states + MoE aux loss (training path)."""
    x = embed_inputs(cfg, params, batch)
    S = x.shape[1]
    positions = jnp.arange(S)[None, :]
    x, _, aux = backbone(cfg, params, x, positions, want_cache=False,
                         remat=cfg.remat if remat is None else remat)
    return rmsnorm(x, params["final_norm"], cfg.norm_eps), aux


def loss_fn(cfg: ModelConfig, params: Params, batch: Dict
            ) -> Tuple[jax.Array, Dict]:
    """Chunked causal-LM (or frame-classification) cross-entropy."""
    h, aux = forward_hidden(cfg, params, batch)
    labels = batch["labels"]                       # (B, S_out) int32, -1 = pad
    B, S, d = h.shape
    if labels.shape[1] != S:                       # vlm: labels only for text
        h = h[:, S - labels.shape[1]:]
        S = labels.shape[1]
    head = _lm_head(cfg, params)

    chunk = min(cfg.loss_chunk, S)
    n_chunks = -(-S // chunk)
    pad = n_chunks * chunk - S
    hp = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
    lp = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    hs = hp.reshape(B, n_chunks, chunk, d).transpose(1, 0, 2, 3)
    ls = lp.reshape(B, n_chunks, chunk).transpose(1, 0, 2)

    def chunk_loss(carry, inp):
        hc, lc = inp
        logits = (hc @ head).astype(jnp.float32)
        logits = logical(logits, "batch", "seq", "vocab")
        logz = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(
            logits, jnp.maximum(lc, 0)[..., None], axis=-1)[..., 0]
        mask = (lc >= 0).astype(jnp.float32)
        nll = (logz - tgt) * mask
        total, count = carry
        return (total + nll.sum(), count + mask.sum()), None

    (total, count), _ = jax.lax.scan(chunk_loss,
                                     (jnp.zeros((), jnp.float32),
                                      jnp.zeros((), jnp.float32)),
                                     (hs, ls))
    loss = total / jnp.maximum(count, 1.0) + 0.01 * aux
    return loss, {"nll": total / jnp.maximum(count, 1.0), "aux": aux,
                  "tokens": count}


def prefill(cfg: ModelConfig, params: Params, batch: Dict, max_seq: int,
            lengths: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, Cache]:
    """Process the full prompt; returns (last-token logits, cache).

    The cache is padded/written for positions [0, S); ``max_seq`` reserves
    extra slots for decode.  ``lengths`` (B,) marks true prompt lengths
    (right-padded batches).
    """
    x = embed_inputs(cfg, params, batch)
    B, S, d = x.shape
    positions = jnp.arange(S)[None, :]
    x, caches, _ = backbone(cfg, params, x, positions, want_cache=True,
                            remat=False)
    h = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    last = jnp.take_along_axis(h, (lengths - 1)[:, None, None], axis=1)
    logits = (last[:, 0] @ _lm_head(cfg, params)).astype(jnp.float32)

    # grow caches to max_seq along the kv-seq axis
    grown = []
    for (kind, n, _win), c in zip(layer_groups(cfg), caches):
        c = dict(c)
        for key in ("k", "v", "ckv", "krope"):
            if key in c:
                cur = c[key]          # (L, B, S, ...) -> pad S up to max_seq
                c[key] = jnp.pad(cur, ((0, 0), (0, 0), (0, max_seq - S))
                                 + ((0, 0),) * (cur.ndim - 3))
        grown.append(c)
    return logits, {"groups": grown, "pos": lengths.astype(jnp.int32)}


def _apply_decode_carry(cfg: ModelConfig, kind: str, p: Params,
                        caches: Cache, idx, x, pos, window
                        ) -> Tuple[jax.Array, Cache]:
    """Decode one layer against the group's FULL stacked caches, updating
    in place via scatter at (idx, b, pos_b) — see gqa_decode_carry."""
    caches = dict(caches)
    if kind == "xlstm_pair":
        h, m_cache = xlstm_mod.mlstm_decode(
            cfg, p["mlstm"], rmsnorm(x, p["m_norm"], cfg.norm_eps),
            jax.tree.map(lambda c: c[idx], caches["m"]))
        x = x + h
        h, s_cache = xlstm_mod.slstm_decode(
            cfg, p["slstm"], rmsnorm(x, p["s_norm"], cfg.norm_eps),
            jax.tree.map(lambda c: c[idx], caches["s"]))
        x = x + h
        caches["m"] = jax.tree.map(lambda full, new: full.at[idx].set(new),
                                   caches["m"], m_cache)
        caches["s"] = jax.tree.map(lambda full, new: full.at[idx].set(new),
                                   caches["s"], s_cache)
        return x, caches

    xn = rmsnorm(x, p["attn_norm"], cfg.norm_eps)
    if kind.startswith("mla"):
        a_out, caches["ckv"], caches["krope"] = attn.mla_decode_carry(
            cfg, p["attn"], xn, caches["ckv"], caches["krope"], idx, pos,
            window)
    else:
        a_out, caches["k"], caches["v"] = attn.gqa_decode_carry(
            cfg, p["attn"], xn, caches["k"], caches["v"], idx, pos, window)
    if kind == "hybrid":
        s_out, s_cache = ssm_mod.ssm_decode(
            cfg, p["ssm"], xn,
            {"conv": caches["conv"][idx], "ssm": caches["ssm"][idx]})
        a_out = 0.5 * (rmsnorm(a_out, p["attn_out_norm"], cfg.norm_eps)
                       + rmsnorm(s_out, p["ssm_out_norm"], cfg.norm_eps))
        caches["conv"] = caches["conv"].at[idx].set(s_cache["conv"])
        caches["ssm"] = caches["ssm"].at[idx].set(s_cache["ssm"])
    x = x + a_out
    if "mlp" in p:
        xn = rmsnorm(x, p["mlp_norm"], cfg.norm_eps)
        if kind.endswith("moe"):
            m_out, _ = mlp_mod.moe_apply(cfg, p["mlp"], xn)
        else:
            m_out = mlp_mod.mlp_apply(cfg, p["mlp"], xn)
        x = x + m_out
    return x, caches


def _paged_decode_blocker(cfg: ModelConfig) -> str:
    """Why decode cannot run against the head-granular paged pool ('' when
    it can).  Pure-GQA full-attention stacks only; MLA (latent cache),
    SSM/hybrid (recurrent state), xLSTM and sliding-window configs use the
    dense reference path."""
    if cfg.attn_type != "gqa":
        return (f"attention type {cfg.attn_type!r} has no head-granular "
                f"K/V pool (GQA only)")
    if cfg.xlstm_pattern or cfg.ssm_state:
        return "recurrent layer state has no place in the paged K/V pool"
    if cfg.sliding_window:
        return "the paged kernels have no sliding-window bound"
    if cfg.is_encoder_only:
        return "an encoder-only model has no decode step"
    return ""


def fused_step_blocker(cfg: ModelConfig) -> str:
    """Why the served path (paged pools + fused prefill/decode step)
    cannot run ``cfg`` — '' when it can."""
    why = _paged_decode_blocker(cfg)
    if not why and cfg.frontend in ("audio_stub", "vision_stub"):
        why = f"the {cfg.frontend} frontend has no token-chunk prefill"
    return why


def supports_paged_decode(cfg: ModelConfig) -> bool:
    """Can decode run against the head-granular paged pool?"""
    return not _paged_decode_blocker(cfg)


def paged_decode_step(cfg: ModelConfig, params: Params,
                      kpool: jax.Array, vpool: jax.Array,
                      block_tables: jax.Array, lengths: jax.Array,
                      write_slot: jax.Array, write_off: jax.Array,
                      tokens: jax.Array, pos: jax.Array
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step against the device-resident paged KV pool.

    The dense QKV/MLP projections run exactly as in ``decode_step``'s
    "carry" variant, but attention consumes ``(B, Hkv, max_pages)`` block
    tables through the Pallas paged kernel instead of a gathered dense
    cache: the pools are carried through the layer scan and updated with
    one (B*Hkv)-element scatter per layer.  Returns (logits, kpool, vpool);
    the caller re-installs the pools, so the cache never leaves the device.

    tokens: (B, 1) int32; pos: (B,) absolute position of each new token;
    other operands documented in ``attn.gqa_decode_paged``.
    """
    assert supports_paged_decode(cfg), "config not supported by paged decode"
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
        x = logical(x, "batch", "seq", "embed")
    layer0 = 0
    for gi, (kind, n, _win) in enumerate(layer_groups(cfg)):

        @jax.named_scope("layer")
        def body(carry, layer_in, _kind=kind):
            xx, kp, vp = carry
            p_l, idx = layer_in
            xn = rmsnorm(xx, p_l["attn_norm"], cfg.norm_eps)
            a_out, kp, vp = attn.gqa_decode_paged(
                cfg, p_l["attn"], xn, kp, vp, idx, block_tables, lengths,
                write_slot, write_off, pos)
            xx = xx + a_out
            return (_mlp_residual(cfg, _kind, p_l, xx), kp, vp), None

        (x, kpool, vpool), _ = jax.lax.scan(
            body, (x, kpool, vpool),
            (params["groups"][gi], layer0 + jnp.arange(n)))
        layer0 += n
    with jax.named_scope("lm_head"):
        h = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = (h[:, 0] @ _lm_head(cfg, params)).astype(jnp.float32)
        logits = logical(logits, "batch", "vocab")
    return logits, kpool, vpool


def _mlp_residual(cfg: ModelConfig, kind: str, p_l: Params, x: jax.Array
                  ) -> jax.Array:
    """``x`` plus the layer's MLP (or MoE) of its normed input — the
    ``mlp`` scope of the paged steps' layer body."""
    if "mlp" not in p_l:
        return x
    with jax.named_scope("mlp"):
        xn = rmsnorm(x, p_l["mlp_norm"], cfg.norm_eps)
        if kind.endswith("moe"):
            m_out, _ = mlp_mod.moe_apply(cfg, p_l["mlp"], xn)
        else:
            m_out = mlp_mod.mlp_apply(cfg, p_l["mlp"], xn)
        return x + m_out


def paged_decode_step_traced(cfg: ModelConfig, params: Params,
                             kpool: jax.Array, vpool: jax.Array,
                             block_tables: jax.Array, lengths: jax.Array,
                             write_slot: jax.Array, write_off: jax.Array,
                             tokens: jax.Array, pos: jax.Array,
                             tracer, span_args=None
                             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Instrumented twin of ``paged_decode_step``: same math, but eager
    (Python loop over layers instead of ``lax.scan``) with one tracer span
    per Attention / MLP module, device-sync'd so durations are real module
    latencies.  The engine runs this when module-level tracing is on; the
    per-head attention-latency samples it produces feed the dispatcher's
    measured snapshot (and ``profiler.fit_attention_model_from_tracer``).

    ``span_args`` (e.g. ``{"heads": ..., "cache_bytes": ...}``) is attached
    to every attention span so the profiler can fit tau(h, g) from spans.
    """
    assert supports_paged_decode(cfg), "config not supported by paged decode"
    with tracer.span("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
        x = logical(x, "batch", "seq", "embed")
        tracer.sync(x)
    layer0 = 0
    for gi, (kind, n, _win) in enumerate(layer_groups(cfg)):
        gp = params["groups"][gi]
        for li in range(n):
            p_l = jax.tree.map(lambda a: a[li], gp)
            idx = layer0 + li
            xn = rmsnorm(x, p_l["attn_norm"], cfg.norm_eps)
            with tracer.span("attention", args=span_args):
                a_out, kpool, vpool = attn.gqa_decode_paged(
                    cfg, p_l["attn"], xn, kpool, vpool, idx, block_tables,
                    lengths, write_slot, write_off, pos)
                tracer.sync(a_out)
            x = x + a_out
            if "mlp" in p_l:
                xn = rmsnorm(x, p_l["mlp_norm"], cfg.norm_eps)
                with tracer.span("mlp"):
                    if kind.endswith("moe"):
                        m_out, _ = mlp_mod.moe_apply(cfg, p_l["mlp"], xn)
                    else:
                        m_out = mlp_mod.mlp_apply(cfg, p_l["mlp"], xn)
                    tracer.sync(m_out)
                x = x + m_out
        layer0 += n
    with tracer.span("lm_head"):
        h = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = (h[:, 0] @ _lm_head(cfg, params)).astype(jnp.float32)
        logits = logical(logits, "batch", "vocab")
        tracer.sync(logits)
    return logits, kpool, vpool


def supports_paged_prefill(cfg: ModelConfig) -> bool:
    """Chunked paged prefill shares the paged-decode support envelope:
    pure-GQA full-attention stacks with a token embedding frontend."""
    return not fused_step_blocker(cfg)


def _paged_chunk_forward(cfg: ModelConfig, params: Params,
                         kpool: jax.Array, vpool: jax.Array,
                         block_tables: jax.Array, lengths: jax.Array,
                         starts: jax.Array, write_slots: jax.Array,
                         write_offs: jax.Array, tokens: jax.Array,
                         last_idx: jax.Array
                         ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Shared body of ``paged_prefill_chunk`` and ``paged_fused_step``:
    embed a (B, C) token block, scatter its K/V into the pools, run the
    chunked-prefill Pallas kernel causally through the block tables, and
    return each row's last-valid-token logits."""
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
        x = logical(x, "batch", "seq", "embed")
    C = tokens.shape[1]
    positions = starts[:, None] + jnp.arange(C)[None, :]
    layer0 = 0
    for gi, (kind, n, _win) in enumerate(layer_groups(cfg)):

        @jax.named_scope("layer")
        def body(carry, layer_in, _kind=kind):
            xx, kp, vp = carry
            p_l, idx = layer_in
            xn = rmsnorm(xx, p_l["attn_norm"], cfg.norm_eps)
            a_out, kp, vp = attn.gqa_prefill_paged(
                cfg, p_l["attn"], xn, kp, vp, idx, block_tables, lengths,
                starts, write_slots, write_offs, positions)
            xx = xx + a_out
            return (_mlp_residual(cfg, _kind, p_l, xx), kp, vp), None

        (x, kpool, vpool), _ = jax.lax.scan(
            body, (x, kpool, vpool),
            (params["groups"][gi], layer0 + jnp.arange(n)))
        layer0 += n
    with jax.named_scope("lm_head"):
        h = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        last = jnp.take_along_axis(h, last_idx[:, None, None], axis=1)
        logits = (last[:, 0] @ _lm_head(cfg, params)).astype(jnp.float32)
        logits = logical(logits, "batch", "vocab")
    return logits, kpool, vpool


def _paged_chunk_forward_traced(cfg: ModelConfig, params: Params,
                                kpool: jax.Array, vpool: jax.Array,
                                block_tables: jax.Array, lengths: jax.Array,
                                starts: jax.Array, write_slots: jax.Array,
                                write_offs: jax.Array, tokens: jax.Array,
                                last_idx: jax.Array, tracer, span_args=None
                                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Instrumented twin of ``_paged_chunk_forward`` — eager Python loop
    over layers with one device-sync'd tracer span per module."""
    with tracer.span("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
        x = logical(x, "batch", "seq", "embed")
        tracer.sync(x)
    C = tokens.shape[1]
    positions = starts[:, None] + jnp.arange(C)[None, :]
    layer0 = 0
    for gi, (kind, n, _win) in enumerate(layer_groups(cfg)):
        gp = params["groups"][gi]
        for li in range(n):
            p_l = jax.tree.map(lambda a: a[li], gp)
            idx = layer0 + li
            xn = rmsnorm(x, p_l["attn_norm"], cfg.norm_eps)
            with tracer.span("attention", args=span_args):
                a_out, kpool, vpool = attn.gqa_prefill_paged(
                    cfg, p_l["attn"], xn, kpool, vpool, idx, block_tables,
                    lengths, starts, write_slots, write_offs, positions)
                tracer.sync(a_out)
            x = x + a_out
            if "mlp" in p_l:
                xn = rmsnorm(x, p_l["mlp_norm"], cfg.norm_eps)
                with tracer.span("mlp"):
                    if kind.endswith("moe"):
                        m_out, _ = mlp_mod.moe_apply(cfg, p_l["mlp"], xn)
                    else:
                        m_out = mlp_mod.mlp_apply(cfg, p_l["mlp"], xn)
                    tracer.sync(m_out)
                x = x + m_out
        layer0 += n
    with tracer.span("lm_head"):
        h = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        last = jnp.take_along_axis(h, last_idx[:, None, None], axis=1)
        logits = (last[:, 0] @ _lm_head(cfg, params)).astype(jnp.float32)
        logits = logical(logits, "batch", "vocab")
        tracer.sync(logits)
    return logits, kpool, vpool


def paged_prefill_chunk(cfg: ModelConfig, params: Params,
                        kpool: jax.Array, vpool: jax.Array,
                        block_tables: jax.Array, lengths: jax.Array,
                        starts: jax.Array, write_slots: jax.Array,
                        write_offs: jax.Array, tokens: jax.Array,
                        last_idx: jax.Array
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Prefill one (B, C) chunk of prompt tokens against the paged pools.

    The prefill symmetric of ``paged_decode_step``: dense QKV/MLP run on
    the whole chunk, each layer scatters the chunk's K/V **directly into
    the device-resident pools** via (slot, offset) index arrays, and the
    chunked-prefill Pallas kernel attends causally through the block
    tables.  The dense ``(L, 1, max_seq, ...)`` intermediate cache of the
    ``prefill`` + ``store_prompt_request`` path never exists; per-request
    prompts are decomposed into chunks by the engine so several requests'
    chunks batch into one jitted call, shapes pow2-bucketed in (B, C,
    max_pages) to bound compiles by ``prefill_bucket_count()``.

    tokens:     (B, C) int32 chunk tokens (0-padded rows/tails)
    starts:     (B,) absolute position of tokens[:, 0] (prefix length)
    lengths:    (B,) tokens stored after this chunk's writes (0 pads rows)
    last_idx:   (B,) in-chunk index of each row's last valid token; the
                returned logits are for that token (only meaningful for
                rows whose chunk completes the prompt)
    other operands documented in ``attn.gqa_prefill_paged``.
    Returns (last-token logits (B, vocab), kpool, vpool).
    """
    assert supports_paged_prefill(cfg), \
        "config not supported by paged prefill"
    return _paged_chunk_forward(cfg, params, kpool, vpool, block_tables,
                                lengths, starts, write_slots, write_offs,
                                tokens, last_idx)


def supports_fused_step(cfg: ModelConfig) -> bool:
    """The fused prefill+decode step needs BOTH paged paths: decode rows
    are degenerate chunks through the chunked-prefill kernel family."""
    return not fused_step_blocker(cfg)


def paged_fused_step(cfg: ModelConfig, params: Params,
                     kpool: jax.Array, vpool: jax.Array,
                     block_tables: jax.Array, lengths: jax.Array,
                     starts: jax.Array, write_slots: jax.Array,
                     write_offs: jax.Array, tokens: jax.Array,
                     last_idx: jax.Array
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """ONE jitted call serving a mixed prefill+decode row batch.

    The row batch (B, C) packs two kinds of rows, distinguished purely by
    their per-row SMEM scalars — the kernel never branches on row kind:

      * **decode rows** — the degenerate chunk: one valid token (the last
        generated one) at ``starts[i] == ctx - 1``, ``lengths[i] == ctx``,
        ``last_idx[i] == 0``.  The causal mask ``k_pos <= q_pos`` plus the
        length mask reduce exactly to decode attention over the stored
        context, and the single-token K/V scatter is the decode-step pool
        write.
      * **prefill rows** — a ≤C-token prompt chunk, exactly as in
        ``paged_prefill_chunk``.

    Because decode is the C=1 special case of the chunk math, the fused
    step shares ``_paged_chunk_forward`` with the prefill path: same layer
    scan, same scatter, same Pallas kernel — so token streams are
    bit-identical to the two-call split schedule while the engine pays ONE
    dispatch per iteration instead of two.  Shapes are pow2-bucketed in
    (B, C, max_pages); the compile universe is
    ``InferenceEngine.fused_bucket_count()``.

    Operand layouts are identical to ``paged_prefill_chunk``; padded rows
    carry ``lengths == 0`` and write to the sink slot.
    Returns (last-valid-token logits (B, vocab), kpool, vpool).
    """
    assert supports_fused_step(cfg), "config not supported by fused step"
    return _paged_chunk_forward(cfg, params, kpool, vpool, block_tables,
                                lengths, starts, write_slots, write_offs,
                                tokens, last_idx)


def paged_fused_step_traced(cfg: ModelConfig, params: Params,
                            kpool: jax.Array, vpool: jax.Array,
                            block_tables: jax.Array, lengths: jax.Array,
                            starts: jax.Array, write_slots: jax.Array,
                            write_offs: jax.Array, tokens: jax.Array,
                            last_idx: jax.Array, tracer, span_args=None
                            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Instrumented twin of ``paged_fused_step`` — eager layer loop with
    per-module Attention / MLP spans.  ``span_args`` should carry the
    per-phase row/token split (``decode_rows``/``prefill_tokens``...) so
    span consumers can attribute one call's time to both phases."""
    assert supports_fused_step(cfg), "config not supported by fused step"
    return _paged_chunk_forward_traced(cfg, params, kpool, vpool,
                                       block_tables, lengths, starts,
                                       write_slots, write_offs, tokens,
                                       last_idx, tracer, span_args)


def paged_prefill_chunk_traced(cfg: ModelConfig, params: Params,
                               kpool: jax.Array, vpool: jax.Array,
                               block_tables: jax.Array, lengths: jax.Array,
                               starts: jax.Array, write_slots: jax.Array,
                               write_offs: jax.Array, tokens: jax.Array,
                               last_idx: jax.Array, tracer, span_args=None
                               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Instrumented twin of ``paged_prefill_chunk`` — eager layer loop with
    per-module Attention / MLP spans (see ``paged_decode_step_traced``)."""
    assert supports_paged_prefill(cfg), \
        "config not supported by paged prefill"
    return _paged_chunk_forward_traced(cfg, params, kpool, vpool,
                                       block_tables, lengths, starts,
                                       write_slots, write_offs, tokens,
                                       last_idx, tracer, span_args)


def _pool_exchange_in(kpools, vpools, anchor: int, anchor_sink: int,
                      g_dev: jax.Array, g_src: jax.Array, g_dst: jax.Array
                      ) -> Tuple[jax.Array, jax.Array]:
    """Gather remote pages into the anchor pool's staging region.

    The paged Pallas kernels consume ONE pool pair, so batch rows whose
    pages live in another device's pool shard are served by copying those
    pages into the anchor's staging slots first — inside the same jitted
    step.  ``g_dev/g_src/g_dst`` are pow2-bucket-padded lane arrays
    (``PoolStepPlan.exchange_arrays``); the loop over pool keys is a
    static Python loop (the pool dict is part of the trace), and each
    device contributes one masked gather+scatter: lanes belonging to
    other devices degrade to sink-to-sink copies via ``jnp.where`` (the
    remote sink read, the anchor sink written — both garbage by
    construction, never read through a length mask).  Zero-lane arrays
    (the single-device common case) skip the copies entirely.
    Returns the updated anchor (kpool, vpool)."""
    ak, av = kpools[anchor], vpools[anchor]
    if g_dev.shape[0] == 0:
        return ak, av
    with jax.named_scope("exchange_in"):
        for dev in sorted(d for d in kpools if d != anchor):
            kp, vp = kpools[dev], vpools[dev]
            rsink = kp.shape[1] - 1
            m = g_dev == dev
            src = jnp.where(m, g_src, rsink)
            dst = jnp.where(m, g_dst, anchor_sink)
            ak = ak.at[:, dst].set(kp[:, src])
            av = av.at[:, dst].set(vp[:, src])
    return ak, av


def _pool_exchange_out(kpools, vpools, anchor: int, anchor_sink: int,
                      w_dev: jax.Array, w_src: jax.Array, w_dst: jax.Array):
    """Write dirty staged pages back to their owning pool shards — the
    inverse of ``_pool_exchange_in``, applied after the forward pass has
    scattered new K/V into the staging copies.  Masked lanes write the
    remote pool's own sink from the anchor's sink.  Returns updated
    (kpools, vpools) dicts."""
    kpools = dict(kpools)
    vpools = dict(vpools)
    if w_dev.shape[0] == 0:
        return kpools, vpools
    ak, av = kpools[anchor], vpools[anchor]
    with jax.named_scope("exchange_out"):
        for dev in sorted(d for d in kpools if d != anchor):
            kp, vp = kpools[dev], vpools[dev]
            rsink = kp.shape[1] - 1
            m = w_dev == dev
            src = jnp.where(m, w_src, anchor_sink)
            dst = jnp.where(m, w_dst, rsink)
            kpools[dev] = kp.at[:, dst].set(ak[:, src])
            vpools[dev] = vp.at[:, dst].set(av[:, src])
    return kpools, vpools


def sharded_decode_step(cfg: ModelConfig, params: Params,
                        kpools, vpools, anchor: int, anchor_sink: int,
                        g_dev: jax.Array, g_src: jax.Array,
                        g_dst: jax.Array, w_dev: jax.Array,
                        w_src: jax.Array, w_dst: jax.Array,
                        block_tables: jax.Array, lengths: jax.Array,
                        write_slot: jax.Array, write_off: jax.Array,
                        tokens: jax.Array, pos: jax.Array):
    """``paged_decode_step`` over per-device pool shards.

    Block tables / write slots are ANCHOR-pool indices built by
    ``PoolStepPlan``; remote pages are staged in by ``_pool_exchange_in``,
    the single-pool decode step runs against the anchor pool, and dirty
    staged pages (the decode-token write page of remote rows) are written
    back — all inside one jit.  ``anchor``/``anchor_sink`` are static.
    Returns (logits, kpools, vpools) with the pool dicts as pytrees."""
    kpools = dict(kpools)
    vpools = dict(vpools)
    ak, av = _pool_exchange_in(kpools, vpools, anchor, anchor_sink,
                               g_dev, g_src, g_dst)
    logits, ak, av = paged_decode_step(cfg, params, ak, av, block_tables,
                                       lengths, write_slot, write_off,
                                       tokens, pos)
    kpools[anchor], vpools[anchor] = ak, av
    kpools, vpools = _pool_exchange_out(kpools, vpools, anchor,
                                        anchor_sink, w_dev, w_src, w_dst)
    return logits, kpools, vpools


def sharded_decode_step_traced(cfg: ModelConfig, params: Params,
                               kpools, vpools, anchor: int,
                               anchor_sink: int, g_dev, g_src, g_dst,
                               w_dev, w_src, w_dst, block_tables, lengths,
                               write_slot, write_off, tokens, pos,
                               tracer, span_args=None):
    """Instrumented twin of ``sharded_decode_step`` (eager exchange around
    the traced single-pool body)."""
    kpools = dict(kpools)
    vpools = dict(vpools)
    ak, av = _pool_exchange_in(kpools, vpools, anchor, anchor_sink,
                               g_dev, g_src, g_dst)
    logits, ak, av = paged_decode_step_traced(
        cfg, params, ak, av, block_tables, lengths, write_slot, write_off,
        tokens, pos, tracer, span_args)
    kpools[anchor], vpools[anchor] = ak, av
    kpools, vpools = _pool_exchange_out(kpools, vpools, anchor,
                                        anchor_sink, w_dev, w_src, w_dst)
    return logits, kpools, vpools


def sharded_prefill_chunk(cfg: ModelConfig, params: Params,
                          kpools, vpools, anchor: int, anchor_sink: int,
                          g_dev, g_src, g_dst, w_dev, w_src, w_dst,
                          block_tables, lengths, starts, write_slots,
                          write_offs, tokens, last_idx):
    """``paged_prefill_chunk`` over per-device pool shards: stage remote
    pages in, run the single-pool chunk forward on the anchor pool, write
    dirty staged pages back — one jit (see ``sharded_decode_step``)."""
    kpools = dict(kpools)
    vpools = dict(vpools)
    ak, av = _pool_exchange_in(kpools, vpools, anchor, anchor_sink,
                               g_dev, g_src, g_dst)
    logits, ak, av = paged_prefill_chunk(cfg, params, ak, av, block_tables,
                                         lengths, starts, write_slots,
                                         write_offs, tokens, last_idx)
    kpools[anchor], vpools[anchor] = ak, av
    kpools, vpools = _pool_exchange_out(kpools, vpools, anchor,
                                        anchor_sink, w_dev, w_src, w_dst)
    return logits, kpools, vpools


def sharded_prefill_chunk_traced(cfg: ModelConfig, params: Params,
                                 kpools, vpools, anchor: int,
                                 anchor_sink: int, g_dev, g_src, g_dst,
                                 w_dev, w_src, w_dst, block_tables,
                                 lengths, starts, write_slots, write_offs,
                                 tokens, last_idx, tracer, span_args=None):
    """Instrumented twin of ``sharded_prefill_chunk``."""
    kpools = dict(kpools)
    vpools = dict(vpools)
    ak, av = _pool_exchange_in(kpools, vpools, anchor, anchor_sink,
                               g_dev, g_src, g_dst)
    logits, ak, av = paged_prefill_chunk_traced(
        cfg, params, ak, av, block_tables, lengths, starts, write_slots,
        write_offs, tokens, last_idx, tracer, span_args)
    kpools[anchor], vpools[anchor] = ak, av
    kpools, vpools = _pool_exchange_out(kpools, vpools, anchor,
                                        anchor_sink, w_dev, w_src, w_dst)
    return logits, kpools, vpools


def sharded_fused_step(cfg: ModelConfig, params: Params,
                       kpools, vpools, anchor: int, anchor_sink: int,
                       g_dev, g_src, g_dst, w_dev, w_src, w_dst,
                       block_tables, lengths, starts, write_slots,
                       write_offs, tokens, last_idx):
    """``paged_fused_step`` over per-device pool shards (mixed decode +
    prefill rows; see ``sharded_decode_step`` for the exchange scheme)."""
    assert supports_fused_step(cfg), "config not supported by fused step"
    kpools = dict(kpools)
    vpools = dict(vpools)
    ak, av = _pool_exchange_in(kpools, vpools, anchor, anchor_sink,
                               g_dev, g_src, g_dst)
    logits, ak, av = paged_fused_step(cfg, params, ak, av, block_tables,
                                      lengths, starts, write_slots,
                                      write_offs, tokens, last_idx)
    kpools[anchor], vpools[anchor] = ak, av
    kpools, vpools = _pool_exchange_out(kpools, vpools, anchor,
                                        anchor_sink, w_dev, w_src, w_dst)
    return logits, kpools, vpools


def sharded_fused_step_traced(cfg: ModelConfig, params: Params,
                              kpools, vpools, anchor: int,
                              anchor_sink: int, g_dev, g_src, g_dst,
                              w_dev, w_src, w_dst, block_tables, lengths,
                              starts, write_slots, write_offs, tokens,
                              last_idx, tracer, span_args=None):
    """Instrumented twin of ``sharded_fused_step``."""
    kpools = dict(kpools)
    vpools = dict(vpools)
    ak, av = _pool_exchange_in(kpools, vpools, anchor, anchor_sink,
                               g_dev, g_src, g_dst)
    logits, ak, av = paged_fused_step_traced(
        cfg, params, ak, av, block_tables, lengths, starts, write_slots,
        write_offs, tokens, last_idx, tracer, span_args)
    kpools[anchor], vpools[anchor] = ak, av
    kpools, vpools = _pool_exchange_out(kpools, vpools, anchor,
                                        anchor_sink, w_dev, w_src, w_dst)
    return logits, kpools, vpools


def decode_step(cfg: ModelConfig, params: Params, cache: Cache,
                tokens: jax.Array) -> Tuple[jax.Array, Cache]:
    """One decode step for all sequences.  tokens: (B, 1) int32.

    ``cfg.decode_impl`` selects the cache-update strategy:
      "carry"   — full stacked caches carried through the scan, token
                  scatter in place (no per-step cache copy);
      "stacked" — caches as scan xs/ys (baseline; copies each layer slice
                  every step — kept for the §Perf before/after record).
    """
    assert not cfg.is_encoder_only, "encoder-only archs have no decode step"
    x = jnp.take(params["embed"], tokens, axis=0)
    x = logical(x, "batch", "seq", "embed")
    pos = cache["pos"]
    groups = layer_groups(cfg)
    new_caches = []
    for gi, (kind, n, win) in enumerate(groups):
        if cfg.decode_impl == "carry":
            def body_c(carry, layer_in, _kind=kind, _win=win):
                xx, caches = carry
                p_l, idx = layer_in
                xx, caches = _apply_decode_carry(cfg, _kind, p_l, caches,
                                                 idx, xx, pos, _win)
                return (xx, caches), None

            (x, group_cache), _ = jax.lax.scan(
                body_c, (x, cache["groups"][gi]),
                (params["groups"][gi], jnp.arange(n)))
            new_caches.append(group_cache)
        else:
            def body(xx, layer_in, _kind=kind, _win=win):
                p_l, cache_l = layer_in
                xx, new_cache_l = _apply_decode(cfg, _kind, p_l, cache_l, xx,
                                                pos, _win)
                return xx, new_cache_l

            x, ys = jax.lax.scan(body, x,
                                 (params["groups"][gi],
                                  cache["groups"][gi]))
            new_caches.append(ys)
    h = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = (h[:, 0] @ _lm_head(cfg, params)).astype(jnp.float32)
    logits = logical(logits, "batch", "vocab")
    return logits, {"groups": new_caches, "pos": pos + 1}
