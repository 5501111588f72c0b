"""Attention blocks: GQA/MHA (with qk-norm, qkv-bias, RoPE, sliding window)
and MLA (DeepSeek-V3 latent attention, absorbed-weight decode path).

Each block exposes:
  init(cfg, key)                      -> per-layer params (unstacked)
  cache_init(cfg, batch, max_seq)     -> per-layer cache pytree
  full(cfg, p, x, positions, window)  -> (out, cache_entries)   # train/prefill
  decode(cfg, p, x, cache, pos, window) -> (out, new_cache)     # one token

Caches are per-layer dicts with leading (B, S, ...); the transformer stacks
them with a leading layer axis for lax.scan.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import logical
from repro.models.common import (apply_rope, cdtype, chunked_attention,
                                 decode_attention, dense_init, rmsnorm)


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

def gqa_init(cfg, key) -> Dict:
    dt = cdtype(cfg)
    d, H, Hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, (H * dh,), dt),
        "wk": dense_init(ks[1], d, (Hkv * dh,), dt),
        "wv": dense_init(ks[2], d, (Hkv * dh,), dt),
        "wo": dense_init(ks[3], H * dh, (d,), dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * dh,), dt)
        p["bk"] = jnp.zeros((Hkv * dh,), dt)
        p["bv"] = jnp.zeros((Hkv * dh,), dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((dh,), jnp.float32)
        p["k_norm"] = jnp.ones((dh,), jnp.float32)
    return p


def gqa_cache_init(cfg, batch: int, max_seq: int) -> Dict:
    dt = jnp.dtype(cfg.kv_dtype)
    Hkv, dh = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, max_seq, Hkv, dh), dt),
        "v": jnp.zeros((batch, max_seq, Hkv, dh), dt),
    }


def _gqa_qkv(cfg, p, x, positions):
    B, S, _ = x.shape
    H, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, H, dh)
    k = k.reshape(B, S, Hkv, dh)
    v = v.reshape(B, S, Hkv, dh)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = logical(q, "batch", "seq", "heads", None)
    # the key/value SEQUENCE carries the kv_seq axis: for archs whose
    # kv-head count does not divide the model axis this shards prefill
    # attention by sequence (partial-softmax psum) instead of replicating
    # the whole score tensor per model rank (§Perf hymba iteration 2)
    k = logical(k, "batch", "kv_seq", "kv_heads", None)
    v = logical(v, "batch", "kv_seq", "kv_heads", None)
    return q, k, v


def gqa_full(cfg, p, x, positions, window=0) -> Tuple[jax.Array, Dict]:
    """Full-sequence attention (training / prefill). Returns cache entries
    in the cache storage dtype (f8 when kv_cache_dtype is set)."""
    q, k, v = _gqa_qkv(cfg, p, x, positions)
    out = chunked_attention(q, k, v, causal=cfg.causal, window=window,
                            chunk=cfg.scan_q_chunk)
    out = out.reshape(x.shape[0], x.shape[1], -1) @ p["wo"]
    cdt = jnp.dtype(cfg.kv_dtype)
    return logical(out, "batch", "seq", "embed"), \
        {"k": k.astype(cdt), "v": v.astype(cdt)}


def gqa_decode(cfg, p, x, cache: Dict, pos: jax.Array, window=0
               ) -> Tuple[jax.Array, Dict]:
    """x: (B,1,d); pos: (B,) absolute positions of the new token."""
    B = x.shape[0]
    q, k, v = _gqa_qkv(cfg, p, x, pos[:, None])
    bidx = jnp.arange(B)
    cdt = cache["k"].dtype
    k_cache = cache["k"].at[bidx, pos].set(k[:, 0].astype(cdt))
    v_cache = cache["v"].at[bidx, pos].set(v[:, 0].astype(cdt))
    k_cache = logical(k_cache, "batch", "kv_seq", "kv_heads", None)
    v_cache = logical(v_cache, "batch", "kv_seq", "kv_heads", None)
    out = decode_attention(q, k_cache.astype(q.dtype),
                           v_cache.astype(q.dtype), kv_len=pos + 1,
                           window=window)
    out = out.reshape(B, 1, -1) @ p["wo"]
    return logical(out, "batch", "seq", "embed"), {"k": k_cache, "v": v_cache}


def gqa_decode_carry(cfg, p, x, k_full, v_full, idx, pos: jax.Array, window=0
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """In-place decode against the full stacked cache (L,B,S,Hkv,dh).

    Writes the new token's K/V with a scatter at (idx, b, pos_b) — only
    B*Hkv*dh elements touch HBM — then attends against the dynamic layer
    slice.  This avoids the per-step full-slice copy of the scan-ys variant
    (§Perf: decode cache traffic halves)."""
    B = x.shape[0]
    q, k, v = _gqa_qkv(cfg, p, x, pos[:, None])
    bidx = jnp.arange(B)
    cdt = k_full.dtype                       # may be f8 (kv_cache_dtype)
    k_full = k_full.at[idx, bidx, pos].set(k[:, 0].astype(cdt))
    v_full = v_full.at[idx, bidx, pos].set(v[:, 0].astype(cdt))
    k_cache = logical(k_full[idx], "batch", "kv_seq", "kv_heads", None)
    v_cache = logical(v_full[idx], "batch", "kv_seq", "kv_heads", None)
    out = decode_attention(q, k_cache.astype(q.dtype),
                           v_cache.astype(q.dtype), kv_len=pos + 1,
                           window=window)
    out = out.reshape(B, 1, -1) @ p["wo"]
    return logical(out, "batch", "seq", "embed"), k_full, v_full


def gqa_decode_paged(cfg, p, x, kpool, vpool, idx, block_tables, lengths,
                     write_slot, write_off, pos: jax.Array
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Decode against the device-resident head-granular paged pool.

    The new token's K/V is scattered straight into this layer's pool slice
    (B*Hkv*dh elements touch memory — no dense cache materialization), then
    the Pallas paged-attention kernel consumes the pool through the block
    tables.  Padded batch rows carry write_slot == sink and lengths == 0, so
    their writes land in the sink slot and their outputs are discarded.

    x:            (B, 1, d) new-token hidden states
    kpool/vpool:  (L, slots, page, dh) full stacked pools (scan carry)
    idx:          layer index into the pool's leading axis
    block_tables: (B, Hkv, max_pages) int32 slot ids
    lengths:      (B,) int32 valid tokens INCLUDING the one written here
    write_slot:   (B, Hkv) int32 slot for the new token's page
    write_off:    (B,) int32 offset of the new token within its page
    pos:          (B,) int32 absolute position of the new token (RoPE)
    """
    from repro.kernels.paged_attention import paged_attention
    B = x.shape[0]
    H, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope("qkv"):
        q, k, v = _gqa_qkv(cfg, p, x, pos[:, None])
    with jax.named_scope("kv_write"):
        cdt = kpool.dtype                    # may be f8 (kv_cache_dtype)
        kpool = kpool.at[idx, write_slot, write_off[:, None]].set(
            k[:, 0].astype(cdt))
        vpool = vpool.at[idx, write_slot, write_off[:, None]].set(
            v[:, 0].astype(cdt))
    with jax.named_scope("attn"):
        # group-major head fold (H = Hkv * r), matching attention_core
        qg = q[:, 0].reshape(B, Hkv, H // Hkv, dh)
        out = paged_attention(qg, kpool[idx].astype(q.dtype),
                              vpool[idx].astype(q.dtype), block_tables,
                              lengths)
        out = out.reshape(B, 1, H * dh) @ p["wo"]
    return logical(out, "batch", "seq", "embed"), kpool, vpool


def write_chunk_pages(pool, idx, write_slots, write_offs, kv) -> jax.Array:
    """``pool.at[idx, write_slots, write_offs[:, None, :]].set(kv)`` for a
    chunk of contiguous token positions, written page by page.

    Each row's chunk of C tokens spans at most ``(C + page - 2) // page +
    1`` pages per KV group.  Those pages are gathered whole from layer
    ``idx``, the chunk's tokens are merged in at their offsets (earlier
    tokens of a partly filled page kept), and whole ``(page, dh)`` tiles
    are scattered back.  A token merges only where its own write slot is
    its page's slot, so padded tokens (sink-slotted) land in the sink
    alone; local pages past a row's chunk repeat its last page, slot and
    content alike, so repeated indices write equal values.

    pool:        (L, slots, page, dh) stacked pool
    write_slots: (B, Hkv, C) int32 slot of each chunk token's page
    write_offs:  (B, C) int32 offset of each chunk token within its page;
                 a row's valid tokens are a prefix at consecutive positions
    kv:          (B, C, Hkv, dh) the chunk's K or V, cast to the pool's
                 dtype (f8 where kv_cache_dtype sets it)
    """
    B, Hkv, C = write_slots.shape
    page = pool.shape[2]
    n_pages = (C + page - 2) // page + 1
    off0 = write_offs[:, :1]                                      # (B, 1)
    last = (off0 + C - 1) // page
    j = jnp.minimum(jnp.arange(n_pages)[None, :], last)           # (B, P)
    first = jnp.maximum(j * page - off0, 0)                       # (B, P)
    page_slots = jnp.take_along_axis(
        write_slots, jnp.broadcast_to(first[:, None], (B, Hkv, n_pages)),
        axis=2)                                                   # (B,Hkv,P)
    tok = (j[:, :, None] * page + jnp.arange(page)
           - off0[:, :, None]).reshape(B, n_pages * page)         # (B, P*pg)
    in_chunk = (tok >= 0) & (tok < C)
    tok = jnp.clip(tok, 0, C - 1)
    tok_slots = jnp.take_along_axis(
        write_slots, jnp.broadcast_to(tok[:, None], (B, Hkv, tok.shape[1])),
        axis=2).reshape(B, Hkv, n_pages, page)
    merge = (in_chunk.reshape(B, 1, n_pages, page)
             & (tok_slots == page_slots[..., None]))
    new = jnp.take_along_axis(jnp.swapaxes(kv, 1, 2).astype(pool.dtype),
                              tok[:, None, :, None], axis=2)
    new = new.reshape(B, Hkv, n_pages, page, -1)
    pages = jnp.where(merge[..., None], new, pool[idx, page_slots])
    return pool.at[idx, page_slots].set(pages)


def gqa_prefill_paged(cfg, p, x, kpool, vpool, idx, block_tables, lengths,
                      starts, write_slots, write_offs, positions
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Prefill one prompt CHUNK against the device-resident paged pool —
    the prefill symmetric of ``gqa_decode_paged``.

    The chunk's K/V is written straight into this layer's pool slice
    (no dense max_seq cache is ever materialized), then the
    chunked-prefill Pallas kernel attends causally against the
    pool through the block tables: each chunk token sees the request's
    stored prefix plus the in-chunk tokens at or before its own position.
    Padded rows carry lengths == 0 and padded tokens write to the sink
    slot, so garbage never reaches a real page or a used output.

    The write goes in whole pages (``write_chunk_pages``), not token rows:
    a scatter of C*Hkv rows of dh elements into the scan-carried stacked
    pool made XLA relayout the whole pool out and back around it, twice
    per pool and layer, at head_dim 64.  Page tiles scatter in place in
    the pool's own layout at every head dim.

    x:            (B, C, d) chunk hidden states
    kpool/vpool:  (L, slots, page, dh) full stacked pools (scan carry)
    idx:          layer index into the pool's leading axis
    block_tables: (B, Hkv, max_pages) int32 slot ids
    lengths:      (B,) int32 tokens stored INCLUDING this chunk's writes
    starts:       (B,) int32 absolute position of each chunk's first token
    write_slots:  (B, Hkv, C) int32 slot for each chunk token's page
    write_offs:   (B, C) int32 offset of each chunk token within its page
    positions:    (B, C) int32 absolute token positions (RoPE)
    """
    from repro.kernels.paged_attention import paged_prefill_attention
    B, C, _ = x.shape
    H, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope("qkv"):
        q, k, v = _gqa_qkv(cfg, p, x, positions)
    with jax.named_scope("kv_write"):
        kpool = write_chunk_pages(kpool, idx, write_slots, write_offs, k)
        vpool = write_chunk_pages(vpool, idx, write_slots, write_offs, v)
    with jax.named_scope("attn"):
        # group-major head fold (H = Hkv * r), matching attention_core
        qg = q.reshape(B, C, Hkv, H // Hkv, dh).transpose(0, 2, 1, 3, 4)
        out = paged_prefill_attention(qg, kpool[idx].astype(q.dtype),
                                      vpool[idx].astype(q.dtype),
                                      block_tables, lengths, starts)
        out = out.transpose(0, 2, 1, 3, 4).reshape(B, C, H * dh) @ p["wo"]
    return logical(out, "batch", "seq", "embed"), kpool, vpool


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3)
# ---------------------------------------------------------------------------

def mla_init(cfg, key) -> Dict:
    dt = cdtype(cfg)
    d, H = cfg.d_model, cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wdq": dense_init(ks[0], d, (cfg.q_lora_rank,), dt),
        "q_norm": jnp.ones((cfg.q_lora_rank,), jnp.float32),
        "wuq": dense_init(ks[1], cfg.q_lora_rank, (H * (dn + dr),), dt),
        "wdkv": dense_init(ks[2], d, (cfg.kv_lora_rank + dr,), dt),
        "kv_norm": jnp.ones((cfg.kv_lora_rank,), jnp.float32),
        "wuk": dense_init(ks[3], cfg.kv_lora_rank, (H, dn), dt),
        "wuv": dense_init(ks[4], cfg.kv_lora_rank, (H, dv), dt),
        "wo": dense_init(ks[5], H * dv, (d,), dt),
    }


def mla_cache_init(cfg, batch: int, max_seq: int) -> Dict:
    dt = cdtype(cfg)
    return {
        "ckv": jnp.zeros((batch, max_seq, cfg.kv_lora_rank), dt),
        "krope": jnp.zeros((batch, max_seq, cfg.qk_rope_head_dim), dt),
    }


def _mla_q(cfg, p, x, positions):
    B, S, _ = x.shape
    H = cfg.n_heads
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    cq = rmsnorm(x @ p["wdq"], p["q_norm"], cfg.norm_eps)
    q = (cq @ p["wuq"]).reshape(B, S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return logical(q_nope, "batch", "seq", "heads", None), \
        logical(q_rope, "batch", "seq", "heads", None)


def _mla_latent(cfg, p, x, positions):
    """MLA latent is kv_seq-annotated (seq over model for MLA archs).

    §Perf deepseek train iteration 3 (REFUTED): replacing this with a
    token-following ('seq'=replicated) annotation — reasoning that the
    128-head attention is head-sharded anyway — RAISED the collective term
    366 s -> 443 s: the explicit model-replication constraint forces extra
    reshards around the per-head K/V expansion.  kv_seq kept."""
    ckv_kr = x @ p["wdkv"]
    ckv = rmsnorm(ckv_kr[..., :cfg.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    krope = ckv_kr[..., cfg.kv_lora_rank:]
    krope = apply_rope(krope[:, :, None, :], positions,
                       cfg.rope_theta)[:, :, 0, :]
    return logical(ckv, "batch", "kv_seq", "kv_lora"), \
        logical(krope, "batch", "kv_seq", None)


def mla_full(cfg, p, x, positions, window=0) -> Tuple[jax.Array, Dict]:
    """Non-absorbed form: materialize per-head K/V from the latent (prefill)."""
    B, S, _ = x.shape
    H = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q_nope, q_rope = _mla_q(cfg, p, x, positions)
    ckv, krope = _mla_latent(cfg, p, x, positions)
    k_nope = jnp.einsum("bsc,chd->bshd", ckv, p["wuk"])
    v = jnp.einsum("bsc,chd->bshd", ckv, p["wuv"])
    k = jnp.concatenate([k_nope,
                         jnp.broadcast_to(krope[:, :, None, :],
                                          (B, S, H, dr))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = chunked_attention(q, k, v, causal=cfg.causal, window=window,
                            chunk=cfg.scan_q_chunk)
    out = out.reshape(B, S, H * dv) @ p["wo"]
    return logical(out, "batch", "seq", "embed"), {"ckv": ckv, "krope": krope}


def mla_decode(cfg, p, x, cache: Dict, pos: jax.Array, window=0
               ) -> Tuple[jax.Array, Dict]:
    """Absorbed-weight decode: scores and values computed directly against
    the latent cache — per-head K/V never materialized (DeepSeek-V3 §2.1)."""
    B = x.shape[0]
    H = cfg.n_heads
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q_nope, q_rope = _mla_q(cfg, p, x, pos[:, None])
    ckv_new, krope_new = _mla_latent(cfg, p, x, pos[:, None])
    bidx = jnp.arange(B)
    ckv = cache["ckv"].at[bidx, pos].set(ckv_new[:, 0])
    krope = cache["krope"].at[bidx, pos].set(krope_new[:, 0])
    ckv = logical(ckv, "batch", "kv_seq", "kv_lora")
    krope = logical(krope, "batch", "kv_seq", None)

    # absorbed q: (B,1,H,dn) x (c,H,dn) -> (B,1,H,c)
    q_abs = jnp.einsum("bqhd,chd->bqhc", q_nope, p["wuk"])
    scale = 1.0 / math.sqrt(dn + dr)
    s_nope = jnp.einsum("bqhc,bsc->bhqs", q_abs, ckv,
                        preferred_element_type=jnp.float32)
    s_rope = jnp.einsum("bqhd,bsd->bhqs", q_rope, krope,
                        preferred_element_type=jnp.float32)
    scores = (s_nope + s_rope) * scale
    S = ckv.shape[1]
    valid = jnp.arange(S)[None, :] < (pos + 1)[:, None]
    from repro.models.common import NEG_INF
    scores = scores + jnp.where(valid, 0.0, NEG_INF)[:, None, None, :]
    w = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(ckv.dtype)
    out_lat = jnp.einsum("bhqs,bsc->bqhc", w, ckv)
    out = jnp.einsum("bqhc,chd->bqhd", out_lat, p["wuv"])
    out = out.reshape(B, 1, -1) @ p["wo"]
    return logical(out, "batch", "seq", "embed"), {"ckv": ckv, "krope": krope}


def mla_decode_carry(cfg, p, x, ckv_full, krope_full, idx, pos: jax.Array,
                     window=0):
    """Absorbed-weight decode against the full stacked latent cache
    (L,B,S,c) with in-place token scatter (see gqa_decode_carry)."""
    B = x.shape[0]
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q_nope, q_rope = _mla_q(cfg, p, x, pos[:, None])
    ckv_new, krope_new = _mla_latent(cfg, p, x, pos[:, None])
    bidx = jnp.arange(B)
    ckv_full = ckv_full.at[idx, bidx, pos].set(ckv_new[:, 0])
    krope_full = krope_full.at[idx, bidx, pos].set(krope_new[:, 0])
    ckv = logical(ckv_full[idx], "batch", "kv_seq", "kv_lora")
    krope = logical(krope_full[idx], "batch", "kv_seq", None)

    q_abs = jnp.einsum("bqhd,chd->bqhc", q_nope, p["wuk"])
    scale = 1.0 / math.sqrt(dn + dr)
    s_nope = jnp.einsum("bqhc,bsc->bhqs", q_abs, ckv,
                        preferred_element_type=jnp.float32)
    s_rope = jnp.einsum("bqhd,bsd->bhqs", q_rope, krope,
                        preferred_element_type=jnp.float32)
    scores = (s_nope + s_rope) * scale
    S = ckv.shape[1]
    valid = jnp.arange(S)[None, :] < (pos + 1)[:, None]
    from repro.models.common import NEG_INF
    scores = scores + jnp.where(valid, 0.0, NEG_INF)[:, None, None, :]
    w = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(ckv.dtype)
    out_lat = jnp.einsum("bhqs,bsc->bqhc", w, ckv)
    out = jnp.einsum("bqhc,chd->bqhd", out_lat, p["wuv"])
    out = out.reshape(B, 1, -1) @ p["wo"]
    return logical(out, "batch", "seq", "embed"), ckv_full, krope_full
