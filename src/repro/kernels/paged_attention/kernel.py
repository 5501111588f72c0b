"""Head-granular paged decode attention — Pallas TPU kernel.

The TPU adaptation of Hetis' §6 cache layer (DESIGN §2): vLLM's CUDA kernel
fetches (seq, pos, head)-indexed blocks with a warp per head; on TPU the
same indirection is expressed through **scalar prefetch** — the block table
lives in SMEM and the K/V ``index_map`` dereferences it, so the HBM->VMEM
DMA pipeline streams exactly the pages owned by this (sequence, kv-head
group), wherever the Dispatcher placed them.

Grid (B, Hkv, max_pages): pages are the sequential axis; flash-style (m, l,
acc) scratch carries across pages; pages past a sequence's length are
zero-skipped (pl.when).  Per-step VMEM: one (page, dh) K tile + V tile +
(r, dh) q/acc — a few hundred KB at page=64, dh=128.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(tables_ref, lengths_ref,          # scalar prefetch (SMEM)
                  q_ref, k_ref, v_ref, o_ref,       # VMEM blocks
                  m_scr, l_scr, acc_scr, *,
                  page: int, max_pages: int):
    b = pl.program_id(0)
    ip = pl.program_id(2)

    @pl.when(ip == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lengths_ref[b]
    base = ip * page

    @pl.when(base < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # (r, dh)
        k = k_ref[0].astype(jnp.float32)                 # (page, dh)
        scale = 1.0 / math.sqrt(q.shape[-1])
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)

        m_prev = m_scr[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_cur[:, None])
        alpha = jnp.exp(m_prev - m_cur)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0],
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + pv
        m_scr[...] = m_cur

    @pl.when(ip == max_pages - 1)
    def _finalize():
        l = l_scr[...]
        safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0] = (acc_scr[...] / safe[:, None]).astype(o_ref.dtype)


def _paged_prefill_kernel(tables_ref, lengths_ref, starts_ref,  # SMEM
                          q_ref, k_ref, v_ref, o_ref,           # VMEM blocks
                          m_scr, l_scr, acc_scr, *,
                          page: int, max_pages: int, r: int):
    """Chunked-prefill generalization of ``_paged_kernel``: the query block
    carries a whole (C, r) chunk folded to C*r rows, and the causal mask is
    per query row — row j (token c = j // r at absolute position
    starts[b] + c) sees keys at positions <= its own.  Pages wholly in a
    row's future contribute exp-weight 0 via the mask multiply, so the
    flash (m, l, acc) carry stays exact without a per-row page skip."""
    b = pl.program_id(0)
    ip = pl.program_id(2)

    @pl.when(ip == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lengths_ref[b]
    start = starts_ref[b]
    base = ip * page

    @pl.when(base < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # (C*r, dh)
        k = k_ref[0].astype(jnp.float32)                 # (page, dh)
        scale = 1.0 / math.sqrt(q.shape[-1])
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        k_pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        q_pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // r
        ok = (k_pos <= q_pos) & (k_pos < length)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        # masked entries give s - m_cur == 0 when a row has seen no key yet
        # (m_cur still NEG_INF); the mask multiply zeroes them exactly.
        p = jnp.exp(s - m_cur[:, None]) * ok.astype(jnp.float32)
        alpha = jnp.exp(m_prev - m_cur)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0],
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + pv
        m_scr[...] = m_cur

    @pl.when(ip == max_pages - 1)
    def _finalize():
        l = l_scr[...]
        safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0] = (acc_scr[...] / safe[:, None]).astype(o_ref.dtype)


def paged_prefill_attention_kernel(q: jax.Array, kpool: jax.Array,
                                   vpool: jax.Array, block_tables: jax.Array,
                                   lengths: jax.Array, starts: jax.Array,
                                   r: int, interpret: bool = False
                                   ) -> jax.Array:
    """q: (B, Hkv, C*r, dh) chunk queries, (C, r) folded row-major;
    kpool/vpool: (slots, page, dh); block_tables: (B, Hkv, max_pages) int32;
    lengths: (B,) int32 keys visible AFTER the chunk's writes (0 pads rows);
    starts: (B,) int32 absolute position of each row's first chunk token."""
    B, Hkv, Cr, dh = q.shape
    slots, page, _ = kpool.shape
    max_pages = block_tables.shape[-1]

    kernel = functools.partial(_paged_prefill_kernel, page=page,
                               max_pages=max_pages, r=r)

    def q_map(b, h, p, tables, lengths, starts):
        return (b, h, 0, 0)

    def kv_map(b, h, p, tables, lengths, starts):
        return (tables[b, h, p], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Hkv, max_pages),
        in_specs=[
            pl.BlockSpec((1, 1, Cr, dh), q_map),
            pl.BlockSpec((1, page, dh), kv_map),
            pl.BlockSpec((1, page, dh), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, Cr, dh), q_map),
        scratch_shapes=[
            pltpu.VMEM((Cr,), jnp.float32),
            pltpu.VMEM((Cr,), jnp.float32),
            pltpu.VMEM((Cr, dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, Cr, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables, lengths, starts, q, kpool, vpool)


def paged_attention_kernel(q: jax.Array, kpool: jax.Array, vpool: jax.Array,
                           block_tables: jax.Array, lengths: jax.Array,
                           interpret: bool = False) -> jax.Array:
    """q: (B, Hkv, r, dh); kpool/vpool: (slots, page, dh);
    block_tables: (B, Hkv, max_pages) int32; lengths: (B,) int32."""
    B, Hkv, r, dh = q.shape
    slots, page, _ = kpool.shape
    max_pages = block_tables.shape[-1]

    kernel = functools.partial(_paged_kernel, page=page, max_pages=max_pages)

    def q_map(b, h, p, tables, lengths):
        return (b, h, 0, 0)

    def kv_map(b, h, p, tables, lengths):
        return (tables[b, h, p], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, max_pages),
        in_specs=[
            pl.BlockSpec((1, 1, r, dh), q_map),
            pl.BlockSpec((1, page, dh), kv_map),
            pl.BlockSpec((1, page, dh), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, r, dh), q_map),
        scratch_shapes=[
            pltpu.VMEM((r,), jnp.float32),
            pltpu.VMEM((r,), jnp.float32),
            pltpu.VMEM((r, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, r, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables, lengths, q, kpool, vpool)
    return out
