"""Prefill flash attention — Pallas TPU kernel.

Tiling: grid (B, Hq, Sq/block_q, Sk/block_k); the last axis is sequential
("arbitrary") so the (m, l, acc) running statistics live in VMEM scratch and
carry across k-blocks.  Block sizes default to 128x128 (MXU-aligned); the
working set per step is q(bq x dh) + k/v(bk x dh) + acc(bq x dh) fp32 —
~0.25 MB at bq=bk=128, dh=128, far under the ~16 MB v5e VMEM budget, leaving
room for double buffering.

GQA is expressed in the k/v index_map (kv head = q head // group); causal
and sliding-window masking zero-skip whole blocks via pl.when.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, block_q: int, block_k: int, n_kblocks: int,
                  causal: bool, window: int, kv_len: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # block-level skip: causal blocks entirely above the diagonal and
    # window blocks entirely out of range do no work at all.
    q_lo = iq * block_q
    k_lo = ik * block_k
    run = jnp.asarray(k_lo < kv_len)
    if causal:
        run &= k_lo <= q_lo + block_q - 1
    if window and window > 0:
        # a block contributes iff its smallest (q_pos - k_pos) is in-window
        run &= q_lo - (k_lo + block_k - 1) < window

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, dh)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, dh)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32,
                                                (block_q, block_k), 0)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32,
                                                (block_q, block_k), 1)
        ok = k_pos < kv_len            # padded keys never attend
        if causal:
            ok &= q_pos >= k_pos
        if window and window > 0:
            ok &= (q_pos - k_pos) < window
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_cur[:, None])
        alpha = jnp.exp(m_prev - m_cur)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + pv
        m_scr[...] = m_cur

    @pl.when(ik == n_kblocks - 1)
    def _finalize():
        l = l_scr[...]
        safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0] = (acc_scr[...] / safe[:, None]).astype(o_ref.dtype)


def flash_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int = 0,
                           block_q: int = 128, block_k: int = 128,
                           kv_len: int = 0, interpret: bool = False
                           ) -> jax.Array:
    """q: (B, Hq, Sq, dh); k, v: (B, Hkv, Sk, dh) -> (B, Hq, Sq, dh).
    ``kv_len``: true (unpadded) key count; 0 means all keys valid."""
    B, Hq, Sq, dh = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    if kv_len <= 0:
        kv_len = Sk
    assert Hq % Hkv == 0, "GQA requires Hq % Hkv == 0"
    group = Hq // Hkv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, \
        "pad sequence to block multiples (ops.py handles this)"
    nq, nk = Sq // block_q, Sk // block_k
    scale = 1.0 / math.sqrt(dh)

    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        n_kblocks=nk, causal=causal, window=window, kv_len=kv_len)

    grid = (B, Hq, nq, nk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dh),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out
