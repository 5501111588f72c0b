"""Pallas kernel validation (interpret mode): shape/dtype sweeps vs the
pure-jnp oracles (brief deliverable (c))."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention import flash_attention, flash_attention_ref
from repro.kernels.paged_attention import (paged_attention,
                                           paged_attention_ref,
                                           paged_prefill_attention,
                                           paged_prefill_attention_ref)

KEY = jax.random.PRNGKey(0)

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Hq,Hkv,S,dh", [
    (1, 4, 4, 128, 64),      # MHA
    (2, 8, 2, 256, 64),      # GQA r=4
    (1, 4, 1, 128, 128),     # MQA, MXU-aligned dh
    (2, 4, 2, 192, 32),      # non-power-of-two seq (pad path)
])
def test_flash_vs_ref(dtype, B, Hq, Hkv, S, dh):
    q = jax.random.normal(KEY, (B, Hq, S, dh), dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, Hkv, S, dh), dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, Hkv, S, dh), dtype)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    ref = flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


@pytest.mark.parametrize("window", [16, 48, 96])
def test_flash_sliding_window(window):
    B, Hq, Hkv, S, dh = 1, 4, 2, 128, 32
    q = jax.random.normal(KEY, (B, Hq, S, dh))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, Hkv, S, dh))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, Hkv, S, dh))
    out = flash_attention(q, k, v, causal=True, window=window,
                          block_q=32, block_k=32)
    ref = flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_non_causal_padded():
    B, Hq, Hkv, S, dh = 1, 2, 2, 100, 32   # pads to 128
    q = jax.random.normal(KEY, (B, Hq, S, dh))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, Hkv, S, dh))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, Hkv, S, dh))
    out = flash_attention(q, k, v, causal=False, block_q=64, block_k=64)
    ref = flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_bshd_layout():
    B, Hq, Hkv, S, dh = 1, 4, 2, 64, 32
    q = jax.random.normal(KEY, (B, S, Hq, dh))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, Hkv, dh))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, Hkv, dh))
    out = flash_attention(q, k, v, causal=True, layout="BSHD",
                          block_q=32, block_k=32)
    ref = flash_attention_ref(q.transpose(0, 2, 1, 3),
                              k.transpose(0, 2, 1, 3),
                              v.transpose(0, 2, 1, 3), causal=True)
    np.testing.assert_allclose(np.asarray(out.transpose(0, 2, 1, 3)),
                               np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Hkv,r,dh,page,maxp", [
    (2, 2, 4, 64, 16, 8),
    (3, 4, 1, 128, 32, 4),    # MHA-ish groups, MXU-aligned
    (1, 1, 8, 64, 16, 16),
])
def test_paged_vs_ref(dtype, B, Hkv, r, dh, page, maxp):
    slots = B * Hkv * maxp + 8
    rng = np.random.default_rng(0)
    bt = jnp.asarray(rng.permutation(slots)[:B * Hkv * maxp]
                     .reshape(B, Hkv, maxp), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, page * maxp, B), jnp.int32)
    kpool = jax.random.normal(KEY, (slots, page, dh), dtype)
    vpool = jax.random.normal(jax.random.fold_in(KEY, 1),
                              (slots, page, dh), dtype)
    q = jax.random.normal(jax.random.fold_in(KEY, 2), (B, Hkv, r, dh),
                          dtype)
    out = paged_attention(q, kpool, vpool, bt, lengths)
    ref = paged_attention_ref(q, kpool, vpool, bt, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000),
       lengths_frac=st.floats(0.05, 1.0))
def test_paged_property_partial_lengths(seed, lengths_frac):
    """Arbitrary per-sequence lengths: the kernel must mask exactly."""
    B, Hkv, r, dh, page, maxp = 2, 2, 2, 32, 8, 4
    slots = B * Hkv * maxp
    rng = np.random.default_rng(seed)
    bt = jnp.asarray(rng.permutation(slots).reshape(B, Hkv, maxp), jnp.int32)
    max_tok = page * maxp
    lengths = jnp.asarray(
        np.maximum(1, (rng.random(B) * lengths_frac * max_tok)).astype(int),
        jnp.int32)
    key = jax.random.PRNGKey(seed)
    kpool = jax.random.normal(key, (slots, page, dh))
    vpool = jax.random.normal(jax.random.fold_in(key, 1), (slots, page, dh))
    q = jax.random.normal(jax.random.fold_in(key, 2), (B, Hkv, r, dh))
    out = paged_attention(q, kpool, vpool, bt, lengths)
    ref = paged_attention_ref(q, kpool, vpool, bt, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


def test_paged_matches_dense_attention():
    """Paged kernel over scattered pages == dense decode attention."""
    from repro.models.common import decode_attention
    B, Hkv, r, dh, page, maxp = 2, 2, 2, 32, 8, 4
    S = page * maxp
    slots = B * Hkv * maxp
    rng = np.random.default_rng(3)
    bt_np = rng.permutation(slots).reshape(B, Hkv, maxp)
    lengths = jnp.asarray([S, S // 2], jnp.int32)
    key = jax.random.PRNGKey(3)
    K = jax.random.normal(key, (B, S, Hkv, dh))
    V = jax.random.normal(jax.random.fold_in(key, 1), (B, S, Hkv, dh))
    kpool = np.zeros((slots, page, dh), np.float32)
    vpool = np.zeros((slots, page, dh), np.float32)
    for b in range(B):
        for h in range(Hkv):
            for p in range(maxp):
                kpool[bt_np[b, h, p]] = np.asarray(
                    K[b, p * page:(p + 1) * page, h])
                vpool[bt_np[b, h, p]] = np.asarray(
                    V[b, p * page:(p + 1) * page, h])
    q = jax.random.normal(jax.random.fold_in(key, 2), (B, Hkv * r, 1, dh))
    dense = decode_attention(q.transpose(0, 2, 1, 3), K, V, kv_len=lengths)
    qg = q.reshape(B, Hkv, r, dh)
    paged = paged_attention(qg, jnp.asarray(kpool), jnp.asarray(vpool),
                            jnp.asarray(bt_np, jnp.int32), lengths)
    np.testing.assert_allclose(
        np.asarray(paged).reshape(B, Hkv * r, dh),
        np.asarray(dense)[:, 0], rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Hkv,C,r,dh,page,maxp", [
    (2, 2, 8, 2, 32, 8, 4),
    (3, 1, 4, 4, 64, 16, 2),
    (1, 4, 16, 1, 32, 8, 8),   # chunk spanning several pages
])
def test_paged_prefill_vs_ref(dtype, B, Hkv, C, r, dh, page, maxp):
    slots = B * Hkv * maxp + 4
    rng = np.random.default_rng(1)
    bt = jnp.asarray(rng.permutation(slots)[:B * Hkv * maxp]
                     .reshape(B, Hkv, maxp), jnp.int32)
    # each row: a stored prefix of `start` tokens plus an n<=C token chunk
    starts = jnp.asarray(rng.integers(0, page * maxp - C, B), jnp.int32)
    nvalid = rng.integers(1, C + 1, B)
    lengths = jnp.asarray(np.asarray(starts) + nvalid, jnp.int32)
    kpool = jax.random.normal(KEY, (slots, page, dh), dtype)
    vpool = jax.random.normal(jax.random.fold_in(KEY, 1),
                              (slots, page, dh), dtype)
    q = jax.random.normal(jax.random.fold_in(KEY, 2), (B, Hkv, C, r, dh),
                          dtype)
    out = paged_prefill_attention(q, kpool, vpool, bt, lengths, starts)
    ref = paged_prefill_attention_ref(q, kpool, vpool, bt, lengths, starts)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


def test_paged_prefill_matches_chunked_attention():
    """Prefill kernel over scattered pages == dense causal chunk attention
    against the same prefix (the chunked_attention path dense prefill
    uses), for a chunk appended after a stored prefix."""
    from repro.models.common import chunked_attention
    B, Hkv, C, r, dh, page, maxp = 2, 2, 8, 2, 32, 8, 4
    S = page * maxp
    slots = B * Hkv * maxp
    rng = np.random.default_rng(5)
    bt_np = rng.permutation(slots).reshape(B, Hkv, maxp)
    starts = np.asarray([0, 13])          # row 0: no prefix; row 1: mid-page
    lengths = jnp.asarray(starts + C, jnp.int32)
    key = jax.random.PRNGKey(5)
    K = jax.random.normal(key, (B, S, Hkv, dh))
    V = jax.random.normal(jax.random.fold_in(key, 1), (B, S, Hkv, dh))
    kpool = np.zeros((slots, page, dh), np.float32)
    vpool = np.zeros((slots, page, dh), np.float32)
    for b in range(B):
        for h in range(Hkv):
            for p in range(maxp):
                kpool[bt_np[b, h, p]] = np.asarray(
                    K[b, p * page:(p + 1) * page, h])
                vpool[bt_np[b, h, p]] = np.asarray(
                    V[b, p * page:(p + 1) * page, h])
    q = jax.random.normal(jax.random.fold_in(key, 2), (B, C, Hkv * r, dh))
    qg = q.reshape(B, C, Hkv, r, dh).transpose(0, 2, 1, 3, 4)
    paged = paged_prefill_attention(
        qg, jnp.asarray(kpool), jnp.asarray(vpool),
        jnp.asarray(bt_np, jnp.int32), lengths,
        jnp.asarray(starts, jnp.int32))
    for b in range(B):
        n = int(starts[b]) + C
        dense = chunked_attention(q[b:b + 1], K[b:b + 1, :n],
                                  V[b:b + 1, :n], causal=True,
                                  q_offset=int(starts[b]))
        got = np.asarray(paged[b].transpose(1, 0, 2, 3)).reshape(
            C, Hkv * r, dh)
        np.testing.assert_allclose(got, np.asarray(dense)[0],
                                   rtol=3e-5, atol=3e-5)
