"""jit'd public wrapper for the paged decode-attention kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.kernel import (
    paged_attention_kernel, paged_prefill_attention_kernel)


def _interpret() -> bool:
    """Pallas interpret mode runs only on the CPU backend; every other
    backend compiles the kernel, so a TPU run can never fall back to the
    interpreter without failing."""
    return jax.default_backend() == "cpu"


@jax.jit
def paged_attention(q: jax.Array, kpool: jax.Array, vpool: jax.Array,
                    block_tables: jax.Array, lengths: jax.Array
                    ) -> jax.Array:
    """Decode attention over the head-granular paged pool.

    q:            (B, Hkv, r, dh) new-token queries, grouped per kv head
    kpool/vpool:  (num_slots, page_size, dh)
    block_tables: (B, Hkv, max_pages) int32 — slot id per (seq, group, page);
                  entries past the sequence length may be arbitrary valid ids
    lengths:      (B,) int32
    """
    assert q.ndim == 4 and kpool.ndim == 3 and block_tables.ndim == 3
    block_tables = jnp.clip(block_tables, 0, kpool.shape[0] - 1)
    return paged_attention_kernel(q, kpool, vpool,
                                  block_tables.astype(jnp.int32),
                                  lengths.astype(jnp.int32),
                                  interpret=_interpret())


@jax.jit
def paged_prefill_attention(q: jax.Array, kpool: jax.Array, vpool: jax.Array,
                            block_tables: jax.Array, lengths: jax.Array,
                            starts: jax.Array) -> jax.Array:
    """Causal chunk attention over the head-granular paged pool (prefill).

    q:            (B, Hkv, C, r, dh) — one C-token prompt chunk per sequence,
                  queries grouped per kv head; the chunk's OWN K/V must
                  already be scattered into the pools
    kpool/vpool:  (num_slots, page_size, dh)
    block_tables: (B, Hkv, max_pages) int32 — entries past the written
                  length may be arbitrary valid ids (masked / page-skipped)
    lengths:      (B,) int32 keys visible after the chunk's writes (0 pads)
    starts:       (B,) int32 absolute position of each chunk's first token
    """
    assert q.ndim == 5 and kpool.ndim == 3 and block_tables.ndim == 3
    B, Hkv, C, r, dh = q.shape
    block_tables = jnp.clip(block_tables, 0, kpool.shape[0] - 1)
    out = paged_prefill_attention_kernel(
        q.reshape(B, Hkv, C * r, dh), kpool, vpool,
        block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
        starts.astype(jnp.int32), r=r, interpret=_interpret())
    return out.reshape(B, Hkv, C, r, dh)
