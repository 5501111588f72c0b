"""jit'd public wrapper for the prefill flash-attention kernel.

Handles padding to block multiples and layout (B,S,H,dh) <-> (B,H,S,dh);
the kernel runs in interpret mode on the CPU backend only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_kernel


def _interpret() -> bool:
    """Pallas interpret mode runs only on the CPU backend; every other
    backend compiles the kernel, so a TPU run can never fall back to the
    interpreter without failing."""
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "layout"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    layout: str = "BHSD") -> jax.Array:
    """Flash attention.  layout "BHSD" (kernel-native) or "BSHD" (model)."""
    if layout == "BSHD":
        q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    B, Hq, Sq, dh = q.shape
    Sk = k.shape[2]
    bq = min(block_q, max(8, Sq))
    bk = min(block_k, max(8, Sk))
    pad_q = (-Sq) % bq
    pad_k = (-Sk) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    out = flash_attention_kernel(q, k, v, causal=causal, window=window,
                                 block_q=bq, block_k=bk, kv_len=Sk,
                                 interpret=_interpret())
    out = out[:, :, :Sq]
    if layout == "BSHD":
        out = out.transpose(0, 2, 1, 3)
    return out
