"""Kernel microbenchmarks (interpret mode on CPU — correctness-grade timing,
the roofline numbers come from the dry-run analysis instead).
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_fn
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_attention


def main(argv=()) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="shrink shapes for CI smoke runs")
    args = ap.parse_args(list(argv))
    key = jax.random.PRNGKey(0)
    B, Hq, Hkv, S, dh = 1, 4, 2, (128 if args.smoke else 256), 64
    q = jax.random.normal(key, (B, Hq, S, dh), jnp.float32)
    k = jax.random.normal(key, (B, Hkv, S, dh), jnp.float32)
    v = jax.random.normal(key, (B, Hkv, S, dh), jnp.float32)
    t = time_fn(lambda: flash_attention(q, k, v).block_until_ready())
    fl = 4 * B * Hq * S * S * dh / 2
    emit("kernel/flash_256", t, f"flops={fl:.2e} interpret=True")

    slots, page, maxp, r = 64, 16, (4 if args.smoke else 8), 2
    bt = jnp.asarray(np.random.default_rng(0).integers(
        0, slots, (B, Hkv, maxp)), jnp.int32)
    lengths = jnp.asarray([100], jnp.int32)
    kpool = jax.random.normal(key, (slots, page, dh), jnp.float32)
    vpool = jax.random.normal(key, (slots, page, dh), jnp.float32)
    qd = jax.random.normal(key, (B, Hkv, r, dh), jnp.float32)
    t = time_fn(lambda: paged_attention(qd, kpool, vpool, bt,
                                        lengths).block_until_ready())
    emit("kernel/paged_decode", t, "interpret=True")


if __name__ == "__main__":
    import sys

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main(sys.argv[1:])
