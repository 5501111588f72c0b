"""Algorithmic counts against hand-computed values for one decode row and
one prefill chunk."""

from types import SimpleNamespace

import pytest

from bench_cpu_sizes import ROOT  # noqa: F401  (puts bench on the path)
from bench import counts

CFG = SimpleNamespace(n_layers=2, n_heads=4, n_kv_heads=2, head_dim=16,
                      d_model=64, d_ff=128, vocab_size=256)
DECODE = (9, 1)          # the token at position 9 attends to 10 keys
CHUNK = (4, 3)           # positions 4, 5, 6 attend to 5, 6, 7 keys


@pytest.mark.parametrize("row,flops,nbytes", [
    # 4 * L * H * dh * keys ; L * (KV of all s0+n positions + q and o) * 2 B
    (DECODE, 4 * 2 * 4 * 16 * 10, 2 * (10 * 2 * 2 * 16 + 2 * 4 * 16) * 2),
    (CHUNK, 4 * 2 * 4 * 16 * 18, 2 * (7 * 2 * 2 * 16 + 3 * 2 * 4 * 16) * 2),
])
def test_attention_counts(row, flops, nbytes):
    assert counts.attention_flops(CFG, [row]) == flops
    assert counts.attention_bytes(CFG, [row], 2) == nbytes


def test_dense_params_and_step_flops():
    # per layer: q, k, v (64 x 16 x (4 + 2 + 2)), o (64 x 64), gated MLP
    assert counts.dense_params(CFG) == 2 * (8192 + 4096 + 24576)
    want = (2 * 73728 * 4                       # dense, 4 tokens
            + 4 * 2 * 4 * 16 * (10 + 18)        # attention
            + 2 * 64 * 256 * 2)                 # head, one row each
    assert counts.step_flops(CFG, [DECODE, CHUNK]) == want
