"""Algorithmic operation and byte counts of the served model's work,
computed from shapes alone (not from what any implementation lowers to).

A row of a step is ``(s0, n)``: ``n`` tokens at positions ``s0 .. s0+n-1``
of one sequence, each attending causally to every earlier position of it
and itself.  A decode row is ``(ctx - 1, 1)``.  One multiply-add counts as
two operations.
"""

from __future__ import annotations

from typing import Iterable, Tuple

Row = Tuple[int, int]


def _keys(rows: Iterable[Row]) -> int:
    """Query-key pairs of the rows: sum over tokens of their context."""
    return sum(n * s0 + n * (n + 1) // 2 for s0, n in rows)


def attention_flops(cfg, rows: Iterable[Row]) -> float:
    """Score and value products of the rows, over every layer:
    4 * heads * head_dim per query-key pair."""
    return 4.0 * cfg.n_layers * cfg.n_heads * cfg.head_dim * _keys(rows)


def attention_bytes(cfg, rows: Iterable[Row], itemsize: int) -> float:
    """Least bytes attention must move: each row reads its K and V once
    per layer (all ``s0 + n`` positions, every KV head) and reads its
    queries and writes its outputs once."""
    rows = list(rows)
    kv = sum(s0 + n for s0, n in rows) * 2 * cfg.n_kv_heads * cfg.head_dim
    qo = sum(n for _, n in rows) * 2 * cfg.n_heads * cfg.head_dim
    return float(cfg.n_layers * (kv + qo) * itemsize)


def dense_params(cfg) -> int:
    """Weights of the matrix products each token passes through (q, k, v,
    o and the gated MLP), over every layer."""
    d, dh = cfg.d_model, cfg.head_dim
    attn = d * dh * (cfg.n_heads + 2 * cfg.n_kv_heads) + cfg.n_heads * dh * d
    mlp = 3 * d * cfg.d_ff
    return cfg.n_layers * (attn + mlp)


def step_flops(cfg, rows: Iterable[Row]) -> float:
    """Model operations of one step: the dense products of every token,
    attention, and the output head for one position per row."""
    rows = list(rows)
    tokens = sum(n for _, n in rows)
    return (2.0 * dense_params(cfg) * tokens + attention_flops(cfg, rows)
            + 2.0 * cfg.d_model * cfg.vocab_size * len(rows))
