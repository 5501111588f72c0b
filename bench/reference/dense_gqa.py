"""Plain reference of a dense decoder with grouped-query attention, RoPE
(rotate-half), RMSNorm and a SiLU-gated MLP: the Qwen2 and Phi-3 families
as their Hugging Face modeling files define them.

It reads a configuration file of ``bench/configs`` and imports nothing of
the program.  The weights it makes from a seed are the benchmark's own:
the program is handed them in its parameter layout, and the reference
reads the same arrays.  The forward pass runs in float32 with every dot at
``Precision.HIGHEST``, one layer at a time inside a scan, over one padded
sequence.  ``quant="fp8"`` is the control: every linear layer's input and
weight rounded to float8_e4m3fn (per-row and per-column scales), the step
below the bfloat16 the configurations state.
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


class Dims(NamedTuple):
    L: int
    d: int
    H: int
    Hkv: int
    dh: int
    ff: int
    V: int
    theta: float
    eps: float
    bias: bool
    tied: bool
    dtype: str


def dims(conf: Dict) -> Dims:
    if conf.get("hidden_act", "silu") != "silu":
        raise ValueError("dense_gqa covers SiLU-gated MLPs only")
    d, H = int(conf["hidden_size"]), int(conf["num_attention_heads"])
    return Dims(L=int(conf["num_hidden_layers"]), d=d, H=H,
                Hkv=int(conf["num_key_value_heads"]),
                dh=int(conf.get("head_dim") or d // H),
                ff=int(conf["intermediate_size"]),
                V=int(conf["vocab_size"]),
                theta=float(conf["rope_theta"]),
                eps=float(conf["rms_norm_eps"]),
                bias=bool(conf.get("attention_bias", False)),
                tied=bool(conf["tie_word_embeddings"]),
                dtype=str(conf["torch_dtype"]))


def program_fields(conf: Dict) -> Dict:
    """The program's ``ModelConfig`` fields that this configuration sets."""
    m = dims(conf)
    return dict(n_layers=m.L, d_model=m.d, n_heads=m.H, n_kv_heads=m.Hkv,
                head_dim=m.dh, d_ff=m.ff, vocab_size=m.V, rope_theta=m.theta,
                norm_eps=m.eps, qkv_bias=m.bias, tie_embeddings=m.tied,
                dtype=m.dtype, act="swiglu", sliding_window=0,
                attn_type="gqa", family="dense", kv_cache_dtype="")


def weight_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed, 64-bit ones included."""
    return jax.random.PRNGKey(
        int(np.random.default_rng(seed).integers(0, 2 ** 31 - 1)))


def _init(m: Dims, key) -> Dict:
    dt = jnp.dtype(m.dtype)
    ks = iter(jax.random.split(key, 16))

    def normal(shape, std, dtype=dt):
        return jax.random.normal(next(ks), shape, dtype) * jnp.asarray(
            std, dtype)

    def scale(shape):               # norm weights near 1, in float32
        return 1.0 + normal(shape, 0.1, jnp.float32)

    L, d, qd, kd = m.L, m.d, m.H * m.dh, m.Hkv * m.dh
    attn = {"wq": normal((L, d, qd), d ** -0.5),
            "wk": normal((L, d, kd), d ** -0.5),
            "wv": normal((L, d, kd), d ** -0.5),
            "wo": normal((L, qd, d), qd ** -0.5)}
    if m.bias:
        attn.update(bq=normal((L, qd), 0.1), bk=normal((L, kd), 0.1),
                    bv=normal((L, kd), 0.1))
    layers = {"attn_norm": scale((L, d)), "attn": attn,
              "mlp_norm": scale((L, d)),
              "mlp": {"wi": normal((L, d, m.ff), d ** -0.5),
                      "wg": normal((L, d, m.ff), d ** -0.5),
                      "wo": normal((L, m.ff, d), m.ff ** -0.5)}}
    params = {"groups": [layers], "embed": normal((m.V, d), 1.0),
              "final_norm": scale((d,))}
    if not m.tied:
        params["lm_head"] = normal((d, m.V), d ** -0.5)
    else:
        # tied: the embedding is also the output head, so give it the
        # head's fan-in scale rather than unit rows
        params["embed"] = params["embed"] * jnp.asarray(d ** -0.5, dt)
    return params


def make_params(conf: Dict, seed: int) -> Dict:
    """Weights in the configuration's dtype, made on the device in one
    jitted call from ``seed``."""
    return jax.jit(functools.partial(_init, dims(conf)))(weight_key(seed))


def param_shapes(conf: Dict) -> Dict:
    return jax.eval_shape(functools.partial(_init, dims(conf)),
                          jax.random.PRNGKey(0))


# ------------------------------------------------------------------ forward
def _fp8(x: jax.Array, axis: int) -> jax.Array:
    """Round to float8_e4m3fn with a scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / s).astype(FP8).astype(jnp.float32) * s


def _linear(x, w, quant: bool):
    if quant:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.dot(x, w, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """Rotate-half RoPE on (S, heads, dh)."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos[:, None].astype(jnp.float32) * inv          # (S, dh/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _scores(m: Dims, params, tokens, targets, quant: bool):
    """Per position of one padded sequence: the largest logit, the logit
    of ``targets`` there, and the arg-max token."""
    f32 = jnp.float32
    S = tokens.shape[0]
    pos = jnp.arange(S)
    x = params["embed"][tokens].astype(f32)
    mask = pos[:, None] >= pos[None, :]
    rep = m.H // m.Hkv

    def layer(x, p):
        p = jax.tree.map(lambda a: a.astype(f32), p)
        a = p["attn"]
        h = _rms(x, p["attn_norm"], m.eps)
        q, k, v = (_linear(h, a[w], quant) for w in ("wq", "wk", "wv"))
        if m.bias:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        q = _rope(q.reshape(S, m.H, m.dh), pos, m.theta)
        k = _rope(k.reshape(S, m.Hkv, m.dh), pos, m.theta)
        v = v.reshape(S, m.Hkv, m.dh)
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / np.sqrt(
            m.dh)
        s = jnp.where(mask[None], s, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v,
                       precision=HIGHEST)
        x = x + _linear(o.reshape(S, m.H * m.dh), a["wo"], quant)
        h = _rms(x, p["mlp_norm"], m.eps)
        g = jax.nn.silu(_linear(h, p["mlp"]["wg"], quant))
        x = x + _linear(g * _linear(h, p["mlp"]["wi"], quant),
                        p["mlp"]["wo"], quant)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["groups"][0])
    x = _rms(x, params["final_norm"].astype(f32), m.eps)
    head = (params["embed"].T if m.tied else params["lm_head"]).astype(f32)
    logits = _linear(x, head, quant)
    tgt = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return logits.max(-1), tgt, jnp.argmax(logits, -1)


def score(conf: Dict, params, tokens: np.ndarray, targets: np.ndarray,
          quant: str = "") -> Dict[str, np.ndarray]:
    """Run the reference (or, with ``quant="fp8"``, the control) over one
    sequence padded to a fixed length; returns host arrays."""
    best, tgt, top = _scores(dims(conf), params, jnp.asarray(tokens),
                             jnp.asarray(targets), quant == "fp8")
    return {"best": np.asarray(best), "target": np.asarray(tgt),
            "argmax": np.asarray(top)}
