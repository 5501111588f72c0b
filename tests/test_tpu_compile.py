"""Compile-only checks for one TPU v5e chip, with no chip attached.

The TPU compiler, which is installed even where no chip is, compiles for a
described v5e: both paged kernels at the head shapes of the served archs
in bf16, and the full-width qwen1.5-0.5b fused step at its largest bucket
with the pool shards ``repro.launch.serve`` allocates, jitted as the
engine jits it.  Nothing runs, so
these say nothing about results or times; they catch what the chip's
compiler refuses (tiling, fast-memory limits, a program that does not fit)
before any chip time is spent.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every xdist worker imports
every test file.
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.cluster import ClusterSpec
from repro.kernels.paged_attention import ops
from repro.kernels.paged_attention.kernel import (
    paged_attention_kernel, paged_prefill_attention_kernel)
from repro.launch import serve
from repro.models import transformer as T
from repro.serving.engine import (EngineConfig, _bucket, jitted_steps,
                                  pool_shard_slots)

V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with JAX's persistent compile cache
    off: a compile for a described chip is written to the cache but cannot
    be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("kernel", ["decode", "prefill"])
@pytest.mark.parametrize("hkv,r,dh", [(16, 1, 64),     # qwen1.5-0.5b
                                      (8, 5, 128)])    # qwen3-14b
def test_paged_kernel_compiles_for_v5e(one_chip, kernel, hkv, r, dh):
    B, page, pages, slots, C = 16, 16, 32, 8193, 32
    pool = _sds(one_chip, (slots, page, dh), jnp.bfloat16)
    tables = _sds(one_chip, (B, hkv, pages), jnp.int32)
    rows = _sds(one_chip, (B,), jnp.int32)
    if kernel == "decode":
        q = _sds(one_chip, (B, hkv, r, dh), jnp.bfloat16)
        fn = jax.jit(lambda q, k, v, t, n: paged_attention_kernel(
            q, k, v, t, n, interpret=False))
        args = (q, pool, pool, tables, rows)
    else:
        q = _sds(one_chip, (B, hkv, C * r, dh), jnp.bfloat16)
        fn = jax.jit(lambda q, k, v, t, n, s: paged_prefill_attention_kernel(
            q, k, v, t, n, s, r=r, interpret=False))
        args = (q, pool, pool, tables, rows, rows)
    text = fn.lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 1


@pytest.fixture(scope="module")
def served_fused_step(one_chip):
    """The largest fused-step bucket of the served qwen1.5-0.5b engine
    (every row, the full chunk, every page, every staging lane), as the
    engine jits it (``engine.jitted_steps``), lowered and compiled with
    the Pallas kernel in it: (lowered, compiled)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_interpret", lambda: False)
        cfg = get_config("qwen1.5-0.5b")
        ecfg = EngineConfig(max_batch=serve.MAX_BATCH, max_seq=serve.MAX_SEQ)
        slots, stage = pool_shard_slots(
            cfg, ClusterSpec.build(serve.CLUSTER),
            serve.PRIMARY_IDS + serve.POOL_IDS, ecfg)
        anchor = serve.PRIMARY_IDS[0]
        pshape = jax.eval_shape(
            lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
        params = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype),
                              pshape)
        pools = {d: _sds(one_chip, (cfg.n_layers,
                                    n + 1 + (stage if d == anchor else 0),
                                    ecfg.page_size, cfg.head_dim),
                         cfg.kv_dtype)
                 for d, n in slots.items()}
        B, C, G = ecfg.max_batch, ecfg.prefill_chunk, _bucket(stage)
        P = -(-ecfg.max_seq // ecfg.page_size)
        i32 = lambda *s: _sds(one_chip, s, jnp.int32)  # noqa: E731
        lanes = [i32(G) for _ in range(6)]
        rest = (i32(B, cfg.n_kv_heads, P), i32(B), i32(B),
                i32(B, cfg.n_kv_heads, C), i32(B, C), i32(B, C), i32(B))
        step = jitted_steps(cfg, ecfg.max_seq, anchor, slots[anchor])[4]
        lowered = step.lower(params, pools, pools, *lanes, *rest)
        yield lowered, lowered.compile()


def test_served_fused_step_fits_v5e(served_fused_step):
    """The largest bucket compiles with exactly one Pallas kernel, and its
    arguments (weights + donated pools) plus temporaries fit one chip's
    memory."""
    _, compiled = served_fused_step
    assert compiled.as_text().count("tpu_custom_call") == 1
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert total < V5E_HBM_BYTES, (mem.argument_size_in_bytes,
                                   mem.temp_size_in_bytes)


def test_served_fused_step_names_and_scopes(served_fused_step):
    """The fused step's module is ``jit_fused_step`` and its ops carry the
    named scopes a profile is read by: the staging exchange in and out,
    and the layer scan's attention and MLP."""
    lowered, compiled = served_fused_step
    text = compiled.as_text()
    assert text.startswith("HloModule jit_fused_step,")
    assert "jit__lambda" not in lowered.as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("exchange_in/", "exchange_out/", "/layer/attn/",
                  "/layer/mlp/", "/layer/qkv/", "/layer/kv_write/",
                  "embed/", "lm_head/"):
        assert any(scope in n for n in names), scope
    # the kernel keeps its name, which the roofline metric matches
    assert re.search(r"%paged_prefill_attention(\.\d+)? = ", text)


def _pool_copies_in_loops(text, pool_shape):
    """Copies, in any while body of compiled HLO ``text``, of an array
    with as many elements as ``pool_shape``, whatever its shape or
    layout (XLA flattens the pool to rows before relaying it out)."""
    n = math.prod(pool_shape)
    bodies = set(re.findall(r"body=%?([\w.\-]+)", text))
    found, inside = [], False
    for line in text.splitlines():
        if line and not line.startswith(" "):
            m = re.match(r"%?([\w.\-]+) ", line)
            inside = bool(m) and m.group(1) in bodies
            continue
        m = re.search(r"= \w+\[([\d,]*)\]\S* copy(-start)?\(", line)
        if inside and m and math.prod(
                int(d) for d in m.group(1).split(",") if d) == n:
            found.append(line.strip()[:120])
    return found


def test_served_fused_step_writes_pool_in_place(served_fused_step):
    """The layer scan of the largest fused bucket writes the chunk's K/V
    into the stacked anchor pool without relaying the whole pool out
    (a token-row scatter at head_dim 64 made XLA transpose it twice per
    pool and layer)."""
    _, compiled = served_fused_step
    text = compiled.as_text()
    cfg = get_config("qwen1.5-0.5b")
    pools = set(re.findall(r"bf16\[(%d,\d+,%d,%d)\]" % (
        cfg.n_layers, EngineConfig().page_size, cfg.head_dim), text))
    assert len(pools) >= 2 and re.search(r"body=", text)
    for shape in pools:
        dims = [int(d) for d in shape.split(",")]
        assert _pool_copies_in_loops(text, dims) == [], shape


@pytest.mark.parametrize("dh", [64, 96, 128])
def test_chunk_page_write_in_scan_compiles_in_place(one_chip, dh):
    """``write_chunk_pages`` on a stacked pool carried through a layer
    ``lax.scan`` leaves no whole-pool copy in the loop, at each head
    dim."""
    from repro.models.attention import write_chunk_pages
    L, slots, page, B, Hkv, C = 24, 16385, 16, 16, 16, 32
    pool = _sds(one_chip, (L, slots, page, dh), jnp.bfloat16)

    def write_all(kp, vp, ws, wo, kv):
        def body(carry, idx):
            kp, vp = carry
            kp = write_chunk_pages(kp, idx, ws, wo, kv)
            vp = write_chunk_pages(vp, idx, ws, wo, kv * 2)
            return (kp, vp), None
        return jax.lax.scan(body, (kp, vp), jnp.arange(L))[0]

    args = (pool, pool, _sds(one_chip, (B, Hkv, C), jnp.int32),
            _sds(one_chip, (B, C), jnp.int32),
            _sds(one_chip, (B, C, Hkv, dh), jnp.bfloat16))
    text = jax.jit(write_all, donate_argnums=(0, 1)).lower(
        *args).compile().as_text()
    assert re.search(r"body=", text)
    assert _pool_copies_in_loops(text, (L, slots, page, dh)) == []
