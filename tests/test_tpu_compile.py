"""Compile-only checks for one TPU v5e chip, with no chip attached.

The TPU compiler, which is installed even where no chip is, compiles for a
described v5e: both paged kernels at the head shapes of the served archs
in bf16, and the full-width qwen1.5-0.5b fused step at its largest bucket
with the pool shards ``repro.launch.serve`` allocates.  Nothing runs, so
these say nothing about results or times; they catch what the chip's
compiler refuses (tiling, fast-memory limits, a program that does not fit)
before any chip time is spent.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every xdist worker imports
every test file.
"""

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
from repro.serving.engine import EngineConfig, _bucket, pool_shard_slots

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


def test_served_fused_step_fits_v5e(one_chip, monkeypatch):
    """The largest fused-step bucket of the served qwen1.5-0.5b engine
    (every row, the full chunk, every page, every staging lane) compiles
    with the Pallas kernel in it, and its arguments (weights + donated
    pools) plus temporaries fit one chip's memory."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = get_config("qwen1.5-0.5b")
    ecfg = EngineConfig(max_batch=serve.MAX_BATCH, max_seq=serve.MAX_SEQ)
    slots, stage = pool_shard_slots(
        cfg, ClusterSpec.build(serve.CLUSTER),
        serve.PRIMARY_IDS + serve.POOL_IDS, ecfg)
    anchor = serve.PRIMARY_IDS[0]
    pshape = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype), pshape)
    pools = {d: _sds(one_chip, (cfg.n_layers,
                                n + 1 + (stage if d == anchor else 0),
                                ecfg.page_size, cfg.head_dim), cfg.kv_dtype)
             for d, n in slots.items()}
    B, C, G = ecfg.max_batch, ecfg.prefill_chunk, _bucket(stage)
    P = -(-ecfg.max_seq // ecfg.page_size)
    i32 = lambda *s: _sds(one_chip, s, jnp.int32)  # noqa: E731
    lanes = [i32(G) for _ in range(6)]
    rest = (i32(B, cfg.n_kv_heads, P), i32(B), i32(B),
            i32(B, cfg.n_kv_heads, C), i32(B, C), i32(B, C), i32(B))
    step = jax.jit(
        lambda p, kp, vp, *a: T.sharded_fused_step(
            cfg, p, kp, vp, anchor, slots[anchor], *a),
        donate_argnums=(1, 2))
    compiled = step.lower(params, pools, pools, *lanes, *rest).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert total < V5E_HBM_BYTES, (mem.argument_size_in_bytes,
                                   mem.temp_size_in_bytes)
