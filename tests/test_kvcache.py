"""Paged head-granular KV cache: per-device pool shards, copy-based
migration, step-plan staging remap — plus hypothesis invariants."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.models.config import ModelConfig
from repro.serving.kvcache import PagedHeadCache

CFG = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=64,
                  head_dim=16, dtype="float32")


def make_cache(slots=(8, 8), stage=8):
    return PagedHeadCache(CFG, {i: n for i, n in enumerate(slots)},
                          page_size=4, stage_slots=stage)


def test_alloc_release_roundtrip():
    kv = make_cache()
    assert kv.ensure_capacity(0, 0, 0, 10)      # 3 pages
    assert kv.partitions[0].used == 3
    kv.check_invariants()
    assert kv.release(0) == 3
    assert kv.partitions[0].used == 0
    kv.check_invariants()


def test_per_device_pools_local_slots():
    """Each device owns its own pool pair; slot ids are pool-local, so the
    same local index can be live on two devices without aliasing."""
    kv = make_cache()
    assert set(kv.kpools) == {0, 1}
    # anchor pool: slots + sink + staging; remote pool: slots + sink
    assert kv.kpools[0].shape[1] == 8 + 1 + kv.stage
    assert kv.kpools[1].shape[1] == 8 + 1
    assert kv.ensure_capacity(0, 0, 0, 4)
    assert kv.ensure_capacity(0, 1, 1, 4)
    s0 = kv.tables[(0, 0)][0]
    s1 = kv.tables[(0, 1)][0]
    assert s0[0] == 0 and s1[0] == 1
    assert s0[1] == s1[1]           # same LOCAL slot id, different pools
    kv.check_invariants()


def test_store_gather_exact():
    kv = make_cache()
    L, dh = CFG.n_layers, CFG.head_dim
    ctx = 10
    rng = np.random.default_rng(0)
    data = {}
    for g in range(CFG.n_kv_heads):
        kv.ensure_capacity(0, g, g % 2, ctx)
        kv.lengths[(0, g)] = ctx
        k = rng.random((L, ctx, dh)).astype(np.float32)
        v = rng.random((L, ctx, dh)).astype(np.float32)
        kv.store_prompt(0, g, k, v)
        data[g] = (k, v)
    K, V = kv.gather_dense(0, ctx)
    for g in range(CFG.n_kv_heads):
        np.testing.assert_array_equal(K[:, :, g], data[g][0])
        np.testing.assert_array_equal(V[:, :, g], data[g][1])


def test_append_token_and_migrate():
    kv = make_cache()
    L, dh = CFG.n_layers, CFG.head_dim
    for g in range(CFG.n_kv_heads):
        kv.ensure_capacity(0, g, 0, 4)
        kv.lengths[(0, g)] = 4
        kv.store_prompt(0, g, np.ones((L, 4, dh), np.float32),
                        np.ones((L, 4, dh), np.float32))
    ok = kv.append_token(0, 0, 0, (np.full((L, dh), 7.0, np.float32),
                                   np.full((L, dh), 8.0, np.float32)))
    assert ok
    K, V = kv.gather_dense(0, 5)
    assert np.all(K[:, 4, 0] == 7.0) and np.all(V[:, 4, 0] == 8.0)
    moved, nbytes = kv.migrate_group(0, 0, dst_device=1)
    assert moved == 2 and nbytes == moved * kv.bytes_per_slot()
    # migration is a cross-pool COPY: the chain now lives in device 1's
    # pool with device-1-local slots, and device 0 got its slots back
    assert all(dev == 1 for dev, _ in kv.tables[(0, 0)])
    assert kv.partitions[1].used == 2
    kv.check_invariants()
    K2, _ = kv.gather_dense(0, 5)
    np.testing.assert_array_equal(K[:, :, 0], K2[:, :, 0])  # data survives


def test_migrate_all_or_nothing_signal():
    """A destination shard without room refuses the WHOLE chain and says
    so — no silent partial move, nothing booked."""
    kv = make_cache(slots=(8, 1))
    kv.ensure_capacity(0, 0, 0, 8)              # 2 pages on device 0
    kv.lengths[(0, 0)] = 8
    res = kv.migrate_group(0, 0, dst_device=1)  # device 1 has 1 free slot
    assert not res.complete
    assert res.moved == 0 and res.nbytes == 0.0
    assert res.requested == 2
    assert all(dev == 0 for dev, _ in kv.tables[(0, 0)])
    assert kv.partitions[1].used == 0           # nothing allocated either
    kv.check_invariants()
    # iterable back-compat carries the refusal too
    moved, nbytes = res
    assert (moved, nbytes) == (0, 0.0)


def test_step_plan_scatter_indices_anchor_space():
    """Plan indices are anchor-pool indices: anchor chains map to their
    own slots, remote chains map into the staging region with matching
    gather + writeback lanes."""
    kv = make_cache()
    ctx = 11
    for g in range(CFG.n_kv_heads):
        kv.ensure_capacity(0, g, g % 2, ctx)    # group 1 on device 1
    plan = kv.step_plan()
    slots, offs = plan.scatter_indices(0, 0, ctx)
    assert slots.shape == (CFG.n_kv_heads, ctx) and offs.shape == (ctx,)
    devs0, local0, offs0 = kv._scatter_indices(0, 0, ctx)
    np.testing.assert_array_equal(slots[0], local0)   # anchor: identity
    np.testing.assert_array_equal(offs, offs0)
    base = kv.partitions[kv.anchor].total + 1
    assert np.all(slots[1] >= base)             # remote: staged
    # 3 remote pages -> 3 gather lanes, all written -> 3 writeback lanes
    assert plan.gather_count == 3 and plan.writeback_count == 3
    g_dev, g_src, g_dst, w_dev, w_src, w_dst = plan.exchange_arrays(4)
    assert g_dev.shape == (4,) and g_dev[3] == -1     # padded lane
    np.testing.assert_array_equal(g_dev[:3], [1, 1, 1])
    np.testing.assert_array_equal(g_dst[:3], w_src[:3])  # stage roundtrip
    devs1, local1, _ = kv._scatter_indices(0, 1, ctx)
    np.testing.assert_array_equal(np.unique(g_src[:3]),
                                  np.unique(local1))
    assert plan.d2d_bytes() == 6 * kv.bytes_per_slot()
    # chunk sub-ranges tile the full range (page-straddling chunks incl.)
    for start, n in [(0, 3), (3, 5), (8, 3)]:
        cs, co = kv.step_plan().scatter_indices(0, start, n)
        np.testing.assert_array_equal(co, offs[start:start + n])
        np.testing.assert_array_equal(cs[0], slots[0, start:start + n])


def test_step_plan_block_table_single_device_no_lanes():
    """Anchor-only chains produce ZERO exchange lanes — the common case
    that keeps the fast path one plain pallas_call."""
    kv = make_cache()
    for g in range(CFG.n_kv_heads):
        kv.ensure_capacity(0, g, 0, 10)
        kv.lengths[(0, g)] = 10
    plan = kv.step_plan()
    bt = plan.block_table_matrix(0, 4)
    assert bt.shape == (CFG.n_kv_heads, 4)
    assert plan.gather_count == 0 and plan.writeback_count == 0
    assert bt[0, 3] == kv.sink                  # padding past the chain


def test_step_plan_staging_exhaustion_raises():
    kv = make_cache(stage=1)
    kv.ensure_capacity(0, 0, 1, 8)              # 2 remote pages
    kv.lengths[(0, 0)] = 8
    plan = kv.step_plan()
    with pytest.raises(RuntimeError, match="staging region exhausted"):
        plan.block_table_matrix(0, 2)


def test_store_prompt_request_roundtrip():
    """Bulk all-group store (per-device scatters) survives gather_dense."""
    kv = make_cache()
    L, Hkv, dh = CFG.n_layers, CFG.n_kv_heads, CFG.head_dim
    ctx = 10
    rng = np.random.default_rng(2)
    k = rng.random((L, ctx, Hkv, dh)).astype(np.float32)
    v = rng.random((L, ctx, Hkv, dh)).astype(np.float32)
    for g in range(Hkv):
        kv.ensure_capacity(0, g, g % 2, ctx)
        kv.lengths[(0, g)] = ctx
    kv.store_prompt_request(0, k, v)
    K, V = kv.gather_dense(0, ctx)
    np.testing.assert_array_equal(K, k)
    np.testing.assert_array_equal(V, v)


def test_pool_dtype_honors_config_and_override():
    """pool_dtype is the byte-accounting source of truth: it follows the
    config's kv dtype (not hardcoded float32) and an explicit override."""
    assert PagedHeadCache.pool_dtype(CFG) == np.dtype(np.float32)
    bf = dataclass_replace(CFG, dtype="bfloat16")
    assert PagedHeadCache.pool_dtype(bf).itemsize == 2
    assert PagedHeadCache.pool_dtype(CFG, dtype=np.float16) \
        == np.dtype(np.float16)
    kv16 = PagedHeadCache(CFG, {0: 4}, page_size=4, dtype=np.float16)
    assert kv16.kpools[0].dtype == np.float16
    assert kv16.bytes_per_slot() == \
        2 * CFG.n_layers * 4 * CFG.head_dim * 2
    # and the default cache really allocates/accounts the config dtype
    kv = make_cache()
    assert kv.kpools[0].dtype == np.float32
    assert kv.bytes_per_slot() == 2 * CFG.n_layers * 4 * CFG.head_dim * 4


def dataclass_replace(cfg, **kw):
    import dataclasses
    return dataclasses.replace(cfg, **kw)


def test_exhaustion_returns_false():
    kv = make_cache(slots=(2, 0))
    assert kv.ensure_capacity(0, 0, 0, 8)       # 2 pages
    assert not kv.ensure_capacity(1, 0, 0, 4)   # no slots left
    kv.check_invariants()


@settings(max_examples=20, deadline=None)
@given(ops=st.lists(
    st.tuples(st.sampled_from(["alloc", "release", "migrate"]),
              st.integers(0, 3), st.integers(0, 1), st.integers(1, 24)),
    min_size=1, max_size=30))
def test_property_no_double_booking(ops):
    kv = make_cache(slots=(6, 6))
    for op, rid, dev, n in ops:
        if op == "alloc":
            for g in range(CFG.n_kv_heads):
                if kv.ensure_capacity(rid, g, dev, n):
                    kv.lengths[(rid, g)] = n
        elif op == "release":
            kv.release(rid)
        else:
            for g in range(CFG.n_kv_heads):
                if (rid, g) in kv.tables:
                    kv.migrate_group(rid, g, dev)
        kv.check_invariants()
