"""Chunked paged-prefill fast path: token exactness vs the dense prefill
reference across odd prompt lengths (page boundaries), preemption-replay
resume, and the prefill recompile guard."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cluster import ClusterSpec
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.serving import EngineConfig, InferenceEngine, Request
from repro.serving.kvcache import PagedHeadCache

CFG = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
                  head_dim=16, dtype="float32", remat=False,
                  scan_q_chunk=64, loss_chunk=64)
KEY = jax.random.PRNGKey(0)
PARAMS = T.init_params(CFG, KEY)

PAGE = 8


def make_engine(prefill_mode="paged", decode_mode="paged", max_seq=96,
                chunk=8, max_batch=8, step_mode="fused"):
    cl = ClusterSpec.build([("A100", 1), ("3090", 1), ("P100", 1)])
    return InferenceEngine(CFG, PARAMS, cl, primary_ids=[0],
                           pool_ids=[1, 2],
                           engine_cfg=EngineConfig(
                               max_batch=max_batch, max_seq=max_seq,
                               page_size=PAGE, decode_mode=decode_mode,
                               prefill_mode=prefill_mode,
                               prefill_chunk=chunk,
                               step_mode=step_mode))


def ref_decode(prompt, n, max_seq=96):
    toks = jnp.asarray(prompt, jnp.int32)[None]
    logits, cache = T.prefill(CFG, PARAMS, {"tokens": toks},
                              max_seq=max_seq)
    out = [int(jnp.argmax(logits[0]))]
    for _ in range(n - 1):
        l2, cache = T.decode_step(CFG, PARAMS, cache,
                                  jnp.asarray([[out[-1]]], jnp.int32))
        out.append(int(jnp.argmax(l2[0])))
    return out


def prompts_of_lengths(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(x) for x in rng.integers(0, CFG.vocab_size, n)]
            for n in lens]


def test_paged_prefill_chunk_matches_dense_prefill():
    """Driving the sharded prefill chunk by hand over a multi-chunk
    prompt — one head group's chain on a REMOTE pool shard — reproduces
    T.prefill's last-token logits AND pool-stored K/V."""
    prompt = prompts_of_lengths([21], seed=3)[0]    # 2.6 pages
    ctx = len(prompt)
    ref_logits, cache = T.prefill(CFG, PARAMS,
                                  {"tokens": jnp.asarray(prompt,
                                                         jnp.int32)[None]},
                                  max_seq=64)
    kv = PagedHeadCache(CFG, {0: 8, 1: 8}, page_size=PAGE, stage_slots=4)
    for g in range(CFG.n_kv_heads):
        kv.ensure_capacity(0, g, g % 2, ctx)
    Hkv, chunk = CFG.n_kv_heads, 8
    maxp = -(-ctx // PAGE)
    logits = None
    staged = 0
    for s0 in range(0, ctx, chunk):
        n = min(chunk, ctx - s0)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :n] = prompt[s0:s0 + n]
        wslots = np.full((1, Hkv, chunk), kv.sink, np.int32)
        woffs = np.zeros((1, chunk), np.int32)
        plan = kv.step_plan()
        slots, offs = plan.scatter_indices(0, s0, n)
        wslots[0, :, :n] = slots
        woffs[0, :n] = offs
        tables = plan.block_table_matrix(0, maxp, n_tokens=s0 + n)[None]
        staged += plan.gather_count
        exch = tuple(jnp.asarray(a) for a in
                     plan.exchange_arrays(max(1, plan.gather_count)))
        kps, vps = kv.pools()
        logits, kps, vps = T.sharded_prefill_chunk(
            CFG, PARAMS, kps, vps, kv.anchor, kv.sink, *exch,
            jnp.asarray(tables),
            jnp.asarray([s0 + n], jnp.int32), jnp.asarray([s0], jnp.int32),
            jnp.asarray(wslots), jnp.asarray(woffs), jnp.asarray(toks),
            jnp.asarray([n - 1], jnp.int32))
        kv.install_pools(kps, vps)
    assert staged > 0                   # the remote chain really staged
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=2e-4, atol=2e-4)
    # pool contents must equal the dense prefill cache, token for token
    for g in range(CFG.n_kv_heads):
        kv.lengths[(0, g)] = ctx
    K, V = kv.gather_dense(0, ctx)
    np.testing.assert_allclose(
        K, np.asarray(cache["groups"][0]["k"][:, 0, :ctx]),
        rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        V, np.asarray(cache["groups"][0]["v"][:, 0, :ctx]),
        rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("decode_mode", ["paged", "dense"])
def test_chunked_prefill_token_exact_odd_lengths(decode_mode):
    """Prompt lengths crossing every page/chunk boundary case: 1, page-1,
    page, page+1, multi-page — chunked == dense prefill == plain decode."""
    lens = [1, PAGE - 1, PAGE, PAGE + 1, 3 * PAGE + 5]
    prompts = prompts_of_lengths(lens)
    outs = {}
    for pmode in ("paged", "dense"):
        eng = make_engine(prefill_mode=pmode, decode_mode=decode_mode)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new_tokens=5))
        eng.run_until_drained(400)
        assert len(eng.finished) == len(prompts)
        eng.kv.check_invariants()
        outs[pmode] = {r.rid: r.output for r in eng.finished}
    assert outs["paged"] == outs["dense"]
    for i, p in enumerate(prompts):
        assert outs["paged"][i] == ref_decode(p, 5)


def test_chunked_prefill_interleaves_with_decode():
    """A long prompt arriving mid-decode must NOT stall the running batch:
    while it prefills chunk by chunk, already-running requests keep
    producing tokens each step (and all streams stay exact)."""
    eng = make_engine(chunk=8)
    short = prompts_of_lengths([4, 5], seed=1)
    eng.submit(Request(rid=0, prompt=short[0], max_new_tokens=12))
    eng.submit(Request(rid=1, prompt=short[1], max_new_tokens=12))
    eng.step()
    assert len(eng.running) == 2
    long_prompt = prompts_of_lengths([33], seed=2)[0]   # 5 chunks
    eng.submit(Request(rid=2, prompt=long_prompt, max_new_tokens=3,
                       arrival=eng.clock))
    produced = []
    for _ in range(4):
        before = [len(r.output) for r in eng.running if r.rid != 2]
        eng.step()
        after = [len(r.output) for r in eng.running if r.rid != 2]
        produced.append(any(a > b for a, b in zip(after, before)))
    # decode advanced during the long prompt's chunked prefill
    assert all(produced)
    assert any(r.rid == 2 for r in eng.prefilling + eng.running)
    eng.run_until_drained(400)
    assert len(eng.finished) == 3
    for r in eng.finished:
        assert r.output == ref_decode(r.prompt, r.max_new_tokens)


def test_chunked_prefill_resume_after_preemption():
    """Preempted requests lose their pages mid-stream and resume via
    chunked REPLAY prefill (prompt + generated tokens) — exactness must
    survive the round trip, including multi-chunk replays."""
    eng = make_engine(chunk=8)
    prompts = prompts_of_lengths([11, 17, 9, 14], seed=4)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=10))
    for _ in range(4):
        eng.step()
    victims = [r for r in eng.running if r.output][:2]
    assert victims
    for r in victims:
        eng._preempt(r)
        assert r.prefill_pos == 0
    eng.kv.check_invariants()
    eng.run_until_drained(800)
    assert len(eng.finished) == 4
    assert eng.metrics["evictions"] >= 2
    for r in eng.finished:
        assert r.output == ref_decode(r.prompt, r.max_new_tokens)


def test_prefill_recompile_guard_bucketed_shapes():
    """>= 50 varied-length requests: total chunked-prefill compiles stay
    within prefill_bucket_count() (the bucketing contract).  Pinned to
    the split schedule — the fused path has its own guard in
    tests/test_fused_step.py."""
    eng = make_engine(chunk=8, max_seq=64, step_mode="split")
    rng = np.random.default_rng(11)
    n_req = 50
    for i in range(n_req):
        eng.submit(Request(
            rid=i,
            prompt=[int(x) for x in rng.integers(0, CFG.vocab_size,
                                                 rng.integers(1, 25))],
            max_new_tokens=1))
    eng.run_until_drained(600)
    assert len(eng.finished) == n_req
    assert eng.metrics["prefill_chunks"] > 0
    assert eng.prefill_compile_count() <= eng.prefill_bucket_count(), \
        (eng.prefill_compile_count(), eng.prefill_bucket_count())
    # bucketing really was exercised by multiple distinct shapes
    assert len(eng._prefill_shapes) >= 2
    # prefill traffic was metered, and TTFT percentiles recorded
    assert eng.metrics["prefill_h2d_bytes"] > 0
    assert eng.metrics["ttft_p95"] >= eng.metrics["ttft_p50"] > 0


def test_chunked_prefill_no_dense_intermediate():
    """The paged prefill path must never materialize the dense max_seq
    cache: neither T.prefill nor store_prompt_request may run."""
    eng = make_engine()
    assert eng.use_paged_prefill

    def boom(*a, **k):
        raise AssertionError("dense prefill path hit on the chunked path")

    eng._prefill_fn = boom
    eng.kv.store_prompt_request = boom
    for i, p in enumerate(prompts_of_lengths([5, 12, 19], seed=6)):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=4))
    eng.run_until_drained(300)
    assert len(eng.finished) == 3


# rows of a mixed chunk batch as (start, n valid tokens); None pads a row
WRITE_ROWS = [(0, 32),      # page-aligned, two whole pages
              (5, 32),      # mid-page, three pages
              (20, 12),     # mid-page, two pages, a prompt's last chunk
              (31, 32),     # one token on the first page, three pages
              (16, 17),     # two pages, one token on the second
              (47, 1),      # a decode row, padded to the chunk
              None]         # a padded row


@pytest.mark.parametrize("dtype", ["bfloat16", "float8_e4m3fn"])
@pytest.mark.parametrize("dh", [64, 96, 128])
def test_chunk_page_write_matches_token_scatter(dh, dtype):
    """``attn.write_chunk_pages`` leaves the stacked pool bit-identical,
    outside the sink slot, to the token-row scatter it replaced, on
    chunks that start mid-page and span one to three pages, a decode row,
    and a padded row."""
    from repro.models.attention import write_chunk_pages
    L, page, C, Hkv, idx = 3, 16, 32, 2, 1
    B = len(WRITE_ROWS)
    n_slots = 64
    sink = n_slots - 1
    rng = np.random.default_rng(dh)
    # each (row, group) owns its own pages, as the allocator hands them out
    chains = rng.permutation(sink)[:B * Hkv * 4].reshape(B, Hkv, 4)
    slots = np.full((B, Hkv, C), sink, np.int32)
    offs = np.zeros((B, C), np.int32)
    for b, row in enumerate(WRITE_ROWS):
        if row is None:
            continue
        start, n = row
        t = np.arange(start, start + n)
        slots[b, :, :n] = chains[b][:, t // page]
        offs[b, :n] = t % page
    pool = jnp.asarray(rng.normal(size=(L, n_slots, page, dh)), dtype)
    kv = jnp.asarray(rng.normal(size=(B, C, Hkv, dh)), jnp.bfloat16)
    slots, offs = jnp.asarray(slots), jnp.asarray(offs)
    want = pool.at[idx, slots, offs[:, None, :]].set(
        jnp.swapaxes(kv, 1, 2).astype(pool.dtype))
    got = jax.jit(write_chunk_pages)(pool, jnp.int32(idx), slots, offs, kv)
    assert got.dtype == pool.dtype and got.shape == pool.shape
    bits = jnp.uint16 if pool.dtype.itemsize == 2 else jnp.uint8
    as_bits = lambda a: np.asarray(  # noqa: E731
        jax.lax.bitcast_convert_type(a, bits))[:, :sink]
    np.testing.assert_array_equal(as_bits(got), as_bits(want))
    # the chunk's tokens really landed outside the sink
    assert not np.array_equal(as_bits(got), as_bits(pool))
