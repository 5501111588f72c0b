"""The comparison that decides ``correct`` fails a broken timed path: the
harness's run on the CPU at a small size with the engine broken
underneath it, once for each fault a served cell can have — a served
token altered where it is produced, and the exchange of pages between
pool shards left out.  Also the control: the reference in fp8 reads a
wider gap than the limit, and the program a narrower one."""

import copy
import time

import jax.numpy as jnp
import pytest

from bench_cpu_sizes import CELLS, SMALL
from bench import harness, loadgen, oracle


def _run(cell, seed, monkeypatch, check_tokens=None):
    # a virtual clock (each step 50 ms) makes the served work, and so what
    # the check compares, the same on a loaded machine as on an idle one
    monkeypatch.setattr(harness, "WallClock",
                        lambda: harness.VirtualClock(0.05))
    over = copy.deepcopy(SMALL)
    if check_tokens is not None:
        over["cell"]["check_tokens"] = check_tokens
    sp = harness.load_spec(cell, overrides=over)
    return harness.run_cell(sp, seed, 3.0, False,
                            t_start=time.perf_counter(), log=lambda m: None)


class _AlterFirstRow:
    """The fused step with row 0's served token replaced by another."""

    def __init__(self, fn):
        self.fn = fn

    def __getattr__(self, name):
        return getattr(self.fn, name)

    def __call__(self, *args):
        logits, kps, vps = self.fn(*args)
        bad = (jnp.argmax(logits[0]) + 1) % logits.shape[-1]
        return logits.at[0, bad].set(1e4), kps, vps


def test_altered_token_is_not_correct(monkeypatch):
    build = harness.build_engine

    def broken(*a, **k):
        eng = build(*a, **k)
        eng._fused_fn = _AlterFirstRow(eng._fused_fn)
        return eng

    monkeypatch.setattr(harness, "build_engine", broken)
    out = _run(CELLS[0], 5, monkeypatch)
    assert out["correct"] is False
    assert out["checks"]["logit_gap"]["value"] > \
        out["checks"]["logit_gap"]["limit"]


# with more tokens compared than the twenty of the other small runs (on
# the chip a run compares some hundreds): a few requests may keep all
# their pages on the anchor shard, where the exchange left out changes
# nothing
@pytest.mark.parametrize("cell", CELLS)
def test_exchange_left_out_is_not_correct(monkeypatch, cell):
    from repro.models import transformer as T
    monkeypatch.setattr(T, "_pool_exchange_in",
                        lambda kp, vp, anchor, *a: (kp[anchor], vp[anchor]))
    out = _run(cell, 6, monkeypatch, check_tokens=120)
    assert out["correct"] is False
    assert out["checks"]["logit_gap"]["value"] > \
        out["checks"]["logit_gap"]["limit"]


def test_fp8_control_fails_the_limit_the_program_meets():
    """The readings ``bench/control.py`` takes on the chip, here at the
    small size: the cell's traffic served on a virtual clock (so the
    served work does not depend on the host's speed), then the
    program's widest gap and the fp8 control's over the same sample."""
    sp = harness.load_spec(CELLS[0], overrides=SMALL)
    ref = harness.reference_module(sp.config)
    cfg = harness.model_config(sp.config, ref)
    limit = SMALL["cell"]["gap_limit"]
    max_seq = int(sp.serve["max_seq"])
    for seed in (21, 22):
        params = ref.make_params(sp.config, seed)
        eng = harness.build_engine(cfg, params, sp.serve, telemetry=False)
        stream = loadgen.make_stream(sp.mix, 3.0, 13.0, seed,
                                     cfg.vocab_size, max_seq)
        loop = harness.OpenLoop(eng, stream, harness.VirtualClock(0.05))
        loop.run(10.0)
        # the widest gap needs enough compared tokens for fp8 to misrank
        samples = oracle.served_samples(loop.tracks, seed, 150)
        prog = oracle.widest_gap(ref, sp.config, params, samples, max_seq)
        ctrl = oracle.control_gap(ref, sp.config, params, samples, max_seq)
        assert prog["tokens"] >= 150
        assert prog["gap"] <= limit < ctrl["gap"], (seed, prog, ctrl)
        # the run's own verdict: the control, in the program's place, is
        # not correct
        assert harness.judge(sp.cell, prog, 0)[1] is True
        assert harness.judge(sp.cell, ctrl, 0)[1] is False
