"""The load generator: seeded, deterministic, inside its clips, and one
schedule of lengths and due times for every seed."""

import json

import numpy as np
import pytest

from bench_cpu_sizes import ROOT
from bench import loadgen

MIXES = sorted((ROOT / "bench" / "traffic").glob("*.json"))


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_stream_is_seeded_and_clipped(path):
    mix = json.loads(path.read_text())
    a = loadgen.make_stream(mix, 2.0, 120.0, 2 ** 31 + 7, 1000, 512,
                            burst=4)
    b = loadgen.make_stream(mix, 2.0, 120.0, 2 ** 31 + 7, 1000, 512,
                            burst=4)
    assert a == b and len(a) > 200
    for r in a:
        assert mix["prompt"]["min"] <= len(r.prompt) <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= r.max_new_tokens \
            <= mix["output"]["max"]
        assert len(r.prompt) + r.max_new_tokens <= 512
        assert all(0 <= t < 1000 for t in r.prompt)
    due = [r.due_s for r in a]
    assert due[:4] == [0.0] * 4 and due[4] > 0.0
    assert all(x <= y for x, y in zip(due, due[1:])) and due[-1] <= 120.0


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_seeds_permute_one_multiset(path):
    """Seeds draw other tokens into one schedule: every request's due
    time, prompt length and output length are the seed's no matter."""
    mix = json.loads(path.read_text())
    a = loadgen.make_stream(mix, 1.5, 60.0, 1, 1000, 512, burst=3)
    b = loadgen.make_stream(mix, 1.5, 60.0, 2 ** 31 + 3, 1000, 512,
                            burst=3)
    assert [(r.due_s, len(r.prompt), r.max_new_tokens) for r in a] == \
        [(r.due_s, len(r.prompt), r.max_new_tokens) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert len(a) > 60 and len({len(r.prompt) for r in a}) > 10
    # a longer horizon extends the schedule and keeps what it had
    c = loadgen.make_stream(mix, 1.5, 90.0, 1, 1000, 512, burst=3)
    assert [r.due_s for r in c[:len(a)]] == [r.due_s for r in a]


def test_mix_longer_than_context_is_refused():
    mix = json.loads((ROOT / "bench/traffic/code_poisson.json").read_text())
    with pytest.raises(ValueError):
        loadgen.make_stream(mix, 1.0, 500.0, 0, 100, 256)


def test_lognormal_mean_matches():
    rng = np.random.default_rng(0)
    x = loadgen.lognormal_with_mean(rng, 140.0, 0.5, 200_000)
    assert abs(x.mean() - 140.0) < 1.0
