"""Open-loop request streams from a traffic mix's data file.

A mix (``bench/traffic/<mix>.json``) gives the length distributions of
prompts and outputs and the arrival process; the cell gives the rate.
The schedule of a stream (every request's due time, prompt length and
output length) is one draw from the mix's fixed ``shape_seed``, the same
for every seed; the run's seed draws the token ids (and, in the harness,
the weights).  So every seed offers the same work at the same times and
reaches the same compiled programs: a seed that reordered the requests
would change how much of the work falls into the window.

The lognormal arithmetic is copied from ``repro.sim.workloads`` (the
paper's HumanEval/ShareGPT/LongBench shapes), not imported: the yardstick
does not move with the program.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class StreamRequest:
    index: int
    due_s: float              # offset from the stream's start
    prompt: tuple
    max_new_tokens: int


def lognormal_with_mean(rng: np.random.Generator, mean: float, sigma: float,
                        n: int) -> np.ndarray:
    """Lognormal draws whose mean is ``mean`` (copy of
    ``repro.sim.workloads._lognormal_with_mean``)."""
    mu = np.log(mean) - sigma ** 2 / 2.0
    return rng.lognormal(mu, sigma, n)


def draw_lengths(dist: Dict, rng: np.random.Generator, n: int) -> np.ndarray:
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "lognormal":
        x = lognormal_with_mean(rng, float(dist["mean"]), float(dist["sigma"]),
                                n)
        return np.clip(x, lo, hi).astype(np.int64)
    if dist["dist"] == "uniform":
        return rng.integers(lo, hi + 1, n)
    raise ValueError(f"unknown length distribution {dist['dist']!r}")


def draw_gaps(mix: Dict, rate: float, rng: np.random.Generator,
              n: int) -> np.ndarray:
    if mix["arrivals"] == "poisson":
        return rng.exponential(1.0 / rate, n)
    raise ValueError(f"unknown arrival process {mix['arrivals']!r}")


def stream_length(rate: float, horizon_s: float) -> int:
    """Gaps drawn for a stream of ``horizon_s`` seconds: the mean count
    with room for a Poisson excess."""
    mean = rate * horizon_s
    return int(math.ceil(mean + 6.0 * math.sqrt(mean + 1.0))) + 8


def schedule(mix: Dict, rate: float, horizon_s: float, burst: int = 0
             ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The stream's fixed schedule, drawn from the mix's ``shape_seed``
    alone: due times (``burst`` at 0, then the arrival process up to
    ``horizon_s``), and a prompt and an output length per request."""
    rng = np.random.default_rng(int(mix.get("shape_seed", 0)))
    due = np.cumsum(draw_gaps(mix, rate, rng,
                              stream_length(rate, horizon_s)))
    due = np.concatenate([np.zeros(burst), due[due <= horizon_s]])
    n = len(due)
    return due, draw_lengths(mix["prompt"], rng, n), draw_lengths(
        mix["output"], rng, n)


def make_stream(mix: Dict, rate: float, horizon_s: float, seed: int,
                vocab: int, max_seq: int, burst: int = 0
                ) -> List[StreamRequest]:
    """The requests of ``mix`` at ``rate`` req/s due over ``horizon_s``
    seconds, led by ``burst`` requests all due at 0 (a cell that starts
    with a full batch).  The schedule (``schedule``) is the same for every
    seed; the seed draws the token ids."""
    due, prompts, outputs = schedule(mix, rate, horizon_s, burst)
    if int((prompts + outputs).max(initial=0)) > max_seq:
        raise ValueError(f"mix {mix['name']!r} can exceed max_seq {max_seq}")
    rng = np.random.default_rng(seed)
    return [StreamRequest(i, float(due[i]),
                          tuple(int(x) for x in rng.integers(
                              0, vocab, int(prompts[i]))),
                          int(outputs[i]))
            for i in range(len(due))]
