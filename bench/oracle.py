"""The comparison that decides ``correct``.

After the window, a sample of the requests the timed path served is drawn
from the seed (finished requests first, always with the one that served
the most tokens) until it holds ``check_tokens`` served tokens.  The plain
reference runs once over each prompt followed by its served tokens, and
at every served position reads how far the served token's logit lies
below the reference's best logit there.  The widest such gap over the
sample is compared with the cell's limit.  Greedy decoding serves the
arg-max of the program's own logits, so a sound run serves tokens that
are the reference's best or a near tie of it.

The control (``control_gap``) runs the same reference in fp8 and reads, at
the same positions of the same sequences, the gap of the token the fp8
pass puts first.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

Sample = Tuple[List[int], List[int]]      # (prompt, served tokens)


def served_samples(tracks, seed: int, check_tokens: int) -> List[Sample]:
    """Requests to check, drawn from ``seed``: the one that served the
    most tokens, then finished requests, then the rest, each group in a
    seeded order, until ``check_tokens`` served tokens are held."""
    served = [t for t in tracks if t.req.output]
    if not served:
        return []
    rng = np.random.default_rng([seed, 0x0AC1E])
    longest = max(served, key=lambda t: (len(t.req.output), -t.due_s))
    rest = [t for t in served if t is not longest]
    done = [t for t in rest if t.req.done]
    live = [t for t in rest if not t.req.done]
    order = ([longest] + [done[i] for i in rng.permutation(len(done))]
             + [live[i] for i in rng.permutation(len(live))])
    out, n = [], 0
    for t in order:
        if n >= check_tokens:
            break
        out.append((list(t.req.prompt), list(t.req.output)))
        n += len(t.req.output)
    return out


def _positions(sample: Sample, max_seq: int):
    """The sequence fed to the reference (prompt + served tokens but the
    last, padded to ``max_seq``), the next token at each position, and the
    positions at which a served token was predicted."""
    prompt, out = sample
    full = prompt + out
    toks = np.zeros((max_seq,), np.int32)
    nxt = np.zeros((max_seq,), np.int32)
    toks[:len(full) - 1] = full[:-1]
    nxt[:len(full) - 1] = full[1:]
    pos = np.arange(len(prompt) - 1, len(full) - 1)
    return toks, nxt, pos


def widest_gap(ref, conf: Dict, params, samples: Sequence[Sample],
               max_seq: int) -> Dict:
    """The widest gap between the reference's best logit and the served
    token's, over every served token of ``samples``."""
    gap, n = -np.inf, 0
    for s in samples:
        toks, nxt, pos = _positions(s, max_seq)
        r = ref.score(conf, params, toks, nxt)
        g = r["best"][pos] - r["target"][pos]
        gap = max(gap, float(np.max(g)))
        n += len(pos)
    return {"gap": float(gap) if n else float("inf"), "tokens": n}


def control_gap(ref, conf: Dict, params, samples: Sequence[Sample],
                max_seq: int, quant: str = "fp8") -> Dict:
    """The widest gap of the token that the lower-precision reference puts
    first, at the same positions of the same sequences."""
    gap, n = -np.inf, 0
    for s in samples:
        toks, nxt, pos = _positions(s, max_seq)
        low = ref.score(conf, params, toks, nxt, quant=quant)
        r = ref.score(conf, params, toks, low["argmax"].astype(np.int32))
        g = r["best"][pos] - r["target"][pos]
        gap = max(gap, float(np.max(g)))
        n += len(pos)
    return {"gap": float(gap) if n else float("inf"), "tokens": n}
