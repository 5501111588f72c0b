"""Small sizes at which the CPU tests run a cell: the cell's own files,
with the model cut to two layers of width 128 and the traffic scaled to a
96-token context.  Shared by the ``test_bench_*`` files."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

SMALL = {
    # eight KV groups: with fewer the dispatcher keeps every group on the
    # primary, and the pool shards' exchange would go untested
    "config": {"num_hidden_layers": 2, "hidden_size": 128,
               "num_attention_heads": 8, "num_key_value_heads": 8,
               "intermediate_size": 128, "vocab_size": 256,
               "serve": {"max_seq": 96, "max_batch": 4}},
    "mix": {"prompt": {"min": 4, "max": 48, "mean": 20},
            "output": {"min": 2, "max": 40, "mean": 10}},
    # gap_limit: over six seeds with 150 tokens compared, the bf16 program
    # read 0-0.015 and the fp8 control 0.26-0.58 at this size on the CPU
    "cell": {"rate": 3.0, "lead_in_s": 1.0, "drain_s": 20.0,
             "check_tokens": 20,
             "gap_limit": 0.1},
}

CELLS = ("qwen1.5-0.5b.code_poisson", "phi3-mini-3.8b.decode_sat")
