"""Per-layer metric ``learn_mfu``.

Model operations per real token (forward and backward, matmuls and causal
in-segment attention, ``work.py``; recomputation not counted) times real
tokens per second, over chips times the bf16 peak.  The rate is this
traced run's own.
"""

import readers

NAME = "learn_mfu"
UNIT = "%"
LAYER = "learner"
MOVES = "learn_tokens_per_s"


def read(r):
    rate = r["result"]["end_to_end"].get("learn_tokens_per_s")
    return readers.mfu(r, readers.counter(r, "train_flops_per_token"), rate)
