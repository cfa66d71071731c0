"""Per-layer metric ``learn_step_ms_p50``.

Median of the benchmark span around ``TokenPPOAgent.learn``
(``bench.learn_call``) inside the window: shard the batch, run the step,
read its metrics back.
"""

import readers

NAME = "learn_step_ms_p50"
UNIT = "ms"
LAYER = "learner"
MOVES = "learn_tokens_per_s"


def read(r):
    return readers.span_p50_ms(r, "bench.learn_call")
