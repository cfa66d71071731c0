"""Per-layer metric ``learn_moe_max_load``.

The largest expert's share of the real tokens' picks times the number of
experts the router scores, all routed layers of a learn step together
(the learner's own metric ``moe_max_load``), averaged over the window's
steps.  1 is a perfectly even router; it is the imbalance the dropless
grouped matmuls had to carry.  A counter, so a CPU rehearsal reads it too.
"""

import readers

NAME = "learn_moe_max_load"
UNIT = "ratio"
LAYER = "experts"
MOVES = "learn_tokens_per_s"


def read(r):
    return readers.counter(r, "learn_moe_max_load")
