"""Per-layer metric ``moe_max_load``.

The largest expert's tokens over the mean expert's, from the tokens each
expert of each layer received from live decode lanes inside the window
(the engine's ``stats()`` ``expert_tokens`` at the window's two ends),
averaged over the layers.  1 is a perfectly even router; the expert that
sets it is the one a capacity limit would have dropped tokens from.
"""

import readers

NAME = "moe_max_load"
UNIT = "ratio"
LAYER = "experts"
MOVES = "rollout_tokens_per_s"


def read(r):
    return readers.counter(r, "moe_max_load")
