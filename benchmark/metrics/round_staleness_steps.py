"""Per-layer metric ``round_staleness_steps``.

Mean over the window's rounds of the ``staleness`` the trainer reports:
learner steps between the weights that generated the sampled rows and the
newest push.  1 is a round learning from what it just made; the trainer's
prioritised ring holds older rows too.
"""

import readers

NAME = "round_staleness_steps"
UNIT = "count"
LAYER = "round"
MOVES = "rollout_tokens_per_s"


def read(r):
    return readers.counter(r, "staleness_mean")
