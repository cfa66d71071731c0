"""Per-layer metric ``round_learn_share``.

Share of the rounds' time inside ``round.sample`` and ``round.learn``:
the replay sample and the learn step up to its one blocking metric read.
"""

import round_spans

NAME = "round_learn_share"
UNIT = "%"
LAYER = "round"
MOVES = "rollout_tokens_per_s"


def read(r):
    return round_spans.share(r, "learn")
