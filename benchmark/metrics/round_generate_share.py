"""Per-layer metric ``round_generate_share``.

Share of the rounds' time inside ``round.generate``: the engine's
macro-steps until the round's group has finished.  The rest of a round
makes no response token, so the loop's rate is the engine's own times this
share.
"""

import round_spans

NAME = "round_generate_share"
UNIT = "%"
LAYER = "round"
MOVES = "rollout_tokens_per_s"


def read(r):
    return round_spans.share(r, "generate")
