"""Per-layer metric ``round_push_share``.

Share of the rounds' time inside ``round.push``, host side: the enqueue
of the device-side copy of every leaf and the prefix cache's flush.  What
the copies cost the device shows in the next round's first read.
"""

import round_spans

NAME = "round_push_share"
UNIT = "%"
LAYER = "round"
MOVES = "rollout_tokens_per_s"


def read(r):
    return round_spans.share(r, "push")
