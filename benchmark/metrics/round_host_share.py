"""Per-layer metric ``round_host_share``.

Share of the rounds' time inside ``round.score``, ``round.pack`` and
``round.seq_add`` plus the root's own time (what no child covers): the
host between the engine and the learner.  With the other four
``round_*_share`` it sums to 100.
"""

import round_spans

NAME = "round_host_share"
UNIT = "%"
LAYER = "round"
MOVES = "rollout_tokens_per_s"


def read(r):
    return round_spans.share(r, "host")
