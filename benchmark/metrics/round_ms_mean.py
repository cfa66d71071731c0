"""Per-layer metric ``round_ms_mean``.

Mean length of a round in the window: the program span ``genrl.round``'s
seconds over its count, from the always-on span totals at the window's
two ends (no profiler, no sampling).
"""

import round_spans

NAME = "round_ms_mean"
UNIT = "ms"
LAYER = "round"
MOVES = "rollout_tokens_per_s"


def read(r):
    return round_spans.round_ms_mean(r)
