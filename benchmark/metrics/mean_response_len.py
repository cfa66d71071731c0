"""Per-layer metric ``mean_response_len``.

Mean response length of the sequences completed inside the window.  It is
set by the traffic (EOS bias), not by the engine: a drift here means the
traffic itself changed, and the rate with it.
"""

import readers

NAME = "mean_response_len"
UNIT = "tokens"
LAYER = "generation engine"
MOVES = "rollout_tokens_per_s"


def read(r):
    return readers.counter(r, "mean_response_len")
