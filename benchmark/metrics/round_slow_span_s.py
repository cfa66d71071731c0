"""Per-layer metric ``round_slow_span_s``.

Seconds in the union of the intervals of the program's ``slow_span``
events that began inside the window (a slow read inside a slow macro-step
inside a slow round counts once).  0 in a healthy run; the events
themselves are in the run's log.
"""

import readers

NAME = "round_slow_span_s"
UNIT = "s"
LAYER = "round"
MOVES = "rollout_tokens_per_s"


def read(r):
    return readers.counter(r, "slow_span_s")
