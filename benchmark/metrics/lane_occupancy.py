"""Per-layer metric ``lane_occupancy``.

Mean share of decode lanes that held a live sequence over the macro-steps
dispatched inside the window, from the engine's own ``stats()``
(``mean_occupancy`` x ``macro_steps`` at the window's two ends).
"""

import readers

NAME = "lane_occupancy"
UNIT = "%"
LAYER = "generation engine"
MOVES = "rollout_tokens_per_s"


def read(r):
    value = readers.counter(r, "lane_occupancy")
    return None if value is None else 100.0 * value
