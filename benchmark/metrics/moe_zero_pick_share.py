"""Per-layer metric ``moe_zero_pick_share``.

Share of the window's router picks, live decode lanes and all layers, that
fell on zero-compute (identity) experts: picks that cost no expert
matrix.  256 of the router's 768 outputs are such, so an even router
reads 33.3%.  From the engine's own counters (``stats()``
``zero_expert_tokens`` over all picks at the window's two ends).  The name
ends in ``_share``, which the benchmark keeps for what a chip run prints:
a CPU rehearsal (no peaks) gets nothing, and reads the same counters
through ``moe_held_picks_per_expert``.
"""

import readers

NAME = "moe_zero_pick_share"
UNIT = "%"
LAYER = "experts"
MOVES = "rollout_tokens_per_s"


def read(r):
    value = readers.counter(r, "moe_zero_pick_share")
    if value is None or r["peaks"] is None:
        return None
    return 100.0 * value
