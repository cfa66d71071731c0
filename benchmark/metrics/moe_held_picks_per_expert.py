"""Per-layer metric ``moe_held_picks_per_expert``.

Mean number of picks an expert held here received in one decode substep of
one layer, over the window: how near the cell comes to the load the
deployment would send an expert (one rank's lanes x 12 / 768 here, 32
ranks' worth there).  From the engine's own counters (``stats()``
``held_expert_tokens`` over ``expert_substeps`` x held experts at the
window's two ends).  A counter, so a CPU rehearsal reads it too.
"""

import readers

NAME = "moe_held_picks_per_expert"
UNIT = "count"
LAYER = "experts"
MOVES = "rollout_tokens_per_s"


def read(r):
    return readers.counter(r, "moe_held_picks_per_expert")
