"""Per-layer metric ``learn_held_picks_per_expert``.

Mean number of picks an expert held here received in one routed layer of
one learn step, over the window's steps: how near the cell comes to the
load the deployment would send an expert (this chip's tokens x 8 / 256
here, 32 ranks' worth there).  From the learner's own metric
``moe_held_picks`` (the real tokens' picks of held experts, summed over
the step's routed layers, the multi-token-prediction module's among them)
over routed layers x held experts.  A counter, so a CPU rehearsal reads
it too.
"""

import readers

NAME = "learn_held_picks_per_expert"
UNIT = "count"
LAYER = "experts"
MOVES = "learn_tokens_per_s"


def read(r):
    return readers.counter(r, "learn_held_picks_per_expert")
