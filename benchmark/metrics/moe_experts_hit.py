"""Per-layer metric ``moe_experts_hit``.

Mean, over the window's decode substeps and the model's layers, of the
share of the experts that received a token from a live lane: what decides
the expert bytes a substep has to read.  From the engine's own counters
(``stats()`` ``expert_hits`` over ``expert_substeps`` x experts at the
window's two ends).  A counter, so a CPU rehearsal reads it too; the name
does not end in ``_share`` because that ending is kept for what only a
device trace feeds.
"""

import readers

NAME = "moe_experts_hit"
UNIT = "%"
LAYER = "experts"
MOVES = "rollout_tokens_per_s"


def read(r):
    value = readers.counter(r, "moe_experts_hit")
    return None if value is None else 100.0 * value
