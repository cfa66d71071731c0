"""Per-layer metric ``learn_update_time_share``.

Own device time of the learn step's ``update`` (the optimiser's pass and
the new parameters) and ``guard`` (the all-finite select, whose name XLA gives
the fused pass of both) scopes over the device's busy time in the traced
window; the gradient norm's own reduction is 0.5% and has no scope (PR 54).
Not listed in any cell yet: ``python benchmark/program_trace.py <trace>
learn_update_time_share`` reads it from any traced learn run.
"""

import op_scopes

NAME = "learn_update_time_share"
UNIT = "%"
LAYER = "learner"
MOVES = "learn_tokens_per_s"


def read(r):
    return op_scopes.share(r, NAME, lambda row: op_scopes.class_of(row.scope) == "update")
