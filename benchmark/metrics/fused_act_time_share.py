"""Per-layer metric ``fused_act_time_share``.

Own device time under the fused iteration's ``act`` scope (the actor's
forward and the action draw) over the device's busy time in the traced
window.  Not listed in any cell yet: ``python benchmark/program_trace.py <trace>
fused_act_time_share`` reads it from any traced run of the fused loop.
"""

import op_scopes

NAME = "fused_act_time_share"
UNIT = "%"
LAYER = "fused classic loop"
MOVES = "env_frames_per_s"


def read(r):
    return op_scopes.share(r, NAME, lambda row: op_scopes.under(row.scope, "act"))
