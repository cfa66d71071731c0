"""Per-layer metric ``learn_backward_time_share``.

Own device time of the learn step's backward pass (operations whose
``op_name`` carries ``transpose(jvp(...))``) over the device's busy time in the
traced window.  Not listed in any cell yet: ``python benchmark/program_trace.py
<trace> learn_backward_time_share`` reads it from any traced learn run.
"""

import op_scopes

NAME = "learn_backward_time_share"
UNIT = "%"
LAYER = "learner"
MOVES = "learn_tokens_per_s"


def read(r):
    return op_scopes.share(r, NAME, lambda row: op_scopes.direction_of(row.scope) == "bwd")
