"""Per-layer metric ``learn_forward_time_share``.

Own device time of the learn step's forward pass (operations whose
``op_name`` carries ``jvp(...)`` and no ``transpose``: the model, the loss) over
the device's busy time in the traced window.  Not listed in any cell yet:
``python benchmark/program_trace.py <trace> learn_forward_time_share`` reads it
from any traced learn run.
"""

import op_scopes

NAME = "learn_forward_time_share"
UNIT = "%"
LAYER = "learner"
MOVES = "learn_tokens_per_s"


def read(r):
    return op_scopes.share(r, NAME, lambda row: op_scopes.direction_of(row.scope) == "fwd")
