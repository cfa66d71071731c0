"""Per-layer metric ``rollout_ffn_time_share``.

Own device time of the decode macro-step's operations under a layer's
channel mixer (its norm, ``mlp_in`` and ``mlp_out``, or the router, the experts
and the shared expert: ``op_scopes.CLASS_OF``) over the device's busy time in
the traced window.  Lower is better.  A run that was not traced, or a trace
whose events carry no ``op_name``, gives nothing.
"""

import op_scopes

NAME = "rollout_ffn_time_share"
UNIT = "%"
LAYER = "experts"
MOVES = "rollout_tokens_per_s"


def read(r):
    return op_scopes.share(r, NAME, lambda row: op_scopes.is_decode(row) and op_scopes.class_of(row.scope) == "ffn")
