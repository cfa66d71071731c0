"""Per-layer metric ``rollout_head_sampler_time_share``.

Own device time of the decode macro-step's operations behind the last
layer (``final_norm``, ``policy_head``, ``value_head``) and in the engine's
``sample`` scope (temperature and top-k, the categorical draw, the
log-probability gather, the key split) over the device's busy time in the
traced window: found by name, where ``wide_head_time_share`` guesses by shape.
Lower is better.  A run that was not traced, or a trace whose events carry no
``op_name``, gives nothing.
"""

import op_scopes

NAME = "rollout_head_sampler_time_share"
UNIT = "%"
LAYER = "generation engine"
MOVES = "rollout_tokens_per_s"


def read(r):
    return op_scopes.share(r, NAME, lambda row: op_scopes.is_decode(row) and op_scopes.class_of(row.scope) == "head_sampler")
