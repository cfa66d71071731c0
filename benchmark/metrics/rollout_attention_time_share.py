"""Per-layer metric ``rollout_attention_time_share``.

Own device time of the decode macro-step's operations under a layer's
token mixer (its norm, ``qkv``, the cache write ``kv_write``, ``attend`` with
the paged kernel, ``proj``; a recurrent mixer or a latent attention in the
families that have one: ``op_scopes.CLASS_OF``) over the device's busy time in
the traced window.  Lower is better.  A run that was not traced, or a trace
whose events carry no ``op_name``, gives nothing.
"""

import op_scopes

NAME = "rollout_attention_time_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "rollout_tokens_per_s"


def read(r):
    return op_scopes.share(r, NAME, lambda row: op_scopes.is_decode(row) and op_scopes.class_of(row.scope) == "attention")
