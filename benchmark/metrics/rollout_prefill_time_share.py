"""Per-layer metric ``rollout_prefill_time_share``.

Own device time of every operation of a program OTHER than the decode
macro-step (the local prefill, the tail prefill over a cached prefix, the
group fork, a push's snapshot copy) over the device's busy time in the traced
window: what admission costs a rollout worker.  An operation's program is its
``XLA Modules`` name, joined by ``program_id`` (``op_scopes.py``).  Lower is
better.  A run that was not traced, or a trace whose events carry no
``op_name``, gives nothing.
"""

import op_scopes

NAME = "rollout_prefill_time_share"
UNIT = "%"
LAYER = "generation engine"
MOVES = "rollout_tokens_per_s"


def read(r):
    return op_scopes.share(r, NAME, lambda row: not op_scopes.is_decode(row))
