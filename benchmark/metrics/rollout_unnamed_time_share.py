"""Per-layer metric ``rollout_unnamed_time_share``.

Own device time of the operations whose ``op_name`` says no more than
the program's ``jit(...)``, the model's class or a bare ``block_N``
(``op_scopes.py``'s ``(unnamed)`` rows, of every program) over the device's busy
time in the traced window: the naming's own health.  Where it grows, a phase
of a hot program wants a ``jax.named_scope``.  Lower is better.  A run that
was not traced, or a trace whose events carry no ``op_name``, gives nothing.
"""

import op_scopes

NAME = "rollout_unnamed_time_share"
UNIT = "%"
LAYER = "generation engine"
MOVES = "rollout_tokens_per_s"


def read(r):
    return op_scopes.share(r, NAME, lambda row: op_scopes.class_of(row.scope) == "unnamed")
