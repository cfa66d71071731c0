"""Per-layer metric ``real_token_frac``.

Share of the packed rows' token slots that hold a real token, the mean of
the learn step's own ``real_token_frac`` over the window's steps.
"""

import readers

NAME = "real_token_frac"
UNIT = "%"
LAYER = "learner"
MOVES = "learn_tokens_per_s"


def read(r):
    value = readers.counter(r, "real_token_frac")
    return None if value is None else 100.0 * value
