"""Per-layer metric ``macro_step_ms_p50``.

Median of the benchmark span around ``engine.step()``
(``bench.engine_step``) inside the window: admission, one macro-step
dispatch, one lagging read and the harvest.
"""

import readers

NAME = "macro_step_ms_p50"
UNIT = "ms"
LAYER = "generation engine"
MOVES = "rollout_tokens_per_s"


def read(r):
    return readers.span_p50_ms(r, "bench.engine_step")
