"""Per-layer metric ``engine_host_ms_p50``.

Median, over the engine cycles in the traced window, of the program span
``scalerl.genrl.macro_step`` less its ``scalerl.genrl.read`` child: the
host's own work per macro-step (admission, the table upload and the
enqueue, the per-lane harvest, Python between them).  Once the device's
macro-step is no longer than this, the host sets the pace.
"""

import program_trace

NAME = "engine_host_ms_p50"
UNIT = "ms"
LAYER = "generation engine"
MOVES = "rollout_tokens_per_s"


def read(r):
    return program_trace.p50_ms(
        r, NAME, lambda p: p.less_ms("scalerl.genrl.macro_step", "scalerl.genrl.read")
    )
