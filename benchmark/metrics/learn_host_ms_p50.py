"""Per-layer metric ``learn_host_ms_p50``.

Median, over the learn steps in the traced window, of the program span
``scalerl.learn.step`` less its ``scalerl.dispatch.read`` child: sharding
the batch onto the chips, the enqueue, and the Python around them.
"""

import program_trace

NAME = "learn_host_ms_p50"
UNIT = "ms"
LAYER = "learner"
MOVES = "learn_tokens_per_s"


def read(r):
    return program_trace.p50_ms(
        r, NAME, lambda p: p.less_ms("scalerl.learn.step", "scalerl.dispatch.read")
    )
