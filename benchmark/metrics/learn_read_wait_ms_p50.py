"""Per-layer metric ``learn_read_wait_ms_p50``.

Median of the program span ``scalerl.dispatch.read`` inside
``scalerl.learn.step`` in the traced window: the one batched read of the
step's metrics, which is the step's device time as the host sees it.
"""

import program_trace

NAME = "learn_read_wait_ms_p50"
UNIT = "ms"
LAYER = "learner"
MOVES = "learn_tokens_per_s"


def read(r):
    return program_trace.p50_ms(
        r, NAME, lambda p: p.durations_ms("scalerl.dispatch.read", inside="scalerl.learn.step")
    )
