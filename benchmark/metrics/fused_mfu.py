"""Per-layer metric ``fused_mfu``.

Model operations of the frames completed in the window (one actor forward,
one learner forward and backward per frame, ``work.py``) per second, over
the chip's bf16 peak.  The rate is this traced run's own.
"""

import readers

NAME = "fused_mfu"
UNIT = "%"
LAYER = "fused classic loop"
MOVES = "env_frames_per_s"


def read(r):
    import work

    rate = r["result"]["end_to_end"].get("env_frames_per_s")
    return readers.mfu(r, work.impala_flops_per_frame(r["ctx"].config), rate)
