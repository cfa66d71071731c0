"""Per-layer metric ``engine_read_wait_ms_p50``.

Median of the program span ``scalerl.genrl.read`` in the traced window:
the one blocking read of the oldest macro-step in flight, which is the
device time the host could not hide.  It falls as the device gets faster;
near 0 means the host sets the pace and ``engine_host_ms_p50`` is the
number to cut.
"""

import program_trace

NAME = "engine_read_wait_ms_p50"
UNIT = "ms"
LAYER = "generation engine"
MOVES = "rollout_tokens_per_s"


def read(r):
    return program_trace.p50_ms(r, NAME, lambda p: p.durations_ms("scalerl.genrl.read"))
