"""Per-layer metric ``segment_flash_fwd_time_share``.

Device time in the ``segment_flash_fwd`` kernel (the packed-row flash
attention's forward pass) over the device's busy time, from the profiler
trace; the kernel is found by the name the program gives its
``pallas_call``.
"""

import program_trace

NAME = "segment_flash_fwd_time_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "learn_tokens_per_s"


def read(r):
    return program_trace.kernel_share(r, NAME, ("segment_flash_fwd",))
