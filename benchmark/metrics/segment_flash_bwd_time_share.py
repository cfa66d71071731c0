"""Per-layer metric ``segment_flash_bwd_time_share``.

Device time in the ``segment_flash_bwd_dq`` and ``segment_flash_bwd_dkv``
kernels (the packed-row flash attention's backward pass) over the device's
busy time, from the profiler trace; the kernels are found by the names the
program gives its ``pallas_call``s.
"""

import program_trace

NAME = "segment_flash_bwd_time_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "learn_tokens_per_s"


def read(r):
    return program_trace.kernel_share(r, NAME, ("segment_flash_bwd_dq", "segment_flash_bwd_dkv"))
