"""Per-layer metric ``fused_mosaic_time_share``.

Device time inside Mosaic custom calls (the program's Pallas kernels) over
the device's busy time, from the profiler trace.  The kernels carry no
stable names yet, so this cannot tell one kernel from another.
"""

import readers

NAME = "fused_mosaic_time_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "env_frames_per_s"


def read(r):
    return readers.mosaic_share(r)
