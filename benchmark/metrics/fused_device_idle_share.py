"""Per-layer metric ``fused_device_idle_share``.

One minus the union of the device-operation intervals over the traced
window, from the profiler trace; averaged over the chips used.
"""

import readers

NAME = "fused_device_idle_share"
UNIT = "%"
LAYER = "device"
MOVES = "env_frames_per_s"


def read(r):
    return readers.idle_share(r)
