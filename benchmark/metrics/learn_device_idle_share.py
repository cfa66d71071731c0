"""Per-layer metric ``learn_device_idle_share``.

One minus the union of the device-operation intervals over the traced
window, from the profiler trace; averaged over the chips used.
"""

import readers

NAME = "learn_device_idle_share"
UNIT = "%"
LAYER = "device"
MOVES = "learn_tokens_per_s"


def read(r):
    return readers.idle_share(r)
