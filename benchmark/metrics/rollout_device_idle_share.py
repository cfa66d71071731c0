"""Per-layer metric ``rollout_device_idle_share``.

One minus the union of the device-operation intervals over the traced
window, from the profiler trace; averaged over the chips used.
"""

import readers

NAME = "rollout_device_idle_share"
UNIT = "%"
LAYER = "device"
MOVES = "rollout_tokens_per_s"


def read(r):
    return readers.idle_share(r)
