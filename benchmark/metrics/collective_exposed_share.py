"""Per-layer metric ``collective_exposed_share``.

Time of collective operations during which no other operation ran on that
device, over the device's busy time, from the profiler trace; averaged
over the chips.
"""

import readers

NAME = "collective_exposed_share"
UNIT = "%"
LAYER = "sharding"
MOVES = "learn_tokens_per_s"


def read(r):
    return readers.collective_exposed_share(r)
