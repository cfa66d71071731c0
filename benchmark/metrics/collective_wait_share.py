"""Per-layer metric ``collective_wait_share``.

``collective_exposed_share`` with ``async-collective-start`` and
``async-collective-done`` counted as collectives too: a LOWER bound of the
exposed link time of a program compiled with asynchronous collective
fusions (``link_ops.py``), and ``collective_exposed_share`` itself for a
program that has none.
"""

import link_ops

NAME = "collective_wait_share"
UNIT = "%"
LAYER = "sharding"
MOVES = "learn_tokens_per_s"


def read(r):
    return link_ops.share(r, "with_waits")
