"""Per-layer metric ``collective_link_share``.

``collective_wait_share`` with every fusion that calls an
``async_collective_fusion`` computation counted as a collective as well:
an UPPER bound of the exposed link time (the matmul inside such a fusion
is counted as link time), and ``collective_exposed_share`` itself for a
program that has none (``link_ops.py``).
"""

import link_ops

NAME = "collective_link_share"
UNIT = "%"
LAYER = "sharding"
MOVES = "learn_tokens_per_s"


def read(r):
    return link_ops.share(r, "with_fused")
