"""Per-layer metric ``chunk_ms_p50``.

Median of the benchmark span between two consecutive chunk reads
(``bench.chunk_wait``) inside the window: one dispatch of the fused loop
as the host sees it.
"""

import readers

NAME = "chunk_ms_p50"
UNIT = "ms"
LAYER = "fused classic loop"
MOVES = "env_frames_per_s"


def read(r):
    return readers.span_p50_ms(r, "bench.chunk_wait")
