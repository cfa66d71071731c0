"""Per-layer metric ``compiles_in_window``.

Programs built (compiled or read from the cache) inside the measured
window, from JAX's monitoring events.  Must read 0: otherwise the warm-up
missed a shape, and the window paid for it.
"""



NAME = "compiles_in_window"
UNIT = "count"
LAYER = "entry and set-up"
MOVES = "setup_s"


def read(r):
    return r["ctx"].window_compile["builds"]
