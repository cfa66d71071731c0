"""Per-layer metric ``compile_s``.

Seconds JAX spent building programs during set-up (compiling, or reading
the persistent cache), from JAX's own monitoring events.
"""



NAME = "compile_s"
UNIT = "s"
LAYER = "entry and set-up"
MOVES = "setup_s"


def read(r):
    return r["ctx"].setup_compile["seconds"]
