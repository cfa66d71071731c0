"""Per-layer metric ``paged_attn_roofline``.

Roofline share of the paged decode-attention kernel: the least time the
chip could take for the cached K and V the traced window's decode steps
had to read (bytes from ``work.py`` and the completed sequences, over the
HBM peak; the kernel is bound by bytes, not operations) over the device
time in Mosaic calls.  In this traffic every Mosaic call is paged decode.
"""

import readers

NAME = "paged_attn_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "rollout_tokens_per_s"


def read(r):
    trace, peaks = r["trace"], r["peaks"]
    kv_bytes = readers.counter(r, "traced_kv_bytes")
    if trace is None or peaks is None or not kv_bytes or trace["mosaic_s"] <= 0:
        return None
    return 100.0 * (kv_bytes / peaks["hbm_bytes_per_s"]) / trace["mosaic_s"]
