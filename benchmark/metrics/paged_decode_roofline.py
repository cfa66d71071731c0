"""Per-layer metric ``paged_decode_roofline``.

Roofline share of the paged decode-attention kernel, found by the name
the program gives its ``pallas_call``: the least time the chip could take
for the cached K and V the traced window's decode steps had to read
(bytes from the cell's own count and the completed sequences, over the
HBM peak) over the device time in ``paged_decode``.  ``paged_attn_roofline``
divides by all Mosaic time, which is the same where paged decode is the
only Mosaic call; here the prefill's grouped expert matmuls are Mosaic
calls too.
"""

import program_trace
import readers

NAME = "paged_decode_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "rollout_tokens_per_s"


def read(r):
    peaks = r["peaks"]
    kv_bytes = readers.counter(r, "traced_kv_bytes")
    program = program_trace.of(r)
    if peaks is None or program is None or not kv_bytes:
        return None
    kernel_s = program.kernel_s.get("paged_decode", 0.0)
    if kernel_s <= 0:
        return None
    return 100.0 * (kv_bytes / peaks["hbm_bytes_per_s"]) / kernel_s
