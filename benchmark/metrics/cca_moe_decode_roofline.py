"""Per-layer metric ``cca_moe_decode_roofline``.

Roofline share of the ZAYA1 stack's decode substeps as a whole: the least
time the chip could take to move every byte the traced substeps had to
move (``zaya_work.py``: each layer's attention, convolution and router
matrices and the policy head once a substep, every expert's three matrices
once a substep whoever was picked (the streamed form a decode substep's
few tokens take reads every bank), each live lane's window in and out, the
live lanes' cached keys and values; over the HBM peak) over the traced
window's busy time.

Prefill programs and forks run inside the traced window too and their
time rides in the denominator, while their reads are not in the numerator:
the value is a lower bound on the decode substeps' own share, as
``hybrid_decode_roofline`` is.  A run whose driver counted no such bytes (a
program without the family) gives nothing.
"""

import readers

NAME = "cca_moe_decode_roofline"
UNIT = "%"
LAYER = "generation engine"
MOVES = "rollout_tokens_per_s"


def read(r):
    trace, peaks = r["trace"], r["peaks"]
    moved = readers.counter(r, "traced_cca_moe_bytes")
    if trace is None or peaks is None or not moved or trace["busy_s"] <= 0:
        return None
    r["ctx"].log(f"{NAME}: {moved / 1e9:.2f} GB to move in {trace['busy_s']:.3f} s busy")
    return 100.0 * (moved / peaks["hbm_bytes_per_s"]) / trace["busy_s"]
