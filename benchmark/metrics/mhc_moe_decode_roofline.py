"""Per-layer metric ``mhc_moe_decode_roofline``.

Roofline share of the Xing4.0 stack's decode substeps as a whole: the least
time the chip could take to move every byte the traced substeps had to
move (``xing4_work.py``: each layer's attention, the routed layers' router
and shared expert, the dense layer's FFN, every hyper-connection's ``Phi``
and the policy head once a substep, every expert's three matrices once a
substep whoever was picked (the streamed form a decode substep's few
tokens take reads every bank), the live lanes' latent rows, each live
lane's stream of rows in and out a sublayer; over the HBM peak) over the
traced window's busy time.

Prefill programs and forks run inside the traced window too and their
time rides in the denominator, while their reads are not in the numerator:
the value is a lower bound on the decode substeps' own share, as
``cca_moe_decode_roofline`` is.  A run whose driver counted no such bytes
(a program without the family) gives nothing.
"""

import readers

NAME = "mhc_moe_decode_roofline"
UNIT = "%"
LAYER = "generation engine"
MOVES = "rollout_tokens_per_s"


def read(r):
    trace, peaks = r["trace"], r["peaks"]
    moved = readers.counter(r, "traced_mhc_moe_bytes")
    if trace is None or peaks is None or not moved or trace["busy_s"] <= 0:
        return None
    r["ctx"].log(f"{NAME}: {moved / 1e9:.2f} GB to move in {trace['busy_s']:.3f} s busy")
    return 100.0 * (moved / peaks["hbm_bytes_per_s"]) / trace["busy_s"]
