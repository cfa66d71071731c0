"""Per-layer metric ``gdn_hybrid_decode_roofline``.

Roofline share of the Qwen3-Next stack's decode substeps as a whole: the
least time the chip could take to move every byte the traced substeps had
to move (``qwen3next_work.py``: each Gated DeltaNet, attention, router and
shared-expert matrix and the policy head once a substep, every HELD
expert's three matrices once a substep whoever was picked (the streamed
form a decode substep's few tokens take reads every bank), the matrix
state and the convolution window of every live lane in and out, the live
lanes' cached keys and values; over the HBM peak) over the traced window's
busy time.

Prefill programs and forks run inside the traced window too and their
time rides in the denominator, while their reads are not in the numerator:
the value is a lower bound on the decode substeps' own share, as
``hybrid_decode_roofline`` is.  Read only where the trace shows the
update (``gdn_decode_time_share`` says how it is found): a program
without one gives nothing.
"""

import harness
import readers

NAME = "gdn_hybrid_decode_roofline"
UNIT = "%"
LAYER = "generation engine"
MOVES = "rollout_tokens_per_s"


def read(r):
    trace, peaks = r["trace"], r["peaks"]
    moved = readers.counter(r, "traced_gdn_hybrid_bytes")
    if trace is None or peaks is None or not moved or trace["busy_s"] <= 0:
        return None
    if harness.load_module("metrics", "gdn_decode_time_share").update_s(r) is None:
        return None
    r["ctx"].log(f"{NAME}: {moved / 1e9:.2f} GB to move in {trace['busy_s']:.3f} s busy")
    return 100.0 * (moved / peaks["hbm_bytes_per_s"]) / trace["busy_s"]
