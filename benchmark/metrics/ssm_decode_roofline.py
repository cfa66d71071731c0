"""Per-layer metric ``ssm_decode_roofline``.

Roofline share of the Mamba-2 decode update (``ssm_decode_update``, one
XLA fusion a Mamba layer; found in the trace as ``ssm_decode_time_share``
says): the least time the chip could take to move the recurrent state the
traced window's decode substeps had to move (decoded tokens, that is live
lanes x substeps, times the Mamba layers times one layer's ``heads x
head_dim x state`` float32 state, read once and written once; bytes from
``nemotron_work.py``, over the HBM peak) over the device time in those
fusions.  The update walks every lane, so a dead lane's rows are moved too
and count as waste, not as work.  It is bound by bytes (0.6 FLOP a byte).
A program without the fusion, or a run that was not traced, gives nothing.
"""

import harness
import readers

NAME = "ssm_decode_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "rollout_tokens_per_s"


def read(r):
    peaks = r["peaks"]
    state_bytes = readers.counter(r, "traced_ssm_state_bytes")
    if peaks is None or not state_bytes:
        return None
    seconds = harness.load_module("metrics", "ssm_decode_time_share").update_s(r)
    if seconds is None:
        return None
    r["ctx"].log(f"{NAME}: {state_bytes / 1e9:.2f} GB of state in {seconds:.3f} s of the update")
    return 100.0 * (state_bytes / peaks["hbm_bytes_per_s"]) / seconds
