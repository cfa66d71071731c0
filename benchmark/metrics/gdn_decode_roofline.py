"""Per-layer metric ``gdn_decode_roofline``.

Roofline share of the Gated DeltaNet decode update (``gdn_decode_update``;
found in the trace as ``gdn_decode_time_share`` says): the least time the
chip could take to move the matrix state the traced window's decode
substeps had to move (decoded tokens, that is live lanes x substeps, times
the Gated DeltaNet layers times one layer's ``value heads x key x value``
float32 state, read ONCE and written once; bytes from
``qwen3next_work.py``, over the HBM peak) over the device time in the
update.  The update walks every lane, so a dead lane's rows are moved too
and count as waste, not as work; an update that reads the state twice
moves half as much again and reads a lower share for it.  It is bound by
bytes (0.9 FLOP a byte).  A program without the update, or a run that was
not traced, gives nothing.
"""

import harness
import readers

NAME = "gdn_decode_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "rollout_tokens_per_s"


def read(r):
    peaks = r["peaks"]
    state_bytes = readers.counter(r, "traced_gdn_state_bytes")
    if peaks is None or not state_bytes:
        return None
    seconds = harness.load_module("metrics", "gdn_decode_time_share").update_s(r)
    if seconds is None:
        return None
    r["ctx"].log(f"{NAME}: {state_bytes / 1e9:.2f} GB of state in {seconds:.3f} s of the update")
    return 100.0 * (state_bytes / peaks["hbm_bytes_per_s"]) / seconds
