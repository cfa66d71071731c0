"""Per-layer metric ``mhc_stream_roofline``.

Roofline share of the hyper-connections in the decode substeps (plain
``jax.numpy``, a few dozen XLA fusions a sublayer; found in the trace as
``mhc_time_share`` says): the least time the chip could take to move their
own bytes over the traced substeps (``xing4_work.py``'s ``mhc_bytes``: each
decoded token's stream of rows ``[n, d]`` read once and written once a
sublayer in the stream's dtype, which is as many passes as the mathematics
needs, one read for the flattened norm, the read mix and the write's
``H_res X`` together and one write; every ``Phi`` with its vectors once a
substep; over the HBM peak) over the device time in those operations.  The
chains of small normalisations between the two passes are bound by
latency, not by bytes, which is what a low share says.  Where XLA fused a
neighbour into an operation on the stream the neighbour's time rides in
the denominator: a lower bound.  A run that was not traced, or whose
driver counted no such bytes, gives nothing.
"""

import harness
import readers

NAME = "mhc_stream_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "rollout_tokens_per_s"


def read(r):
    peaks = r["peaks"]
    moved = readers.counter(r, "traced_mhc_bytes")
    if peaks is None or not moved:
        return None
    seconds = harness.load_module("metrics", "mhc_time_share").mhc_s(r)
    if seconds is None:
        return None
    r["ctx"].log(f"{NAME}: {moved / 1e9:.2f} GB of streams and maps' weights in {seconds:.3f} s of their operations")
    return 100.0 * (moved / peaks["hbm_bytes_per_s"]) / seconds
