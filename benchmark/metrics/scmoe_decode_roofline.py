"""Per-layer metric ``scmoe_decode_roofline``.

Roofline share of a decode substep outside the latent attention kernel:
the least time the chip could take to read the weights the traced decode
substeps had to read (every double layer's two attentions, two dense FFNs
and router and the policy head once a substep, and each HELD expert that a
live lane's token picked in that substep, in the dtype they are stored in;
bytes from ``longcat_work.py`` and the engine's expert counters, over the
HBM peak) over the traced window's busy time less the time in
``paged_decode_latent``.

Prefill programs run inside the traced window too and their time rides in
the denominator, while their reads are not in the numerator: the value is
a lower bound on the decode substeps' own share.
"""

import program_trace
import readers

NAME = "scmoe_decode_roofline"
UNIT = "%"
LAYER = "experts"
MOVES = "rollout_tokens_per_s"


def read(r):
    trace, peaks = r["trace"], r["peaks"]
    weight_bytes = readers.counter(r, "traced_weight_bytes")
    program = program_trace.of(r)
    if trace is None or peaks is None or program is None or not weight_bytes:
        return None
    rest_s = trace["busy_s"] - program.kernel_s.get("paged_decode_latent", 0.0)
    if rest_s <= 0:
        return None
    r["ctx"].log(
        f"{NAME}: {weight_bytes / 1e9:.2f} GB of weights in {rest_s:.3f} s outside paged_decode_latent"
    )
    return 100.0 * (weight_bytes / peaks["hbm_bytes_per_s"]) / rest_s
