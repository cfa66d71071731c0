"""Per-layer metric ``latent_decode_roofline``.

Roofline share of the absorbed latent decode kernel, found by the name the
program gives its ``pallas_call`` (``paged_decode_latent``): the least time
the chip could take for the latent rows the traced window's decode steps
had to read (cached tokens each decoded token attended to, from the
completed sequences, times the row's 576 float32 values in each of the
eight pools, over the HBM peak) over the device time in that kernel.  A
program without the kernel, or a trace without its name, gives nothing.
"""

import program_trace
import readers

NAME = "latent_decode_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "rollout_tokens_per_s"


def read(r):
    peaks = r["peaks"]
    row_bytes = readers.counter(r, "traced_latent_bytes")
    program = program_trace.of(r)
    if peaks is None or program is None or not row_bytes:
        return None
    kernel_s = program.kernel_s.get("paged_decode_latent", 0.0)
    if kernel_s <= 0:
        return None
    r["ctx"].log(f"{NAME}: {row_bytes / 1e9:.2f} GB of latent rows in {kernel_s:.3f} s of the kernel")
    return 100.0 * (row_bytes / peaks["hbm_bytes_per_s"]) / kernel_s
