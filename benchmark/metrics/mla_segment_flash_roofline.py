"""Per-layer metric ``mla_segment_flash_roofline``.

Roofline share of the three segment-flash kernels at the latent
attention's head shape (q and k 192 wide, v 128), found by the names the
program gives its ``pallas_call``s (``segment_flash_fwd``,
``segment_flash_bwd_dq``, ``segment_flash_bwd_dkv``): the least time the
chip could take for the attention operations the traced learn steps had
to do (causal within segments, from the replay's segment lengths and the
traced steps' real tokens; unpadded widths; forward and both backward
kernels of every attention a step, ``joyai_work.py``; compute bounds these
kernels, so operations over the bf16 peak) over the device time in the
three kernels.  Padding and recomputed scores count as waste.  A program
without the kernels, or a trace without their names, gives nothing.
"""

import program_trace
import readers

NAME = "mla_segment_flash_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "learn_tokens_per_s"

_KERNELS = ("segment_flash_fwd", "segment_flash_bwd_dq", "segment_flash_bwd_dkv")


def read(r):
    peaks = r["peaks"]
    flops = readers.counter(r, "traced_attention_flops")
    program = program_trace.of(r)
    if peaks is None or program is None or not flops:
        return None
    kernel_s = sum(program.kernel_s.get(name, 0.0) for name in _KERNELS)
    if kernel_s <= 0:
        return None
    r["ctx"].log(f"{NAME}: {flops / 1e12:.3f} TFLOP of attention in {kernel_s:.3f} s of the kernels")
    return 100.0 * (flops / peaks["bf16_flops_per_s"]) / kernel_s
