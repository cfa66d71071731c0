"""Per-layer metric ``gdn_decode_time_share``.

Device time inside the Gated DeltaNet decode update over the device's
busy time in the traced window: whether the matrix state's traffic is
where a decode substep's time goes.

The update is a Pallas kernel that carries its name (``name=
"gdn_decode_update"`` on its ``pallas_call``, ``ops/pallas_gdn.py``), so
the trace shows it as ``%gdn_decode_update.N custom-call`` and
``program_trace`` sums it by that name.  :func:`update_s` is what
``gdn_decode_roofline`` and ``gdn_hybrid_decode_roofline`` load too.  A
program without that kernel (the parent of the PR that brought it, a model
of another family), or a run that was not traced, gives nothing.
"""

import program_trace

NAME = "gdn_decode_time_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "rollout_tokens_per_s"
KERNEL = "gdn_decode_update"


def update_s(r):
    """Seconds of the traced window the device spent in the update's
    kernel (averaged over the devices); ``None`` with none."""
    program = program_trace.of(r)
    seconds = None if program is None else program.kernel_s.get(KERNEL, 0.0)
    return seconds or None


def read(r):
    trace = r["trace"]
    seconds = update_s(r)
    if trace is None or seconds is None or trace["busy_s"] <= 0:
        return None
    r["ctx"].log(f"{NAME}: {seconds:.3f} s in the state's update of {trace['busy_s']:.3f} s busy")
    return 100.0 * seconds / trace["busy_s"]
