"""Per-layer metric ``ssm_decode_time_share``.

Device time inside the Mamba-2 decode update (``ssm_decode_update``) over
the device's busy time in the traced window: whether the state-space
layers' state traffic is where a decode substep's time goes.

The update is plain ``jax.numpy`` and XLA makes ONE fusion a Mamba layer
of it, which the trace names by its whole HLO instruction: a ``fusion``
whose result is a tuple that holds the state's shape (``f32[lanes, heads,
head_dim, state]``, the sizes from the configuration) beside ``y``.  A
fusion whose only result is the state is a write into it (a prefill's, a
fork's) and is not counted.  :func:`update_s` finds those events and is
what ``ssm_decode_roofline`` and ``hybrid_decode_roofline`` load too.  A
program without such a fusion, or a run that was not traced, gives
nothing.
"""

import re

import program_trace
import trace_reduce

NAME = "ssm_decode_time_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "rollout_tokens_per_s"


def update_s(r):
    """Seconds of the traced window the device spent in the fusions that
    read every lane's recurrent state and give it back beside ``y``
    (averaged over the devices), kept in the reading; ``None`` with none."""
    if "ssm_update_s" not in r:
        r["ssm_update_s"] = _update_s(r)
    return r["ssm_update_s"]


def _update_s(r):
    from jax.profiler import ProfileData

    program, cfg = program_trace.of(r), getattr(r["ctx"], "config", None)
    if program is None or not cfg or "mamba_num_heads" not in cfg:
        return None
    state = re.compile(r"f32\[\d+,{mamba_num_heads},{mamba_head_dim},{ssm_state_size}\]".format(**cfg))

    def is_update(hlo):
        # "%head = (result, result) fusion(operands), ...": a tuple that holds the state
        result, found, _operands = hlo.partition(" = ")[2].partition(" fusion(")
        return bool(found) and result.startswith("(") and state.search(result) is not None

    path = r["ctx"].trace_path
    shift = trace_reduce.load(path).clock_shift_ns
    lo, hi = program.window_ns
    seconds, devices = 0.0, 0
    for plane in ProfileData.from_file(path).planes:
        if not trace_reduce._DEVICE_PLANE.match(plane.name):
            continue
        ran = [
            (ev.start_ns + shift, ev.start_ns + shift + ev.duration_ns)
            for line in plane.lines if line.name in trace_reduce._OP_LINES
            for ev in line.events if is_update(ev.name)
        ]
        if ran:
            devices += 1
            seconds += trace_reduce.total(trace_reduce.clip(trace_reduce.union(ran), lo, hi)) / 1e9
    return seconds / devices if seconds > 0 else None


def read(r):
    trace = r["trace"]
    seconds = update_s(r)
    if trace is None or seconds is None or trace["busy_s"] <= 0:
        return None
    r["ctx"].log(f"{NAME}: {seconds:.3f} s in the state's update of {trace['busy_s']:.3f} s busy")
    return 100.0 * seconds / trace["busy_s"]
