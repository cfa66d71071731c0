"""Per-layer metric ``wide_head_time_share``.

Device time of the operations that hold the ``[lanes, vocabulary]`` array
(the policy head's product and the sampler: temperature, the categorical
draw, the log-probability gather) over the device's busy time in the traced
window: what a 262k-row vocabulary costs a decode substep.

The trace names an XLA operation by its whole HLO instruction, shapes
included, so the operations are found by the shape ``[lanes,vocabulary]``
(any element type) in the instruction's result or among its operands, as
``ssm_decode_time_share`` finds its fusion by the state's shape; the sizes
come from the driver's ``wide_head_shape`` counter.  An instruction that
only carries the array (the decode loop's ``while``, a ``conditional``, a
``call``) is control flow, whose event spans the work inside it, and is
not counted.  A prefill's head (``[rows, bucket, vocabulary]``) does not
match and is not counted.  A run that was not traced, or a driver that
names no such shape, gives nothing.
"""

import re

import program_trace
import readers
import trace_reduce

_OPCODE = re.compile(r" = (?:\(.*?\)|\S+) ([a-z\-]+)\(")
_CONTROL_FLOW = ("while", "conditional", "call")

NAME = "wide_head_time_share"
UNIT = "%"
LAYER = "generation engine"
MOVES = "rollout_tokens_per_s"


def _carries_only(hlo):
    opcode = _OPCODE.search(hlo)
    return opcode is not None and opcode.group(1) in _CONTROL_FLOW


def _wide_s(r, shape):
    from jax.profiler import ProfileData

    program = program_trace.of(r)
    if program is None:
        return None
    wide = re.compile(r"\[{},{}\]".format(*shape))
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
            for ev in line.events if wide.search(ev.name) and not _carries_only(ev.name)
        ]
        if ran:
            devices += 1
            seconds += trace_reduce.total(trace_reduce.clip(trace_reduce.union(ran), lo, hi)) / 1e9
    return seconds / devices if seconds > 0 else None


def read(r):
    trace = r["trace"]
    shape = readers.counter(r, "wide_head_shape")
    if trace is None or not shape or trace["busy_s"] <= 0:
        return None
    seconds = _wide_s(r, shape)
    if seconds is None:
        return None
    r["ctx"].log(f"{NAME}: {seconds:.3f} s in operations on {shape} of {trace['busy_s']:.3f} s busy")
    return 100.0 * seconds / trace["busy_s"]
