"""Per-layer metric ``mhc_time_share``.

Device time of the hyper-connections' operations in the decode substeps
over the device's busy time in the traced window: what a residual stream
of rows, mixed a sublayer by a Sinkhorn-projected matrix, costs a decode
substep beside the byte-bound expert banks.

The trace names an XLA operation by its whole HLO instruction, shapes
included, so the operations are found by shape, as ``wide_head_time_share``
finds the head: an instruction counts when its result or an operand holds
the decode stream's shape ``[lanes, 1, n, d]`` (any element type; from the
driver's ``mhc_stream_shape`` counter) or a map's: ``[lanes, 1, c]`` or,
where XLA dropped the token axis of one, ``[lanes, c]``, with ``c`` one of
``n (n + 2)`` (the projection's 24 columns), ``n n`` (the matrix's 16
entries, through the exponential and the 40 normalisations) and ``n`` (the
read weights, the write gates), and ``[lanes, 1, n, n]`` / ``[lanes, n,
n]`` (the matrix as the write takes it).  An instruction that only carries such an array (the
decode loop's ``while``, a ``conditional``, a ``call``) is control flow,
whose event spans the work inside it, and is not counted.  **Nor is an
instruction that also holds an array of more elements than the stream
itself**: XLA makes the WRITE the epilogue of the sublayer's last product
(the attention's output projection, the dense layer's and the experts'
down projections: their fusions give the stream's four rows), and that
instruction's time is the reading of a weight matrix or of 64 expert banks,
not the hyper-connection's (the largest array a hyper-connection owns,
``Phi``, is a quarter of the decode stream).  So the share is the maps, the
read and what of the write stands alone: a lower bound on what the
hyper-connections cost, by the epilogues' few microseconds.  Where XLA
fused a small neighbour into a counted operation (the sublayer's pre-norm
into the read, the embedding's gather into the expansion, the final norm's
first pass into the read-out) the neighbour's time is counted too: PERF.md
says which, from the compiled text.  A prefill's stream (``[rows, bucket, n,
d]``) does not match and is not counted.  :func:`mhc_s` finds those events
and is what ``mhc_stream_roofline`` loads too.  A run that was not traced,
or a driver that names no such shape, gives nothing.
"""

import math
import re

import program_trace
import readers
import trace_reduce

_OPCODE = re.compile(r" = (?:\(.*?\)|\S+) ([a-z\-]+)\(")
_SHAPE = re.compile(r"\[([\d,]+)\]")
_CONTROL_FLOW = ("while", "conditional", "call")

NAME = "mhc_time_share"
UNIT = "%"
LAYER = "experts"
MOVES = "rollout_tokens_per_s"


def shapes(stream):
    """The regular expression of every shape that marks an operation of
    the hyper-connections, from the decode stream's ``[lanes, 1, n, d]``."""
    lanes, one, n, d = (int(v) for v in stream)
    widths = sorted({n * (n + 2), n * n, n})
    found = [f"{lanes},{one},{n},{d}", f"{lanes},{one},{n},{n}", f"{lanes},{n},{n}"]
    found += [f"{lanes},{one},{c}" for c in widths] + [f"{lanes},{c}" for c in widths]
    return re.compile(r"\[(?:" + "|".join(found) + r")\]")


def _carries_only(hlo):
    opcode = _OPCODE.search(hlo)
    return opcode is not None and opcode.group(1) in _CONTROL_FLOW


def _holds_more_than(hlo, elements):
    """Whether the instruction names an array of more than ``elements``
    elements: a neighbour's product with the write as its epilogue."""
    return any(
        math.prod(int(d) for d in dims.split(",")) > elements
        for dims in _SHAPE.findall(hlo)
    )


def mhc_s(r):
    """Seconds of the traced window the device spent in operations on the
    decode stream or a map (averaged over the devices), kept in the
    reading; ``None`` with none."""
    if "mhc_s" not in r:
        r["mhc_s"] = _mhc_s(r)
    return r["mhc_s"]


def _mhc_s(r):
    from jax.profiler import ProfileData

    stream = readers.counter(r, "mhc_stream_shape")
    program = program_trace.of(r) if stream else None
    if program is None:
        return None
    marks = shapes(stream)
    whole = math.prod(int(v) for v in stream)
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
            for ev in line.events
            if marks.search(ev.name) and not _carries_only(ev.name)
            and not _holds_more_than(ev.name, whole)
        ]
        if ran:
            devices += 1
            seconds += trace_reduce.total(trace_reduce.clip(trace_reduce.union(ran), lo, hi)) / 1e9
    return seconds / devices if seconds > 0 else None


def read(r):
    trace = r["trace"]
    if trace is None or trace["busy_s"] <= 0:
        return None
    seconds = mhc_s(r)
    if seconds is None:
        return None
    r["ctx"].log(
        f"{NAME}: {seconds:.3f} s in operations on the stream or a map of {trace['busy_s']:.3f} s busy"
    )
    return 100.0 * seconds / trace["busy_s"]
