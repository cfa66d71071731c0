"""From a profiler trace to numbers: the benchmark's own reduction.

The JAX profiler writes one ``.xplane.pb`` per traced window.  This module
reads it with ``jax.profiler.ProfileData`` and nothing else, and computes,
per device and averaged over the devices used:

- ``busy_s``: the union of the intervals in which an operation ran on the
  device, clipped to the window; a ``while``, a ``conditional`` or a call
  that spans its body's operations on the same line is a container, not an
  operation, and is in no union (a loop is busy while its body is);
- ``window_s``: the length of the traced window (the host annotation
  ``bench.window`` when the trace has one, else first event to last);
- ``mosaic_s``: the union of the Mosaic custom calls (Pallas kernels);
- ``collective_s`` and ``collective_exposed_s``: the union of the
  collective operations, and the part of it during which no other
  operation ran on that device;
- ``device_ops``: the ten operation names with most summed device time of
  their own (a loop's time is its body's operations'; what they leave
  uncovered stays under the loop's name, and is idle time of the device), a
  name being the HLO instruction's head with its numbers replaced by ``N``
  (so that one operation in 24 layers is one name), its opcode and its
  result's type;
- ``idle_gaps``: the device's idle time inside the window: what lies
  between the operations of a running loop under one name of its own, the
  rest charged to the benchmark's host span (``bench.*``) that was open at
  that instant, innermost span first, summed by span name, ten largest.

Device events are the events of the device planes' operation lines
(``XLA Ops``).  The program's kernels carry no stable names yet, so a
Mosaic call is recognised by what the compiler calls it, not by which
kernel it is (PERF.md, Open questions).
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

WINDOW_SPAN = "bench.window"
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
# the line of a device plane that holds one event per executed operation
_OP_LINES = ("XLA Ops",)
_COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast|ragged-all-to-all)"
)
# "%head = <type> opcode(operands), attributes": the trace names a device
# operation by its whole HLO instruction
_HLO = re.compile(r"^(%[^\s]+) = (\(?[a-z0-9]+\[[0-9,]*\])?.*?\s([a-z][a-z0-9\-]*)\(")
_NO_SPAN = "(no bench span open)"
_IN_LOOP = "(between operations inside a device loop)"


class Event(NamedTuple):
    name: str  # "%head opcode result", the head's numbers replaced by N
    start_ns: float
    end_ns: float
    mosaic: bool
    collective: bool


def short_name(hlo: str) -> Tuple[str, str, str]:
    """``(head, opcode, result)`` of an HLO instruction's text, the result
    as its (first) element type and shape; a name that is no instruction
    is its own head."""
    match = _HLO.match(hlo)
    if not match:
        return hlo, "", ""
    return match.group(1), match.group(3), (match.group(2) or "").lstrip("(")


class Trace(NamedTuple):
    devices: Dict[int, List[Event]]  # device id -> operation events, host clock
    spans: List[Tuple[str, float, float]]  # host annotations: name, start, end
    clock_shift_ns: float  # what was added to the device's timestamps


# ---------------------------------------------------------------------------
# recording


def start_trace(trace_dir: str) -> None:
    """Start the profiler with the Python call tracer off: it would add an
    event per Python call to the trace and slow the host it measures."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def stop_trace(trace_dir: str) -> str:
    """Stop the profiler; the path of the ``.xplane.pb`` it wrote."""
    import jax

    jax.profiler.stop_trace()
    return newest_xplane(trace_dir)


def newest_xplane(trace_dir: str) -> str:
    files = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime,
    )
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


# ---------------------------------------------------------------------------
# reading


def _is_mosaic(name: str) -> bool:
    """A Mosaic (Pallas) custom call: the trace names a device operation
    by its whole HLO instruction, target included."""
    return 'custom_call_target="tpu_custom_call"' in name


def load(path: str, span_prefix: str = "bench.") -> Trace:
    """Read the device operations and the benchmark's host spans.

    The device's timestamps run ahead of the host's by about a
    millisecond in these traces (a device module is seen to start before
    the host call that enqueued it).  Both sides carry the runtime's
    ``run_id``, so the device events are shifted by the least amount that
    lets no module start before its own ``DoEnqueueProgram`` began; what
    remains is the launch latency of the tightest pair, tens of
    microseconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[int, List[Event]] = {}
    spans: List[Tuple[str, float, float]] = []
    module_start: Dict[Tuple[int, str], float] = {}
    enqueue_start: Dict[Tuple[int, str], float] = {}
    for plane in data.planes:
        match = _DEVICE_PLANE.match(plane.name)
        if match:
            dev = int(match.group(2))
            events = devices.setdefault(dev, [])
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for ev in line.events:
                        run_id = dict(ev.stats).get("run_id")
                        if run_id is not None:
                            module_start[(dev, str(run_id))] = float(ev.start_ns)
                if line.name not in _OP_LINES:
                    continue
                for ev in line.events:
                    head, opcode, result = short_name(ev.name)
                    start = float(ev.start_ns)
                    # one name for the same operation in every layer and
                    # every copy: "%block_7.5" and "%block_21.5" are "%block_N.N"
                    head = re.sub(r"\d+", "N", head)
                    events.append(
                        Event(
                            f"{head} {opcode} {result}".strip(), start,
                            start + float(ev.duration_ns), _is_mosaic(ev.name),
                            bool(_COLLECTIVE.match(opcode) or _COLLECTIVE.match(head)),
                        )
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(span_prefix):
                        start = float(ev.start_ns)
                        spans.append((ev.name, start, start + float(ev.duration_ns)))
                    elif ev.name == "DoEnqueueProgram":
                        stats = dict(ev.stats)
                        if "run_id" in stats:
                            key = (int(stats.get("device_ordinal", 0)), str(stats["run_id"]))
                            enqueue_start[key] = float(ev.start_ns)
    early = [
        enqueue_start[k] - module_start[k] for k in module_start if k in enqueue_start
    ]
    shift = max([0.0] + early)
    if shift:
        devices = {
            dev: [e._replace(start_ns=e.start_ns + shift, end_ns=e.end_ns + shift) for e in evs]
            for dev, evs in devices.items()
        }
    return Trace(devices=devices, spans=spans, clock_shift_ns=shift)


def dump(path: str, per_line: int = 400) -> List[str]:
    """Every plane, line and (up to ``per_line``) event as text, with the
    stats of each event name's first occurrence."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: List[str] = []
    for plane in data.planes:
        out.append(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name!r} events={len(events)}")
            seen = set()
            for ev in events[:per_line]:
                row = f"    {ev.start_ns:.0f} +{ev.duration_ns:.0f} {ev.name}"
                if ev.name not in seen:
                    seen.add(ev.name)
                    row += "  " + repr(
                        {k: str(v)[:160] for k, v in dict(ev.stats).items()}
                    )
                out.append(row)
    return out


# ---------------------------------------------------------------------------
# interval arithmetic (closed-open intervals in ns, numpy arrays)


def union(intervals: Iterable[Tuple[float, float]]) -> np.ndarray:
    """Merge intervals; returns a sorted ``[n, 2]`` array of disjoint ones."""
    arr = np.asarray(
        [(s, e) for s, e in intervals if e > s], dtype=np.float64
    ).reshape(-1, 2)
    if not len(arr):
        return arr
    arr = arr[np.argsort(arr[:, 0], kind="stable")]
    ends = np.maximum.accumulate(arr[:, 1])
    # a new group starts where an interval begins after every earlier end
    new = np.concatenate(([True], arr[1:, 0] > ends[:-1]))
    starts = arr[new, 0]
    group_end = np.concatenate((ends[np.flatnonzero(new)[1:] - 1], ends[-1:]))
    return np.stack([starts, group_end], axis=1)


def clip(merged: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if not len(merged):
        return merged
    out = np.stack(
        [np.maximum(merged[:, 0], lo), np.minimum(merged[:, 1], hi)], axis=1
    )
    return out[out[:, 1] > out[:, 0]]


def total(merged: np.ndarray) -> float:
    return float(np.sum(merged[:, 1] - merged[:, 0])) if len(merged) else 0.0


def complement(merged: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """The gaps of disjoint sorted ``merged`` inside ``[lo, hi)``."""
    merged = clip(merged, lo, hi)
    edges = np.concatenate(([lo], merged.reshape(-1), [hi]))
    gaps = edges.reshape(-1, 2)
    return gaps[gaps[:, 1] > gaps[:, 0]]


def subtract(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """``a`` minus ``b`` for disjoint sorted interval arrays."""
    if not len(a):
        return a
    lo, hi = float(a[0, 0]), float(a[-1, 1])
    return intersect(a, complement(b, lo, hi))


def intersect(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Intersection of two disjoint sorted interval arrays."""
    out: List[Tuple[float, float]] = []
    i = j = 0
    while i < len(a) and j < len(b):
        lo = max(a[i, 0], b[j, 0])
        hi = min(a[i, 1], b[j, 1])
        if hi > lo:
            out.append((lo, hi))
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return np.asarray(out, dtype=np.float64).reshape(-1, 2)


def owner_timeline(
    spans: Sequence[Tuple[str, float, float]]
) -> List[Tuple[float, float, str]]:
    """Flatten possibly nested spans into disjoint pieces, each owned by
    the covering span that started last (innermost wins)."""
    points = sorted({p for _n, s, e in spans for p in (s, e)})
    pieces: List[Tuple[float, float, str]] = []
    ordered = sorted(spans, key=lambda x: x[1])
    active: List[Tuple[str, float, float]] = []
    k = 0
    for lo, hi in zip(points[:-1], points[1:]):
        while k < len(ordered) and ordered[k][1] <= lo:
            active.append(ordered[k])
            k += 1
        active = [sp for sp in active if sp[2] > lo]
        if active:
            name = max(active, key=lambda sp: sp[1])[0]
            if pieces and pieces[-1][2] == name and pieces[-1][1] == lo:
                pieces[-1] = (pieces[-1][0], hi, name)
            else:
                pieces.append((lo, hi, name))
    return pieces


def nesting(
    events: Sequence[Event], lo: float = -np.inf, hi: float = np.inf
) -> List[Tuple[Event, float, bool]]:
    """Each event with the nanoseconds inside ``[lo, hi)`` that are its own
    and whether it is a container.  A ``while``, a ``conditional`` or a
    call spans the operations of its body on the same line: what they
    cover is theirs, not its, and it is a container, not an operation."""
    ordered = sorted(events, key=lambda e: (e.start_ns, -e.end_ns))
    out: List[Tuple[Event, float, bool]] = []
    # [event, ns its children cover, has a child, where that cover ends,
    #  the latest end among the event and what started inside it]
    stack: List[List] = []

    def close(entry) -> None:
        ev, covered, parent, _until, reach = entry
        span = max(0.0, min(ev.end_ns, hi) - max(ev.start_ns, lo))
        out.append((ev, max(0.0, span - covered), parent))
        if stack:
            # the parent is covered from here to where this event, or an
            # operation that began inside it and outlasted it, ends
            above = stack[-1]
            start = max(ev.start_ns, above[3], above[0].start_ns, lo)
            end = min(reach, above[0].end_ns, hi)
            above[1] += max(0.0, end - start)
            above[3] = max(above[3], end)
            above[4] = max(above[4], reach)

    for ev in ordered:
        while stack and stack[-1][0].end_ns <= ev.start_ns:
            close(stack.pop())
        if stack and ev.end_ns <= stack[-1][0].end_ns:
            stack[-1][2] = True
        stack.append([ev, 0.0, False, -np.inf, ev.end_ns])
    while stack:
        close(stack.pop())
    return out


def self_times(events: Sequence[Event], lo: float, hi: float) -> Dict[str, float]:
    """Seconds per operation name inside ``[lo, hi)``, each operation's own;
    a container's own time is what its body left uncovered."""
    own: Dict[str, float] = {}
    for ev, ns, _parent in nesting(events, lo, hi):
        own[ev.name] = own.get(ev.name, 0.0) + ns / 1e9
    return own


def device_times(events: Sequence[Event], lo: float, hi: float) -> Dict[str, object]:
    """One device's interval sums inside ``[lo, hi)``, in nanoseconds:
    ``busy`` (the union of its operations, also returned merged as
    ``merged``), ``mosaic``, ``collective`` and ``collective_exposed``
    (collectives minus the union of every other operation), and the merged
    ``containers``.  Only operations make the device busy: a loop that
    waits between two operations of its body does not, and a loop around a
    collective does not hide it."""
    nested = nesting(events)
    ops = [ev for ev, _ns, parent in nested if not parent]

    def merged_of(keep) -> np.ndarray:
        return clip(union((e.start_ns, e.end_ns) for e in ops if keep(e)), lo, hi)

    merged = merged_of(lambda e: True)
    coll = merged_of(lambda e: e.collective)
    return {
        "merged": merged,
        "containers": clip(
            union((ev.start_ns, ev.end_ns) for ev, _ns, parent in nested if parent), lo, hi
        ),
        "busy": total(merged),
        "mosaic": total(merged_of(lambda e: e.mosaic)),
        "collective": total(coll),
        "collective_exposed": total(subtract(coll, merged_of(lambda e: not e.collective))),
    }


def charge_gaps(
    gaps: np.ndarray, spans: Sequence[Tuple[str, float, float]]
) -> Dict[str, float]:
    """Seconds of ``gaps`` charged to each span name (innermost wins)."""
    charged: Dict[str, float] = {}
    pieces = owner_timeline(spans)
    covered = 0.0
    j = 0
    for lo, hi in gaps:
        while j < len(pieces) and pieces[j][1] <= lo:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < hi:
            piece_lo, piece_hi, name = pieces[k]
            part = min(hi, piece_hi) - max(lo, piece_lo)
            if part > 0:
                charged[name] = charged.get(name, 0.0) + part / 1e9
                covered += part
            k += 1
    rest = total(gaps) - covered
    if rest > 0:
        charged[_NO_SPAN] = rest / 1e9
    return charged


# ---------------------------------------------------------------------------
# the reduction


def reduce_trace(
    path: str, span_prefix: str = "bench.", top: int = 10
) -> Optional[Dict[str, object]]:
    """Reduce one trace file; ``None`` when no device operation is in it."""
    trace = load(path, span_prefix)
    devices = {d: evs for d, evs in trace.devices.items() if evs}
    if not devices:
        return None
    windows = [(s, e) for n, s, e in trace.spans if n == WINDOW_SPAN]
    if windows:
        lo, hi = windows[-1]
    else:
        lo = min(ev.start_ns for evs in devices.values() for ev in evs)
        hi = max(ev.end_ns for evs in devices.values() for ev in evs)
    spans = [sp for sp in trace.spans if sp[0] != WINDOW_SPAN]

    sums = {"busy": 0.0, "mosaic": 0.0, "collective": 0.0, "collective_exposed": 0.0}
    op_time: Dict[str, float] = {}
    charged: Dict[str, float] = {}
    for dev in sorted(devices):
        events = devices[dev]
        times = device_times(events, lo, hi)
        for key in sums:
            sums[key] += times[key]
        for name, seconds in self_times(events, lo, hi).items():
            op_time[name] = op_time.get(name, 0.0) + seconds
        # idle inside a running loop is the program's; the rest is charged
        # to what the host was doing
        gaps = complement(times["merged"], lo, hi)
        in_loop = total(intersect(gaps, times["containers"]))
        if in_loop > 0:
            charged[_IN_LOOP] = charged.get(_IN_LOOP, 0.0) + in_loop / 1e9
        for name, s in charge_gaps(subtract(gaps, times["containers"]), spans).items():
            charged[name] = charged.get(name, 0.0) + s
    n = len(devices)
    window_s = (hi - lo) / 1e9
    busy_s = sums["busy"] / n / 1e9
    return {
        "devices": n,
        "clock_shift_s": trace.clock_shift_ns / 1e9,
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "mosaic_s": sums["mosaic"] / n / 1e9,
        "collective_s": sums["collective"] / n / 1e9,
        "collective_exposed_s": sums["collective_exposed"] / n / 1e9,
        "device_ops": [
            [name, s / n]
            for name, s in sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
        ],
        "idle_gaps": [
            [name, s / n]
            for name, s in sorted(charged.items(), key=lambda kv: -kv[1])[:top]
        ],
    }
