"""The program's own spans and kernel names in a profiler trace.

``scalerl_tpu/runtime/tracing.span`` opens a profiler annotation named
``scalerl.<span>`` around each phase of the hot paths, and every Pallas
kernel carries a ``name=`` that the trace shows as the head of its
operation (``%segment_flash_fwd.3``).  This module reads both out of the
run's ``.xplane.pb`` with what ``trace_reduce.py`` exports, inside the
``bench.window`` annotation:

- per program span name: count, durations and **self time** (a span's
  duration less what its child spans cover);
- the device's idle time outside containers, charged to the innermost
  open **program** span (``trace_reduce`` charges it to ``bench.*`` spans);
- device time per **kernel name**: the union of the Mosaic events whose
  head, numbers replaced, is that kernel's, averaged over the devices.

A trace of a program that opens no such span or names no kernel (the
parent of the PR that added them) reduces to empty tables, and every
reader built on this returns ``None``: nothing here raises for want of
something to read.  A CPU rehearsal's trace has no device plane and still
carries the spans on its host plane.

    python benchmark/program_trace.py <file.xplane.pb> [metric ...]

prints the tables as text, and then the value each named per-layer metric
file under ``metrics/`` reads from that trace: the way to the metrics built
on this module for as long as no cell's ``per_layer`` list names them.
"""

from __future__ import annotations

import re
import statistics
import sys
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import trace_reduce

PREFIX = "scalerl."
_NO_SPAN = "(no program span open)"
_KERNEL_HEAD = re.compile(r"^%([A-Za-z_][A-Za-z_\-]*?)(\.N)*$")


class Span(NamedTuple):
    name: str
    start_ns: float
    end_ns: float
    parent: int  # index into the same list, -1 for a root
    self_ns: float  # duration less the union of its child spans


def tree(spans: Sequence[Tuple[str, float, float]]) -> List[Span]:
    """Nest spans by containment (a span's parent is the innermost span
    that wholly contains it) and give each its self time.  Spans of one
    thread nest exactly; one that only overlaps another is its sibling."""
    ordered = sorted(spans, key=lambda sp: (sp[1], -sp[2]))
    out: List[Span] = []
    children: List[List[Tuple[float, float]]] = []
    stack: List[int] = []
    for name, start, end in ordered:
        while stack and not (out[stack[-1]].end_ns >= end and out[stack[-1]].start_ns <= start):
            stack.pop()
        parent = stack[-1] if stack else -1
        if parent >= 0:
            children[parent].append((start, end))
        out.append(Span(name, start, end, parent, 0.0))
        children.append([])
        stack.append(len(out) - 1)
    return [
        sp._replace(
            self_ns=(sp.end_ns - sp.start_ns) - trace_reduce.total(trace_reduce.union(children[i]))
        )
        for i, sp in enumerate(out)
    ]


class Program(NamedTuple):
    """One trace, reduced."""

    window_ns: Tuple[float, float]
    spans: List[Span]  # every program span wholly inside the window
    idle_by_span: Dict[str, float]  # seconds, averaged over the devices
    kernel_s: Dict[str, float]  # seconds per kernel name, averaged over the devices
    devices: int

    def _above(self, i: int, name: str) -> Optional[int]:
        """The nearest span called ``name`` above span ``i``."""
        i = self.spans[i].parent
        while i >= 0 and self.spans[i].name != name:
            i = self.spans[i].parent
        return i if i >= 0 else None

    def durations_ms(self, name: str, inside: Optional[str] = None) -> List[float]:
        """Durations of the spans called ``name``; with ``inside``, only
        of those that have a span of that name above them."""
        return [
            (sp.end_ns - sp.start_ns) / 1e6
            for i, sp in enumerate(self.spans)
            if sp.name == name and (inside is None or self._above(i, inside) is not None)
        ]

    def less_ms(self, name: str, without: str) -> List[float]:
        """Per span called ``name``: its duration less the spans called
        ``without`` below it."""
        below: Dict[int, float] = {}
        for j, sp in enumerate(self.spans):
            if sp.name == without:
                i = self._above(j, name)
                if i is not None:
                    below[i] = below.get(i, 0.0) + (sp.end_ns - sp.start_ns)
        return [
            (sp.end_ns - sp.start_ns - below.get(i, 0.0)) / 1e6
            for i, sp in enumerate(self.spans) if sp.name == name
        ]

    def table(self) -> List[Tuple[str, int, float, float, float]]:
        """``(name, count, median ms, median self ms, idle s charged)``
        per span name, in order of first appearance."""
        rows = []
        for name in dict.fromkeys(sp.name for sp in self.spans):
            own = [sp for sp in self.spans if sp.name == name]
            rows.append((
                name, len(own),
                _median([(sp.end_ns - sp.start_ns) / 1e6 for sp in own]),
                _median([sp.self_ns / 1e6 for sp in own]),
                self.idle_by_span.get(name, 0.0),
            ))
        return rows


def _median(values: Sequence[float]) -> Optional[float]:
    return statistics.median(values) if values else None


def kernel_of(event_name: str) -> Optional[str]:
    """The kernel's name out of a Mosaic event's ``"%head opcode result"``
    (numbers already replaced): ``%segment_flash_fwd.N`` is
    ``segment_flash_fwd``."""
    match = _KERNEL_HEAD.match(event_name.split(" ", 1)[0])
    return match.group(1) if match else None


def reduce(path: str) -> Optional[Program]:
    """Reduce one trace file; ``None`` when it has no ``bench.window``."""
    window = [
        (s, e) for n, s, e in trace_reduce.load(path, "bench.").spans
        if n == trace_reduce.WINDOW_SPAN
    ]
    if not window:
        return None
    lo, hi = window[-1]
    trace = trace_reduce.load(path, PREFIX)
    spans = tree([sp for sp in trace.spans if sp[1] >= lo and sp[2] <= hi])
    flat = [(sp.name, sp.start_ns, sp.end_ns) for sp in spans]
    devices = {d: evs for d, evs in trace.devices.items() if evs}
    idle: Dict[str, float] = {}
    kernel_ns: Dict[str, float] = {}
    for dev in sorted(devices):
        events = devices[dev]
        times = trace_reduce.device_times(events, lo, hi)
        gaps = trace_reduce.subtract(
            trace_reduce.complement(times["merged"], lo, hi), times["containers"]
        )
        for name, s in trace_reduce.charge_gaps(gaps, flat).items():
            idle[name] = idle.get(name, 0.0) + s
        # as ``device_times`` counts Mosaic time: operations only, no containers
        ran: Dict[str, List[Tuple[float, float]]] = {}
        for ev, _own, container in trace_reduce.nesting(events):
            kernel = kernel_of(ev.name) if ev.mosaic and not container else None
            if kernel is not None:
                ran.setdefault(kernel, []).append((ev.start_ns, ev.end_ns))
        for kernel, intervals in ran.items():
            merged = trace_reduce.clip(trace_reduce.union(intervals), lo, hi)
            kernel_ns[kernel] = kernel_ns.get(kernel, 0.0) + trace_reduce.total(merged)
    n = max(len(devices), 1)
    if trace_reduce._NO_SPAN in idle:
        idle[_NO_SPAN] = idle.pop(trace_reduce._NO_SPAN)
    return Program(
        window_ns=(lo, hi),
        spans=spans,
        idle_by_span={name: s / n for name, s in idle.items()},
        kernel_s={name: ns / n / 1e9 for name, ns in kernel_ns.items()},
        devices=len(devices),
    )


def report(program: Program) -> List[str]:
    """The tables as text: one line per span name, per kernel."""
    lo, hi = program.window_ns
    lines = [
        f"program spans inside the traced window ({(hi - lo) / 1e9:.3f} s, "
        f"{program.devices} device(s)):"
    ]
    for name, count, p50, self_p50, idle in program.table():
        lines.append(
            f"  {name}: {count} spans, median {p50:.3f} ms, median self time "
            f"{self_p50:.3f} ms, device idle charged {idle:.4f} s"
        )
    rest = program.idle_by_span.get(_NO_SPAN)
    if rest is not None:
        lines.append(f"  {_NO_SPAN}: device idle charged {rest:.4f} s")
    if program.kernel_s:
        lines.append("device time by kernel name (per device):")
        for name, s in sorted(program.kernel_s.items(), key=lambda kv: -kv[1]):
            lines.append(f"  {name}: {s:.4f} s")
    return lines


# ---------------------------------------------------------------------------
# what the per-layer metric files under ``metrics/`` call


def of(r) -> Optional[Program]:
    """The run's trace reduced (once a run, kept in the reading), or
    ``None`` when the run was not traced.  The first call logs the tables,
    and the rate the window kept with the profiler on for its last seconds."""
    if "program" not in r:
        path = r["ctx"].trace_path
        r["program"] = program = None if path is None else reduce(path)
        if program is not None:
            for line in report(program):
                r["ctx"].log(line)
            if "end_to_end" in r["result"]:
                r["ctx"].log("end to end in this traced run:", r["result"]["end_to_end"])
    return r["program"]


def p50_ms(r, metric: str, values_ms) -> Optional[float]:
    """The median of ``values_ms(program)``, logged with the count of
    spans it was taken from; ``None`` with none."""
    program = of(r)
    values = values_ms(program) if program is not None else []
    value = _median(values)
    if value is not None:
        r["ctx"].log(
            f"{metric}: {value:.3f} ms, the median of {len(values)} spans in the traced window"
        )
    return value


def kernel_share(r, metric: str, kernels: Sequence[str]) -> Optional[float]:
    """Device time in the named kernels over the device's busy time, in
    percent; ``None`` when the trace names none of them."""
    program, trace = of(r), r["trace"]
    if program is None or trace is None or trace["busy_s"] <= 0:
        return None
    found = [k for k in kernels if k in program.kernel_s]
    if not found:
        return None
    share = 100.0 * sum(program.kernel_s[k] for k in found) / trace["busy_s"]
    named = 100.0 * sum(program.kernel_s.values()) / trace["busy_s"]
    r["ctx"].log(
        f"{metric}: {share:.3f}% of busy time in {found}; every named kernel together "
        f"{named:.3f}%, every Mosaic call {100.0 * trace['mosaic_s'] / trace['busy_s']:.3f}%"
    )
    return share


def main(argv: Sequence[str]) -> int:
    """The tables of one trace file, then what the named metric files read
    from it."""
    import types

    import harness

    path, *metrics = argv
    ctx = types.SimpleNamespace(trace_path=path, log=print)
    reading = {"ctx": ctx, "result": {}, "trace": trace_reduce.reduce_trace(path)}
    if of(reading) is None:
        print("no bench.window in this trace")
    for name in metrics:
        metric = harness.load_module("metrics", name)
        print(f"{name} = {metric.read(reading)} {metric.UNIT}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
