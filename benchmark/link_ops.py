"""Collectives the compiler has put inside other operations.

``trace_reduce`` knows a collective by its opcode or its head
(``all-reduce``, ``collective-permute-done``, ...).  Compiled with
asynchronous collectives (``scalerl_tpu/parallel/train_step.py``,
``ASYNC_COLLECTIVE_OPTIONS``), a sharded learn step runs some of its
all-reduces under three more kinds of instruction, all of opcode
``fusion``, and ``collective_exposed_share`` reads their time as compute:

- ``%async-collective-start`` opens the reduction and
  ``%async-collective-done`` waits for what is left of it: link time, with
  nothing beside it (**waits**);
- ``%fusion.N = ... calls=%async_collective_fusion.N`` runs one step of the
  reduction beside a matmul that does not depend on it, and lasts as long
  as the longer of the two (**fused**): the trace cannot say which part of
  it the device waited for the link.

So the exposed share of such a program has a lower bound (the accepted
operations and the waits) and an upper bound (the fused ones as well), and
the two readers built on this module report them.  A program without
these instructions reads ``collective_exposed_share`` under both names.
The arithmetic is ``trace_reduce.device_times``: only which operations
count as collectives differs.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

import trace_reduce

WAIT, FUSED = "wait", "fused"
_WAIT = re.compile(r"^%async-collective-(start|done)\b")
_FUSED = "calls=%async_collective_fusion"


def kind(hlo: str) -> Optional[str]:
    """``WAIT`` or ``FUSED`` for an instruction that carries a collective
    ``trace_reduce`` does not see, else ``None``."""
    if _WAIT.match(hlo):
        return WAIT
    return FUSED if _FUSED in hlo else None


def instructions(path: str) -> Dict[int, List[str]]:
    """Per device, the whole HLO text of every operation event, in the
    order ``trace_reduce.load`` keeps them."""
    from jax.profiler import ProfileData

    out: Dict[int, List[str]] = {}
    for plane in ProfileData.from_file(path).planes:
        match = trace_reduce._DEVICE_PLANE.match(plane.name)
        if match:
            names = out.setdefault(int(match.group(2)), [])
            for line in plane.lines:
                if line.name in trace_reduce._OP_LINES:
                    names.extend(ev.name for ev in line.events)
    return out


def exposed_ns(
    events: Sequence[trace_reduce.Event], hlo: Sequence[str], lo: float, hi: float,
    kinds: Sequence[str],
) -> Tuple[float, float]:
    """``(exposed collective ns, busy ns)`` of one device inside
    ``[lo, hi)`` with the instructions of ``kinds`` counted as collectives
    beside the accepted ones."""
    relabelled = [
        ev._replace(collective=ev.collective or kind(text) in kinds)
        for ev, text in zip(events, hlo, strict=True)
    ]
    times = trace_reduce.device_times(relabelled, lo, hi)
    return times["collective_exposed"], times["busy"]


def shares(path: str) -> Optional[Dict[str, float]]:
    """Exposed collective time over busy time, in percent, summed over the
    devices: ``accepted`` (what ``collective_exposed_share`` reads),
    ``with_waits`` and ``with_fused``; ``None`` for a trace with no device
    operation in its window."""
    trace = trace_reduce.load(path)
    devices = {d: evs for d, evs in trace.devices.items() if evs}
    if not devices:
        return None
    windows = [(s, e) for n, s, e in trace.spans if n == trace_reduce.WINDOW_SPAN]
    if windows:
        lo, hi = windows[-1]
    else:
        lo = min(ev.start_ns for evs in devices.values() for ev in evs)
        hi = max(ev.end_ns for evs in devices.values() for ev in evs)
    hlo = instructions(path)
    out = {}
    for name, kinds in (("accepted", ()), ("with_waits", (WAIT,)), ("with_fused", (WAIT, FUSED))):
        sums = [exposed_ns(devices[d], hlo[d], lo, hi, kinds) for d in sorted(devices)]
        busy = sum(b for _e, b in sums)
        if busy <= 0:
            return None
        out[name] = 100.0 * sum(e for e, _b in sums) / busy
    return out


def share(r, which: str) -> Optional[float]:
    """One of :func:`shares` for the run's trace (reduced once a run, kept
    in the reading); ``None`` when the run was not traced.  The first call
    logs all three."""
    if "link_shares" not in r:
        path = r["ctx"].trace_path
        r["link_shares"] = found = None if path is None else shares(path)
        if found is not None:
            r["ctx"].log(
                "exposed collectives over busy time: "
                f"{found['accepted']:.3f}% as collective_exposed_share reads them, "
                f"{found['with_waits']:.3f}% with async-collective-start/done, "
                f"{found['with_fused']:.3f}% with the asynchronous collective fusions as well"
            )
    found = r["link_shares"]
    return None if found is None else found[which]
