"""Device time by program scope.

Every event of a device plane's ``XLA Ops`` line points, by metadata id, at
an ``XEventMetadata`` that holds more than the instruction's text: the jax
``op_name`` the operation was traced under (stat ``tf_op``:
``jit(decode)/while/body/closed_call/TransformerPolicy/block_7/qkv/dot_general:``),
XLA's ``hlo_category`` (``convolution fusion`` is a matmul whatever shape its
first output has), the ``program_id`` of the module it belongs to, the
compiler's ``flops`` and ``bytes_accessed`` and the ``source`` line.
``jax.profiler.ProfileData`` surfaces none of that, so this module decodes
the ``.xplane.pb`` itself: a reader of the protobuf wire format for the six
messages needed, which skips a host plane's lines by their length (no
TensorFlow import: that costs 10-14 s and the chip's host may not have it).

Everything else is ``trace_reduce``'s, by import: the ``bench.window``
bounds, the clock shift (scopes sit on the host's clock with the
``scalerl.*`` spans), the container rule (``nesting``: a ``while``, a
``conditional`` or a call is in no sum) and the averaging over devices.

A **scope** is the ``op_name`` with the program's own ``jit(...)`` taken off,
``block_7`` written ``block_N``, and jax's wrappers dropped (``while/body``,
``closed_call``, ``checkpoint``, ``pjit``, every inner ``jit(...)``, an
einsum's spec); ``jvp(...)`` and ``transpose(...)`` around a name are kept as
the direction, a leading ``fwd`` or ``bwd``.  Two kinds of nameless time are kept apart:

- ``(compiler) <hlo_category>``: an operation with NO ``tf_op`` is the
  compiler's own (``copy-start``, ``slice-done``, memory-space assignment);
- ``(unnamed) <program>``: an operation whose scope is only the program's
  ``jit(...)``, the model's class or a bare ``block_N``: program code that
  nobody named.  Where such a row is large, a ``jax.named_scope`` is missing.

``table`` gives per (program, scope): own seconds, calls, ``hlo_category``,
the compiler's ``flops`` and ``bytes_accessed`` summed (what the
IMPLEMENTATION moved: never a roofline's numerator, which stays the work a
step had to do) and the ``source`` of its largest operation.  The class of a
scope (``attention``, ``ffn``, ``head_sampler``, ...) comes from the one
table ``CLASS_OF``, keyed by the module and scope names the programs
declare; a name the table does not know is ``other``, and is printed.

    python benchmark/op_scopes.py <file.xplane.pb> [depth]

prints the whole table (scopes cut to ``depth`` names where given).  A trace
whose events carry no ``tf_op`` (an older runtime) or that has no device
plane (a CPU rehearsal) gives ``None``; nothing here raises for want of
something to read.
"""

from __future__ import annotations

import re
import sys
import time
from typing import Dict, Iterator, List, NamedTuple, Optional, Sequence, Tuple

import trace_reduce

UNNAMED = "(unnamed)"
COMPILER = "(compiler)"
_KEPT_STATS = ("tf_op", "hlo_category", "program_id", "flops", "bytes_accessed", "source")

# the class of a module or scope name, innermost known name first.  Keys are
# the names the models (``models/transformer.py``, ``routed_ffn.py``), the
# engine, the token learner and the fused loop declare, an index after the
# last ``_`` taken off (``attn_1``, ``ffn_norm_0``) unless the name is here
# with it (a plain GPT-2 layer's two norms are flax's ``LayerNorm_0`` and
# ``LayerNorm_1``)
CLASS_OF = {
    # a layer's token mixer: its norm, projections, cache write, core
    "LayerNorm_0": "attention", "attn_norm": "attention", "attn": "attention",
    "mixer": "attention", "qkv": "attention", "q": "attention", "kv": "attention",
    "q_norm": "attention", "k_norm": "attention", "proj": "attention",
    "kv_write": "attention", "attend": "attention", "attn_hc": "attention",
    "ssm_decode_update": "attention", "gdn_decode_update": "attention",
    "cca_window": "attention",
    # a layer's channel mixer: its norm, dense or routed
    "LayerNorm_1": "ffn", "ffn_norm": "ffn", "ffn": "ffn", "mlp_in": "ffn",
    "mlp_out": "ffn", "router": "ffn", "zaya_router": "ffn", "experts": "ffn",
    "shared": "ffn", "shared_gate": "ffn", "ffn_hc": "ffn",
    # behind the last layer, and the engine's sampler
    "final_norm": "head_sampler", "policy_head": "head_sampler",
    "value_head": "head_sampler", "sample": "head_sampler",
    "token_embed": "embed", "pos_embed": "embed", "obs_embed": "embed",
    # the token learner's step outside the model
    "loss": "loss", "update": "update", "guard": "update",
    # the fused classic loop's iteration
    "act": "act", "env_step": "env", "store": "store", "learn": "learn",
}

_BLOCK = re.compile(r"\bblock_\d+")
_INDEXED = re.compile(r"_\d+$")
_JIT = re.compile(r"^(?:jit|pjit|xla_call)\((.*)\)$")
# a transformation around a name: ``jvp(TransformerPolicy)``, ``transpose(jvp())``,
# and ``transpose(loss)`` where a custom-vjp kernel's backward rule was traced
_TRANSFORM = re.compile(r"^(transpose|jvp|vmap|remat|checkpoint|custom_jvp|custom_vjp)\((.*)\)$")
# a model's class as flax names an unnamed root module: ``TransformerPolicy``
_ROOT_CLASS = re.compile(r"^[A-Z][A-Za-z0-9]*$")
_WRAPPERS = frozenset(
    ("while", "body", "cond", "closed_call", "checkpoint", "pjit", "core_call",
     "custom_jvp_call", "custom_vjp_call", "rematted_computation", "remat", "branch")
)


# ---------------------------------------------------------------------------
# the protobuf wire format, as far as an XSpace needs it


def _varint(buf: bytes, pos: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def _fields(buf: bytes, pos: int, end: int) -> Iterator[Tuple[int, int, object]]:
    """``(field number, wire type, value)`` of one message's fields: an int
    for a varint or a fixed-width field, ``(start, end)`` into ``buf`` for a
    length-delimited one, which is not read until somebody asks."""
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value = (pos, pos + size)
            pos += size
        elif wire == 1:
            value = int.from_bytes(buf[pos : pos + 8], "little")
            pos += 8
        elif wire == 5:
            value = int.from_bytes(buf[pos : pos + 4], "little")
            pos += 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}: not an XSpace")
        yield number, wire, value


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0] : span[1]].decode("utf-8", "replace")


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _map_entry(buf: bytes, span: Tuple[int, int]) -> Tuple[int, Optional[Tuple[int, int]]]:
    key, value = 0, None
    for number, _wire, got in _fields(buf, *span):
        if number == 1:
            key = _signed(got)
        elif number == 2:
            value = got
    return key, value


def _stat(buf: bytes, span: Tuple[int, int], stat_names: Dict[int, str]):
    """One ``XStat`` as ``(name, value)`` for the integer and string kinds
    (the kept stats are of no other); a ``ref_value`` is the NAME of the
    stat metadata it points at."""
    name, value = None, None
    for number, _wire, got in _fields(buf, *span):
        if number == 1:
            name = stat_names.get(got)
        elif number == 3:  # uint64_value
            value = got
        elif number == 4:  # int64_value
            value = _signed(got)
        elif number == 5:  # str_value
            value = _text(buf, got)
        elif number == 7:  # ref_value
            value = stat_names.get(got, "")
    return name, value


class Meta(NamedTuple):
    """What an operation's ``XEventMetadata`` says of it."""

    name: str  # the whole HLO instruction, or a module's ``jit_decode(123)``
    op_name: Optional[str]  # ``tf_op`` less its trailing ``:type``
    category: str
    program_id: Optional[int]
    flops: int
    bytes_accessed: int
    source: str


class DevicePlane(NamedTuple):
    metas: Dict[int, Meta]  # by metadata id: the join's key
    modules: Dict[int, str]  # program_id -> ``jit_decode``
    ops: List[Tuple[int, float, float]]  # (metadata id, start ns, duration ns), line order


def _plane(buf: bytes, span: Tuple[int, int]) -> Optional[DevicePlane]:
    """One ``XPlane`` if it is a device's, else ``None`` without a look
    inside its lines (a host plane's hold most of the file)."""
    name, lines, event_meta, stat_meta = "", [], [], []
    for number, _wire, got in _fields(buf, *span):
        if number == 2:
            name = _text(buf, got)
        elif number == 3:
            lines.append(got)
        elif number == 4:
            event_meta.append(got)
        elif number == 5:
            stat_meta.append(got)
    if not trace_reduce._DEVICE_PLANE.match(name):
        return None
    stat_names: Dict[int, str] = {}
    for entry in stat_meta:
        key, value = _map_entry(buf, entry)
        for number, _wire, got in _fields(buf, *value) if value else ():
            if number == 2:
                stat_names[key] = _text(buf, got)
    metas: Dict[int, Meta] = {}
    for entry in event_meta:
        key, value = _map_entry(buf, entry)
        text, stats = "", {}
        for number, _wire, got in _fields(buf, *value) if value else ():
            if number == 2:
                text = _text(buf, got)
            elif number == 5:
                stat_name, stat_value = _stat(buf, got, stat_names)
                if stat_name in _KEPT_STATS:
                    stats[stat_name] = stat_value
        op_name = stats.get("tf_op")
        metas[key] = Meta(
            name=text,
            op_name=None if op_name is None else str(op_name).rsplit(":", 1)[0],
            category=str(stats.get("hlo_category", "")),
            program_id=stats.get("program_id"),
            flops=int(stats.get("flops") or 0),
            bytes_accessed=int(stats.get("bytes_accessed") or 0),
            source=str(stats.get("source", "")),
        )
    ops: List[Tuple[int, float, float]] = []
    modules: Dict[int, str] = {}
    for line in lines:
        line_name, stamp_ns, events = "", 0, []
        for number, _wire, got in _fields(buf, *line):
            if number == 2:
                line_name = _text(buf, got)
            elif number == 3:
                stamp_ns = _signed(got)
            elif number == 4:
                events.append(got)
        if line_name == "XLA Modules":
            for event in events:
                for number, _wire, got in _fields(buf, *event):
                    if number == 1 and got in metas:
                        # "jit_decode(6671129503126069069)"
                        head, _, rest = metas[got].name.rpartition("(")
                        if head and rest.rstrip(")").isdigit():
                            modules[int(rest.rstrip(")"))] = head
        if line_name not in trace_reduce._OP_LINES:
            continue
        for event in events:
            meta_id = offset_ps = duration_ps = 0
            for number, _wire, got in _fields(buf, *event):
                if number == 1:
                    meta_id = got
                elif number == 2:
                    offset_ps = got
                elif number == 3:
                    duration_ps = got
            # whole nanoseconds, as ``jax.profiler.ProfileData`` gives them
            ops.append((meta_id, float(stamp_ns + offset_ps // 1000), float(duration_ps // 1000)))
    return DevicePlane(metas, modules, ops)


def decode(path: str) -> List[DevicePlane]:
    """The device planes of one ``.xplane.pb``."""
    with open(path, "rb") as f:
        buf = f.read()
    planes = []
    for number, wire, got in _fields(buf, 0, len(buf)):
        if number == 1 and wire == 2:
            plane = _plane(buf, got)
            if plane is not None:
                planes.append(plane)
    return planes


# ---------------------------------------------------------------------------
# from an op_name to a scope


def scope_of(op_name: Optional[str], category: str, program: str) -> Tuple[str, str]:
    """``(scope, what is left of the path)`` of one operation.  The second
    is the op_name's tail as jax wrote it, for the rows that have no name:
    it says what the unnamed code does (``jit(log_softmax)/reduce_max``)."""
    if not op_name:
        return f"{COMPILER} {category or 'unknown'}", ""
    names: List[str] = []
    direction = ""
    parts = op_name.split("/")
    for part in parts:
        while (turned := _TRANSFORM.match(part)) is not None:
            if turned.group(1) == "transpose":
                direction = "bwd"
            elif turned.group(1) == "jvp" and not direction:
                direction = "fwd"
            part = turned.group(2)
        if not part or _JIT.match(part) or part in _WRAPPERS or "<locals>" in part or "->" in part:
            continue
        name = _BLOCK.sub("block_N", part)
        if not names or names[-1] != name:  # ``loss/transpose(loss)``: one name
            names.append(name)
    # the last name is the primitive (``dot_general``) unless the path was
    # cut at a scope the table knows
    if names and _class_key(names[-1]) is None:
        leaf = names.pop()
    else:
        leaf = ""
    said = [n for n in names if not _ROOT_CLASS.match(n) and n != "block_N"]
    if not said:
        tail = "/".join(parts[1:] if _JIT.match(parts[0]) else parts)
        return f"{UNNAMED} {program}", tail
    scope = "/".join(n for n in names if not _ROOT_CLASS.match(n))
    if direction:
        scope = f"{direction}/{scope}"
    return (f"{scope}/{leaf}" if leaf else scope), ""


def _class_key(name: str) -> Optional[str]:
    """The key of ``CLASS_OF`` a path component answers to, if any."""
    base = name.split(".", 1)[0]  # a module's method: ``attn_hc.read``
    if base in CLASS_OF:
        return base
    base = _INDEXED.sub("", base)
    return base if base in CLASS_OF else None


def class_of(scope: str) -> str:
    """The class of a scope by its innermost known name; ``unnamed``,
    ``compiler``, or ``other`` for a name the table does not know."""
    if scope.startswith(UNNAMED):
        return "unnamed"
    if scope.startswith(COMPILER):
        return "compiler"
    for name in reversed(scope.split("/")):
        key = _class_key(name)
        if key is not None:
            return CLASS_OF[key]
    return "other"


def direction_of(scope: str) -> str:
    head = scope.split("/", 1)[0]
    return head if head in ("fwd", "bwd") else ""


def under(scope: str, name: str) -> bool:
    """Whether ``name`` is one of the scope's own names (whatever lies
    below it: a learner's model has a ``policy_head`` too)."""
    return not scope.startswith("(") and name in scope.split("/")


# ---------------------------------------------------------------------------
# the table


class Row(NamedTuple):
    program: str  # ``jit_decode``
    scope: str
    seconds: float  # own device time inside the window, averaged over devices
    calls: float
    category: str  # of the row's largest operation
    flops: float  # the compiler's count, summed over the calls
    bytes_accessed: float
    source: str  # of the row's largest operation
    tails: Tuple[Tuple[str, float], ...]  # unnamed rows: (op_name tail @ source, seconds)


class Table(NamedTuple):
    rows: List[Row]  # largest first
    window_s: float
    busy_s: float  # trace_reduce's, for the shares
    own_s: float  # the rows' sum: busy_s plus what overlapping operations count twice
    devices: int
    seconds_to_read: float

    def share(self, keep) -> float:
        """Percent of busy time in the rows ``keep(row)`` accepts."""
        if self.busy_s <= 0:
            return 0.0
        return 100.0 * sum(r.seconds for r in self.rows if keep(r)) / self.busy_s

    def fold(self, depth: int) -> List[Row]:
        """The rows with scopes cut to their first ``depth`` names."""
        merged: Dict[Tuple[str, str], Row] = {}
        for row in self.rows:
            scope = row.scope if row.scope.startswith("(") else "/".join(row.scope.split("/")[:depth])
            old = merged.get((row.program, scope))
            merged[(row.program, scope)] = row._replace(scope=scope) if old is None else old._replace(
                seconds=old.seconds + row.seconds, calls=old.calls + row.calls,
                flops=old.flops + row.flops, bytes_accessed=old.bytes_accessed + row.bytes_accessed,
            )
        return sorted(merged.values(), key=lambda r: -r.seconds)


def read(path: str) -> Optional[Table]:
    """Reduce one trace file by scope; ``None`` when it has no device
    operation, or none that carries a ``tf_op``."""
    t0 = time.perf_counter()
    planes = [p for p in decode(path) if p.ops]
    if not planes or not any(m.op_name for p in planes for m in p.metas.values()):
        return None
    trace = trace_reduce.load(path)
    windows = [(s, e) for n, s, e in trace.spans if n == trace_reduce.WINDOW_SPAN]
    if windows:
        lo, hi = windows[-1]
    else:
        lo = min(s for p in planes for _m, s, _d in p.ops) + trace.clock_shift_ns
        hi = max(s + d for p in planes for _m, s, d in p.ops) + trace.clock_shift_ns
    acc: Dict[Tuple[str, str], Dict[str, object]] = {}
    busy_ns = 0.0
    for plane in planes:
        # the events' NAME is their metadata id: the join is by id, never by
        # the instruction's text (two programs both have a ``%fusion.1``)
        events = [
            trace_reduce.Event(str(m), s + trace.clock_shift_ns, s + d + trace.clock_shift_ns, False, False)
            for m, s, d in plane.ops
        ]
        busy_ns += trace_reduce.device_times(events, lo, hi)["busy"]
        own: Dict[int, List[float]] = {}
        for ev, ns, container in trace_reduce.nesting(events, lo, hi):
            if container or ns <= 0:
                continue
            slot = own.setdefault(int(ev.name), [0.0, 0])
            slot[0] += ns
            slot[1] += 1
        for meta_id, (ns, calls) in own.items():
            meta = plane.metas.get(meta_id) or Meta("", None, "", None, 0, 0, "")
            program = plane.modules.get(meta.program_id, "")
            if not program and meta.op_name:
                called = _JIT.match(meta.op_name.split("/", 1)[0])
                program = f"jit_{called.group(1)}" if called else ""
            scope, tail = scope_of(meta.op_name, meta.category, program.removeprefix("jit_"))
            row = acc.setdefault(
                (program, scope),
                dict(ns=0.0, calls=0, flops=0.0, bytes=0.0, top=-1.0, category="", source="", tails={}),
            )
            row["ns"] += ns
            row["calls"] += calls
            row["flops"] += float(meta.flops) * calls
            row["bytes"] += float(meta.bytes_accessed) * calls
            if ns > row["top"]:
                row.update(top=ns, category=meta.category, source=meta.source)
            if tail or scope.startswith(UNNAMED):
                key = f"{tail} @ {_short_source(meta.source)}"
                row["tails"][key] = row["tails"].get(key, 0.0) + ns
    n = len(planes)
    rows = [
        Row(
            program, scope, r["ns"] / n / 1e9, r["calls"] / n, r["category"], r["flops"] / n,
            r["bytes"] / n, _short_source(r["source"]),
            tuple(sorted(((k, v / n / 1e9) for k, v in r["tails"].items()), key=lambda kv: -kv[1])[:8]),
        )
        for (program, scope), r in acc.items()
    ]
    rows.sort(key=lambda r: -r.seconds)
    return Table(
        rows=rows, window_s=(hi - lo) / 1e9, busy_s=busy_ns / n / 1e9,
        own_s=sum(r.seconds for r in rows), devices=n,
        seconds_to_read=time.perf_counter() - t0,
    )


def _short_source(source: str) -> str:
    """``/root/repo/scalerl_tpu/genrl/continuous.py:1105`` from the
    package's directory on: a checkout's place is nobody's business."""
    for mark in ("/scalerl_tpu/", "/benchmark/"):
        at = source.rfind(mark)
        if at >= 0:
            return source[at + 1 :]
    return source.rsplit("/", 1)[-1]


def report(table: Table, rows: Optional[Sequence[Row]] = None, top: Optional[int] = 25) -> List[str]:
    """The table as text, largest rows first."""
    rows = table.rows if rows is None else rows
    off = 100.0 * (table.own_s - table.busy_s) / table.busy_s if table.busy_s > 0 else 0.0
    lines = [
        f"device time by program scope: {len(rows)} rows, own time {table.own_s:.4f} s of "
        f"{table.busy_s:.4f} s busy ({off:+.2f}%: operations that overlap count twice) in a "
        f"window of {table.window_s:.3f} s, {table.devices} device(s), read in "
        f"{table.seconds_to_read:.1f} s; " + ", ".join(
            f"{kind} {table.share(lambda r, k=kind: class_of(r.scope) == k):.2f}%"
            for kind in sorted({class_of(r.scope) for r in table.rows})
        )
    ]
    for row in rows if top is None else rows[:top]:
        s = max(row.seconds, 1e-12)
        lines.append(
            f"  {row.seconds:9.5f} s {100.0 * row.seconds / max(table.busy_s, 1e-12):6.2f}% "
            f"{row.calls:9.0f} calls  {row.program} | {row.scope}  [{class_of(row.scope)}; "
            f"{row.category}; {row.flops / s / 1e12:.2f} TFLOP/s, {row.bytes_accessed / s / 1e9:.1f} GB/s "
            f"by the compiler's count; {row.source}]"
        )
        if row.scope.startswith(UNNAMED):
            for tail, seconds in row.tails:
                lines.append(f"      {seconds:9.5f} s  {tail}")
    return lines


# ---------------------------------------------------------------------------
# what the per-layer metric files under ``metrics/`` call


def of(r) -> Optional[Table]:
    """The run's trace by scope (once a run, kept in the reading), or
    ``None`` when the run was not traced or its trace names no scope.  The
    first call logs the 25 largest rows."""
    if "op_scopes" not in r:
        path = r["ctx"].trace_path
        try:
            table = None if path is None or r.get("trace") is None else read(path)
        except (ValueError, IndexError, KeyError) as e:
            r["ctx"].log(f"op_scopes: the trace could not be read by scope: {e!r}")
            table = None
        r["op_scopes"] = table
        if table is not None:
            for line in report(table):
                r["ctx"].log(line)
    return r["op_scopes"]


def share(r, metric: str, keep) -> Optional[float]:
    """Own device time of the rows ``keep(row)`` accepts over the device's
    busy time, in percent; ``None`` without a table."""
    table = of(r)
    if table is None or table.busy_s <= 0:
        return None
    value = table.share(keep)
    r["ctx"].log(f"{metric}: {value:.3f}% of {table.busy_s:.4f} s busy")
    return value


def is_decode(row: Row) -> bool:
    """The engine's macro-step program (``jit_decode``; ``jit_verify`` is
    its speculative form)."""
    return row.program in ("jit_decode", "jit_verify")


def main(argv: Sequence[str]) -> int:
    table = read(argv[0])
    if table is None:
        print("no device operation with an op_name in this trace")
        return 0
    rows = table.fold(int(argv[1])) if len(argv) > 1 else None
    print("\n".join(report(table, rows, top=None)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
