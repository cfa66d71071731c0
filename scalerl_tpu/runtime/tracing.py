"""Cross-tier distributed tracing: spans over the fleet wire.

Telemetry (``runtime/telemetry.py``) answers aggregate questions — what is
p95, how many sheds — but never causal ones: *why* did this sequence take
900 ms?  Queue wait, decode, a reconnect retransmit, or replay backlog?
SEED RL and MindSpeed RL (PAPERS.md, arxiv 2507.19017) both argue the
actor/generation/learner tiers bottleneck each other in non-obvious ways;
per-request causality across process boundaries is the substrate every
"compose the planes" tuning decision stands on.  This module is that
substrate, in the telemetry idiom:

- :class:`Span` / :class:`SpanContext` — trace_id/span_id/parent_id plus
  ``host_id`` and **host-side monotonic timestamps only** (graftlint JG001
  twin: a span must never force a device read to stamp a time).  Wall-clock
  is derived once per process from a (wall, monotonic) anchor, so a
  mid-run NTP step cannot corrupt durations, and cross-host alignment is a
  single per-host offset the :class:`ClockSkewEstimator` measures off the
  heartbeat ping/pong RTTs that already flow.
- **Head-based sampling** — the decision is made once at the trace ROOT
  (``SCALERL_TRACE_SAMPLE=<rate>``, default 0.0: hot loops pay nothing);
  every descendant follows its parent's decision because a span with a
  remote parent context is always recorded.  Finished spans land in a
  bounded ring (``SCALERL_TRACE_SPANS``), so overhead is O(1) like the
  FlightRecorder.
- **Context propagation piggybacked on existing frames** — the codec-v2
  message dicts gain an optional ``"trace"`` key the same way ``_telem``
  rides result uploads: serving ``act`` requests, fleet task leases,
  disagg ``seq_batch`` uploads, and snapshot pushes all carry their parent
  context with zero new round-trips (:func:`inject` / :func:`extract`).
- **Retroactive spans** (:func:`record_span`) — instrumentation sites
  stamp ``time.monotonic()`` at the boundaries they already cross and emit
  the span after the fact, so tracing never adds a blocking call to a hot
  loop.
- **Live spans on the profiler's clock** (:func:`span`) — the hot paths
  (engine macro-step, learn step, fused chunk, round) open one context
  manager per phase.  It always opens a profiler annotation named
  ``"scalerl." + name`` through a hook (:func:`set_annotator`;
  ``utils/profiling.py`` installs ``jax.profiler.TraceAnnotation``), so a
  device trace carries the program's own phases on the profiler's clock,
  and records the :class:`Span` too when the trace's root was sampled.
  Whatever the profiler and the sampler do, a live span **always knows
  how long it took**: enter and exit read the monotonic clock, and the
  registry keeps per span name a counter pair ``span.<name>.count`` /
  ``span.<name>.seconds`` (:func:`span_totals`; two snapshots subtract).
  A span that took more than ``SLOW_FACTOR`` times its name's running
  level and at least ``SLOW_MIN_S`` records ONE ``slow_span`` flight
  event (:func:`slow_spans`): the names open above it, its direct
  children's shares, the thread's CPU seconds and the garbage
  collector's pauses inside it.
- **Per-host JSONL export** — when ``SCALERL_TRACE_DIR`` is set every
  finished span is appended (line-buffered) to
  ``spans_<host>.jsonl``, so a SIGTERM'd generation host loses at most the
  span it was writing; ``tools/trace_report.py`` merges the files,
  reconstructs trace trees, emits Chrome ``trace_event`` JSON, and prints
  the critical-path breakdown.

jax-free by design: fleet workers, generation-host shells, and spawn
children import this for pennies, and nothing here can ever issue a device
transfer.  The FlightRecorder link is the other direction: this module
registers a trace-id provider with ``telemetry``, so every flight event
recorded while a span is active carries the active ``trace`` id — fault
forensics link both ways.
"""

from __future__ import annotations

import gc
import json
import os
import random
import threading
import time
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Mapping, Optional

from scalerl_tpu.runtime import telemetry
from scalerl_tpu.utils.logging import get_logger

logger = get_logger(__name__)

ENV_SAMPLE = "SCALERL_TRACE_SAMPLE"
ENV_DIR = "SCALERL_TRACE_DIR"
ENV_SPANS = "SCALERL_TRACE_SPANS"

# the wire piggyback key: any protocol dict may carry one
# {"tid": ..., "sid": ...} context under this key (docs/OBSERVABILITY.md
# "Distributed tracing" documents the shape)
TRACE_KEY = "trace"

# one (wall, monotonic) anchor per process: every span's wall time is
# anchor_wall + (t_mono - anchor_mono), so a wall-clock step mid-run moves
# NOTHING (the timers.py lesson) and cross-host alignment reduces to one
# per-host offset
_ANCHOR_WALL = time.time()
_ANCHOR_MONO = time.monotonic()


def wall_of(t_mono: float) -> float:
    """Map a ``time.monotonic()`` stamp onto this process's wall anchor."""
    return _ANCHOR_WALL + (t_mono - _ANCHOR_MONO)


def new_id() -> str:
    return os.urandom(8).hex()


class SpanContext:
    """The propagated identity of a span: (trace_id, span_id)."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: str, span_id: str) -> None:
        self.trace_id = trace_id
        self.span_id = span_id

    def to_wire(self) -> Dict[str, str]:
        return {"tid": self.trace_id, "sid": self.span_id}

    @classmethod
    def from_wire(cls, node: Any) -> Optional["SpanContext"]:
        if not isinstance(node, Mapping):
            return None
        tid, sid = node.get("tid"), node.get("sid")
        if not (isinstance(tid, str) and isinstance(sid, str)):
            return None
        return cls(tid, sid)

    def __repr__(self) -> str:  # debugging aid in stall dumps
        return f"SpanContext({self.trace_id}/{self.span_id})"


class Span:
    """One recorded operation.  Created by :meth:`Tracer.start_span`;
    ``end()`` (idempotent) hands it to the tracer's ring + sink."""

    __slots__ = (
        "name", "kind", "trace_id", "span_id", "parent_id", "host",
        "t_start", "t_end", "attrs", "_tracer", "_ended",
    )
    sampled = True

    def __init__(
        self,
        tracer: "Tracer",
        name: str,
        trace_id: str,
        span_id: str,
        parent_id: Optional[str],
        kind: str,
        t_start: float,
        attrs: Dict[str, Any],
    ) -> None:
        self._tracer = tracer
        self.name = name
        self.kind = kind
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.host = telemetry.host_id()
        self.t_start = t_start  # monotonic
        self.t_end: Optional[float] = None
        self.attrs = attrs
        self._ended = False

    @property
    def context(self) -> SpanContext:
        return SpanContext(self.trace_id, self.span_id)

    def end(self, t_end: Optional[float] = None, **attrs: Any) -> None:
        """Finish the span at ``t_end`` (``time.monotonic()``, default now).
        Host-side stamps ONLY — never materialize a device value to end a
        span (the JG001 fixture pair pins this)."""
        if self._ended:
            return
        self._ended = True
        if attrs:
            self.attrs.update(attrs)
        self.t_end = t_end if t_end is not None else time.monotonic()
        self._tracer._finish(self)

    def to_record(self) -> Dict[str, Any]:
        t_end = self.t_end if self.t_end is not None else self.t_start
        return {
            "trace": self.trace_id,
            "span": self.span_id,
            "parent": self.parent_id,
            "name": self.name,
            "kind": self.kind,
            "host": self.host,
            "t0": wall_of(self.t_start),
            "dur": max(t_end - self.t_start, 0.0),
            "attrs": self.attrs,
        }

    # context-manager protocol: activates the span for FlightRecorder
    # trace stamping, ends it on exit
    def __enter__(self) -> "Span":
        self._tracer._push_active(self)
        return self

    def __exit__(self, *exc: Any) -> None:
        self._tracer._pop_active(self)
        self.end()


class _NoopSpan:
    """The unsampled root: every operation is a no-op, ``context`` is None
    so :func:`inject` stays silent and descendants stay unsampled."""

    __slots__ = ()
    sampled = False
    context = None
    trace_id = None

    def end(self, t_end: Optional[float] = None, **attrs: Any) -> None:
        pass

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc: Any) -> None:
        pass


NOOP_SPAN = _NoopSpan()


def _context_of(parent: Any) -> Optional[SpanContext]:
    """Normalize a parent argument: Span, SpanContext, wire dict, or None."""
    if parent is None or parent is NOOP_SPAN:
        return None
    if isinstance(parent, SpanContext):
        return parent
    ctx = getattr(parent, "context", None)
    if isinstance(ctx, SpanContext):
        return ctx
    return SpanContext.from_wire(parent)


class _ActiveStack(threading.local):
    """Per-thread stack of active recorded spans; ``None`` on a thread
    that never activated one (a class default: reading it costs no
    raised-and-caught AttributeError, which every live span would pay)."""

    stack: Optional[List[Any]] = None


class Tracer:
    """Head-sampling span factory with a bounded finished-span ring and an
    optional per-host JSONL sink (``SCALERL_TRACE_DIR``)."""

    def __init__(
        self,
        sample_rate: Optional[float] = None,
        capacity: Optional[int] = None,
        out_dir: Optional[str] = None,
    ) -> None:
        if sample_rate is None:
            sample_rate = float(os.environ.get(ENV_SAMPLE, "0") or 0.0)
        if capacity is None:
            capacity = int(os.environ.get(ENV_SPANS, "4096") or 4096)
        self.sample_rate = max(0.0, min(float(sample_rate), 1.0))
        self.capacity = max(int(capacity), 1)
        self.out_dir = out_dir if out_dir is not None else os.environ.get(
            ENV_DIR, ""
        )
        self._lock = threading.Lock()
        self._ring: Deque[Dict[str, Any]] = deque()
        self.dropped = 0
        self._sink = None
        self._sink_path: Optional[str] = None
        self._tls = _ActiveStack()
        # has any thread ever made a span or a context active?  Until then
        # no live span has a recorded parent to look for
        self.activated = False
        self._rng = random.Random(os.urandom(8))
        self._listeners: List[Callable[[Dict[str, Any]], None]] = []

    # -- sampling + span creation ---------------------------------------
    def _sample(self) -> bool:
        if self.sample_rate <= 0.0:
            return False
        if self.sample_rate >= 1.0:
            return True
        return self._rng.random() < self.sample_rate

    def start_span(
        self,
        name: str,
        parent: Any = None,
        kind: str = "",
        t_start: Optional[float] = None,
        **attrs: Any,
    ):
        """A new span.  ``parent`` is a Span, SpanContext, wire dict, or
        None; with None the HEAD sampling decision is made here (rate 0 =
        free no-op), with a parent the span always records — descendants
        follow their root's decision across process boundaries.
        ``t_start`` is an optional ``time.monotonic()`` stamp for
        retroactive spans."""
        ctx = _context_of(parent)
        if ctx is None:
            if not self._sample():
                return NOOP_SPAN
            trace_id, parent_id = new_id(), None
        else:
            trace_id, parent_id = ctx.trace_id, ctx.span_id
        span = Span(
            self,
            name,
            trace_id,
            new_id(),
            parent_id,
            kind,
            t_start if t_start is not None else time.monotonic(),
            dict(attrs),
        )
        telemetry.get_registry().counter("trace.spans_started").inc()
        return span

    # -- finished-span plumbing -----------------------------------------
    def _finish(self, span: Span) -> None:
        rec = span.to_record()
        reg = telemetry.get_registry()
        reg.counter("trace.spans_finished").inc()
        with self._lock:
            if len(self._ring) >= self.capacity:
                self._ring.popleft()
                self.dropped += 1
                reg.counter("trace.spans_dropped").inc()
            self._ring.append(rec)
            self._sink_write(rec)
            listeners = list(self._listeners)
        # outside the ring lock: a listener (the TierLedger's online feed)
        # may take its own locks and must never be able to deadlock a span
        # end against finished()/clear()
        for fn in listeners:
            try:
                fn(rec)
            except Exception as e:  # noqa: BLE001 — a listener must never kill a span site
                logger.warning("trace listener failed: %r", e)

    def add_listener(self, fn: Callable[[Dict[str, Any]], None]) -> None:
        """Subscribe ``fn`` to every finished-span record (called after the
        ring append, outside the ring lock).  This is how the streaming
        tier attribution (``runtime/attribution.py``) consumes spans ONLINE
        without polling the bounded ring — same records the JSONL sink
        writes, zero extra stamps."""
        with self._lock:
            if fn not in self._listeners:
                self._listeners.append(fn)

    def remove_listener(self, fn: Callable[[Dict[str, Any]], None]) -> None:
        with self._lock:
            try:
                self._listeners.remove(fn)
            except ValueError:
                pass

    def finished(self) -> List[Dict[str, Any]]:
        """The retained span records, oldest first (bounded ring)."""
        with self._lock:
            return list(self._ring)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()

    # -- active-span stack (FlightRecorder linkage) ---------------------
    def _push_active(self, span: Span) -> None:
        self.activated = True
        stack = self._tls.stack
        if stack is None:
            stack = self._tls.stack = []
        stack.append(span)

    def _pop_active(self, span: Span) -> None:
        stack = self._tls.stack
        if stack and stack[-1] is span:
            stack.pop()

    def current_span(self):
        stack = self._tls.stack
        return stack[-1] if stack else None

    def activate(self, parent: Any):
        """Context manager: make ``parent`` (Span/SpanContext/wire dict)
        the active trace for this thread WITHOUT creating a new span —
        flight events recorded inside carry its trace id."""
        return _Activation(self, _context_of(parent))

    # -- the per-host JSONL sink ----------------------------------------
    def _ensure_sink(self) -> bool:
        # called under self._lock; opens the per-host file + meta line once
        if self._sink is not None:
            return True
        try:
            os.makedirs(self.out_dir, exist_ok=True)
            host = "".join(
                ch if ch.isalnum() or ch in "-_" else "_"
                for ch in telemetry.host_id()
            )
            self._sink_path = os.path.join(
                self.out_dir, f"spans_{host}_{os.getpid()}.jsonl"
            )
            self._sink = open(self._sink_path, "a", buffering=1)
            self._sink.write(
                json.dumps(
                    {
                        "kind": "meta",
                        "host": telemetry.host_id(),
                        "pid": os.getpid(),
                        "anchor_wall": _ANCHOR_WALL,
                    },
                    default=str,
                )
                + "\n"
            )
            return True
        except Exception as e:  # noqa: BLE001 — the sink must never kill a span site
            logger.warning("trace sink open failed: %r", e)
            self.out_dir = ""
            return False

    # "meta"/"skew" are span-file record kinds consumed offline by
    # tools/trace_report.py, not codec-v2 wire frames — no recv pump ever
    # dispatches on them.  # graftlint: wire-ignore=meta,skew
    def _sink_write(self, obj: Dict[str, Any]) -> None:
        # called under self._lock.  Line-per-record append on a
        # line-buffered file: a SIGTERM'd host (no atexit) loses at most
        # the line in flight.
        if not self.out_dir or not self._ensure_sink():
            return
        try:
            self._sink.write(json.dumps(obj, default=str) + "\n")
        except Exception as e:  # noqa: BLE001
            logger.warning("trace sink write failed: %r", e)
            self.out_dir = ""  # stop retrying a broken sink

    def export_skew(self, estimator: Optional["ClockSkewEstimator"] = None) -> None:
        """Append this process's per-peer clock-skew offsets to the span
        file (``trace_report`` aligns other hosts' spans with them)."""
        est = estimator if estimator is not None else get_skew()
        with self._lock:
            if not self.out_dir:
                return
            self._sink_write(
                {"kind": "skew", "host": telemetry.host_id(),
                 "offsets": est.offsets()}
            )

    def close(self) -> None:
        with self._lock:
            if self._sink is not None:
                try:
                    self._sink.close()
                except Exception:  # noqa: BLE001 — teardown
                    pass
                self._sink = None


class _Activation:
    __slots__ = ("_tracer", "_ctx", "_span")

    def __init__(self, tracer: Tracer, ctx: Optional[SpanContext]) -> None:
        self._tracer = tracer
        self._ctx = ctx
        self._span = None

    def __enter__(self) -> Optional[SpanContext]:
        if self._ctx is not None:
            # a context-only activation rides the same stack as real spans
            holder = _CtxHolder(self._ctx)
            self._span = holder
            self._tracer._push_active(holder)
        return self._ctx

    def __exit__(self, *exc: Any) -> None:
        if self._span is not None:
            self._tracer._pop_active(self._span)


class _CtxHolder:
    """A stack entry for :meth:`Tracer.activate`: carries a trace id
    without being a recordable span."""

    __slots__ = ("trace_id", "context")
    sampled = True

    def __init__(self, ctx: SpanContext) -> None:
        self.trace_id = ctx.trace_id
        self.context = ctx


# ---------------------------------------------------------------------------
# wire propagation


def inject(msg: Dict[str, Any], parent: Any) -> Dict[str, Any]:
    """Stamp ``msg[TRACE_KEY]`` with the parent's context (no-op for
    unsampled/None parents).  Returns ``msg`` for chaining."""
    ctx = _context_of(parent)
    if ctx is not None and isinstance(msg, dict):
        msg[TRACE_KEY] = ctx.to_wire()
    return msg


def extract(msg: Any) -> Optional[SpanContext]:
    """The propagated context riding ``msg`` (dict with a ``trace`` key),
    or None.  Never mutates the message."""
    if not isinstance(msg, Mapping):
        return None
    return SpanContext.from_wire(msg.get(TRACE_KEY))


# ---------------------------------------------------------------------------
# clock-skew estimation off the existing heartbeat ping/pong RTTs


class ClockSkewEstimator:
    """Per-peer wall-clock offset from (ping t_send, pong rt, recv time).

    The classic NTP bound: ``offset = t_peer - (t_send + rtt / 2)``.  The
    sample taken at the smallest observed RTT is the tightest bound, so
    that one wins (an EWMA would let slow, asymmetric samples smear it).
    Offsets are measured at the OBSERVER — ``trace_report`` subtracts
    ``offset[host]`` from that host's span times to align every file on
    the observer's clock.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # peer -> (best_rtt, offset_at_best_rtt, samples)
        self._peers: Dict[str, List[float]] = {}

    def observe(
        self, peer: str, t_send: float, t_peer: float, t_recv: float
    ) -> None:
        rtt = max(t_recv - t_send, 0.0)
        offset = t_peer - (t_send + rtt / 2.0)
        with self._lock:
            entry = self._peers.get(peer)
            if entry is None:
                self._peers[peer] = [rtt, offset, 1.0]
            else:
                entry[2] += 1.0
                if rtt <= entry[0]:
                    entry[0], entry[1] = rtt, offset

    def offset(self, peer: str) -> float:
        with self._lock:
            entry = self._peers.get(peer)
            return entry[1] if entry is not None else 0.0

    def offsets(self) -> Dict[str, float]:
        with self._lock:
            return {p: e[1] for p, e in self._peers.items()}

    def samples(self, peer: str) -> int:
        with self._lock:
            entry = self._peers.get(peer)
            return int(entry[2]) if entry is not None else 0


def observe_pong(msg: Mapping[str, Any], t_recv: Optional[float] = None) -> None:
    """Feed one heartbeat pong into the default skew estimator.  Pongs
    carry the original ping's wall ``t`` plus the responder's ``rt`` and
    ``host`` (``supervisor.make_pong``); the hub calls this from its recv
    pump, so every heartbeat interval refreshes every link's offset with
    zero extra traffic."""
    if not isinstance(msg, Mapping):
        return
    peer, t_send, t_peer = msg.get("host"), msg.get("t"), msg.get("rt")
    if not peer or not isinstance(t_send, (int, float)) or not isinstance(
        t_peer, (int, float)
    ):
        return
    get_skew().observe(
        str(peer), float(t_send), float(t_peer),
        t_recv if t_recv is not None else time.time(),
    )


# ---------------------------------------------------------------------------
# process-wide defaults

_LOCK = threading.Lock()
_TRACER: Optional[Tracer] = None
_SKEW: Optional[ClockSkewEstimator] = None


def get_tracer() -> Tracer:
    global _TRACER
    if _TRACER is None:
        with _LOCK:
            if _TRACER is None:
                _TRACER = Tracer()
    return _TRACER


def get_skew() -> ClockSkewEstimator:
    global _SKEW
    if _SKEW is None:
        with _LOCK:
            if _SKEW is None:
                _SKEW = ClockSkewEstimator()
    return _SKEW


def reset() -> None:
    """Fresh default tracer + skew estimator, re-reading the env (tests)."""
    global _TRACER, _SKEW
    with _LOCK:
        if _TRACER is not None:
            _TRACER.close()
        _TRACER = Tracer()
        _SKEW = ClockSkewEstimator()


def start_span(name: str, parent: Any = None, kind: str = "", **attrs: Any):
    return get_tracer().start_span(name, parent=parent, kind=kind, **attrs)


def record_span(
    name: str,
    parent: Any,
    t_start: float,
    t_end: float,
    kind: str = "",
    **attrs: Any,
):
    """One-shot retroactive span from two ``time.monotonic()`` stamps the
    call site already took — the sanctioned hot-path idiom (the JG001
    good twin): no device value, no extra syscalls inside the loop."""
    span = get_tracer().start_span(
        name, parent=parent, kind=kind, t_start=t_start, **attrs
    )
    span.end(t_end=t_end)
    return span


# the profiler hook: a factory ``name -> context manager`` that opens a
# host annotation in the device profiler's trace.  This module stays
# jax-free, so ``utils/profiling.py`` (which imports jax already) installs
# ``jax.profiler.TraceAnnotation`` here when it is imported; with nothing
# installed a live span has no profiler half.
_ANNOTATOR: Optional[Callable[[str], Any]] = None
ANNOTATION_PREFIX = "scalerl."


def set_annotator(factory: Optional[Callable[[str], Any]]) -> None:
    global _ANNOTATOR
    _ANNOTATOR = factory


def get_annotator() -> Optional[Callable[[str], Any]]:
    return _ANNOTATOR


# ---------------------------------------------------------------------------
# always-on span accounting: totals per name and the slow-span event

SPAN_TOTALS_PREFIX = "span."
# a span is slow when it took more than SLOW_FACTOR times its name's
# running level and at least SLOW_MIN_S: constants, not knobs
SLOW_FACTOR = 4.0
SLOW_MIN_S = 0.25
SLOW_EVENT = "slow_span"
# a name's first occurrences build programs (a compile is not a stall):
# they are neither judged nor let into the level
_LEVEL_SKIP = 2
# the spans that block on the device: these and the roots carry a stamp of
# the thread's CPU clock, so that a slow one says whether the host worked
# or waited.  That clock is a real system call (5 us on the v5e's host,
# PERF.md PR 34), so a thread reads it at most once in _CPU_STAMP_EVERY_S
# and a span takes the thread's newest stamp: the CPU seconds it reports
# are since a moment ``cpu_slack_s`` before it began
_BLOCKING = frozenset({"genrl.read", "dispatch.read"})
_CPU_STAMP_EVERY_S = 0.02

_monotonic = time.monotonic
_thread_time = time.thread_time


class _LiveStack(threading.local):
    stack: Optional[List[Any]] = None  # the live spans open on this thread
    cpu_s = 0.0  # the thread's CPU clock when it was last read,
    cpu_at = float("-inf")  # and when that was, on the monotonic clock


_LIVE = _LiveStack()


class _GcClock:
    """The garbage collector's recent collections as ``(start, seconds)``
    on the monotonic clock, from ``gc.callbacks``: nothing runs between
    collections and a span stamps nothing; a slow span counts the
    collections that began inside it (of the last 4,096)."""

    __slots__ = ("recent", "_t0")

    def __init__(self) -> None:
        self.recent: Deque[Any] = deque(maxlen=4096)
        self._t0 = 0.0

    def __call__(self, phase: str, info: Dict[str, Any]) -> None:
        if phase == "start":
            self._t0 = _monotonic()
        else:
            self.recent.append((self._t0, _monotonic() - self._t0))

    def inside(self, t0: float, t1: float):
        """``(collections, pause seconds)`` that began in ``[t0, t1]``."""
        pauses = [took for began, took in list(self.recent) if t0 <= began <= t1]
        return len(pauses), sum(pauses)


_GC = _GcClock()
gc.callbacks.append(_GC)


class _NameStat:
    """One span name: the registry's counter pair and the running level
    its next occurrence is compared with (``_LiveSpan.__exit__`` keeps
    both, inline: a call is a fifth of a span's budget)."""

    __slots__ = ("registry", "count", "seconds", "add_one", "add_seconds", "seen", "level")

    def __init__(self, name: str) -> None:
        reg = telemetry.get_registry()
        self.registry = reg
        self.count = reg.counter(f"{SPAN_TOTALS_PREFIX}{name}.count")
        self.seconds = reg.counter(f"{SPAN_TOTALS_PREFIX}{name}.seconds")
        self.add_one, self.add_seconds = self.count.inc, self.seconds.inc
        self.seen = 0
        self.level: Optional[float] = None


_STATS: Dict[str, _NameStat] = {}


def _stat_for(name: str) -> _NameStat:
    # a fresh registry (telemetry.reset, tests) starts every name anew
    stat = _STATS[name] = _NameStat(name)
    return stat


def span_totals() -> Dict[str, Dict[str, float]]:
    """``{name: {"count": n, "seconds": s}}`` of every live span name that
    has ended in this process, from the registry's counter pairs.  Two
    snapshots subtract: the spans that ended between them."""
    reg = telemetry._REGISTRY
    return {
        name: {"count": stat.count.value, "seconds": stat.seconds.value}
        for name, stat in list(_STATS.items())
        if stat.registry is reg
    }


def slow_spans(
    since: Optional[float] = None, until: Optional[float] = None
) -> List[Dict[str, Any]]:
    """The ``slow_span`` events the flight recorder still holds, oldest
    first; with ``since``/``until`` (``time.monotonic()`` stamps) those
    that BEGAN inside that interval."""
    return [
        e for e in telemetry.get_recorder().events(SLOW_EVENT)
        if (since is None or e["t_start"] >= since)
        and (until is None or e["t_start"] <= until)
    ]


class _LiveSpan:
    """``tracing.span``: a live span around work as it happens::

        with tracing.span("genrl.read", kind="genrl"):
            host = _device_get(outputs)

    Entering it opens the profiler annotation ``"scalerl." + name`` (a
    no-op object unless the profiler runs) and, when this thread's trace
    was sampled at its root, records the :class:`Span` as a child of
    :func:`current_span`.  Whatever those two do, its duration lands in
    :func:`span_totals`, and a slow one in :func:`slow_spans`.  Host-side
    stamps only: never force a device value to open, annotate or close a
    span (graftlint JG001)."""

    __slots__ = (
        "_name", "_kind", "_attrs", "_late", "_annotation", "_span", "_t0",
        "_cpu0", "_cpu_at", "_kids",
    )

    def __init__(self, name: str, kind: str = "", **attrs: Any) -> None:
        self._name = name
        self._kind = kind
        self._attrs = attrs
        self._late: Optional[Dict[str, Any]] = None  # what set() was handed
        self._annotation = None
        self._span = None
        self._kids: Optional[List[Any]] = None  # (name, seconds) per direct child

    def __enter__(self) -> "_LiveSpan":
        if _ANNOTATOR is not None:
            self._annotation = _ANNOTATOR(ANNOTATION_PREFIX + self._name)
            self._annotation.__enter__()
        live = _LIVE
        stack = live.stack
        if stack is None:
            stack = live.stack = []
        t0 = self._t0 = _monotonic()
        if not stack or self._name in _BLOCKING:
            if t0 - live.cpu_at > _CPU_STAMP_EVERY_S:
                live.cpu_s, live.cpu_at = _thread_time(), t0
            self._cpu0, self._cpu_at = live.cpu_s, live.cpu_at
        else:
            self._cpu0 = None
        stack.append(self)
        tracer = _TRACER or get_tracer()
        if tracer.sample_rate > 0.0 or tracer.activated:
            # the recorded half.  The rule start_span has: head decision at
            # the root, children follow the span active on this thread (an
            # unsampled root is active too, so that its children stay
            # unsampled)
            parent = tracer.current_span()
            if parent is not None or tracer.sample_rate > 0.0:
                span = (
                    NOOP_SPAN
                    if parent is NOOP_SPAN
                    else tracer.start_span(
                        self._name, parent=parent, kind=self._kind, **self._attrs
                    )
                )
                tracer._push_active(span)
                self._span = span
        return self

    def set(self, **attrs: Any) -> None:
        """Attributes known only at the block's end (host values only).
        Kept as handed in (no copy: four of a macro-step's five spans are
        children that call this once); a slow span's event merges them."""
        if self._late is None:
            self._late = attrs
        else:
            self._late.update(attrs)
        if self._span is not None and self._span.sampled:
            self._span.attrs.update(attrs)

    def __exit__(self, exc_type: Any = None, exc: Any = None, tb: Any = None) -> None:
        t1 = _monotonic()
        dur = t1 - self._t0
        name = self._name
        stack = _LIVE.stack
        if stack and stack[-1] is self:
            stack.pop()
        stat = _STATS.get(name)
        if stat is None or stat.registry is not telemetry._REGISTRY:
            stat = _stat_for(name)
        stat.add_one()
        stat.add_seconds(dur)
        if stack:
            above = stack[-1]
            if above._kids is None:
                above._kids = [(name, dur)]
            else:
                above._kids.append((name, dur))
        # the name's running level: its first occurrences build programs
        # and are neither judged nor let in; after them the level falls
        # quickly and rises slowly, by a twentieth of itself at most, so
        # that one stall does not hide the next
        seen = stat.seen
        stat.seen = seen + 1
        if seen >= _LEVEL_SKIP:
            level = stat.level
            if level is None:
                stat.level = dur
            elif dur < level:
                stat.level = level + 0.25 * (dur - level)
            else:
                stat.level = level + 0.05 * ((dur if dur < 2.0 * level else 2.0 * level) - level)
                if dur >= SLOW_MIN_S and dur > SLOW_FACTOR * level:
                    self._record_slow(stack, t1, dur, level)
        span = self._span
        if span is not None:
            get_tracer()._pop_active(span)
            span.end()
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)

    def _record_slow(
        self, stack: List["_LiveSpan"], t1: float, dur: float, level: float
    ) -> None:
        """ONE flight event; nothing is dumped, nothing interrupts."""
        held: Dict[str, float] = {}
        for child, took in self._kids or ():
            held[child] = held.get(child, 0.0) + took
        collections, pause_s = _GC.inside(self._t0, t1)
        event = {
            "name": self._name,
            "above": [s._name for s in stack],
            "t_start": self._t0,
            "t_end": t1,
            "dur_s": dur,
            "level_s": level,
            "children": {c: took / dur for c, took in held.items()},
            # None: neither a root nor a blocking read, so not stamped
            "cpu_s": None if self._cpu0 is None else _thread_time() - self._cpu0,
            "cpu_slack_s": None if self._cpu0 is None else self._t0 - self._cpu_at,
            "gc_collections": collections,
            "gc_pause_s": pause_s,
            "attrs": {**self._attrs, **(self._late or {})},
        }
        telemetry.record_event(SLOW_EVENT, **event)
        logger.warning("slow span: %s", json.dumps(event, default=str))


# ``tracing.span(name, kind="", **attrs)``: the class itself, so that
# opening a span is one call
span = _LiveSpan


def current_trace_id() -> Optional[str]:
    span = get_tracer().current_span()
    return getattr(span, "trace_id", None) if span is not None else None


def sampling_enabled() -> bool:
    """Cheap hot-loop predicate: is there any chance a root samples?"""
    return get_tracer().sample_rate > 0.0


def export_skew() -> None:
    get_tracer().export_skew()


# FlightRecorder linkage: every flight event recorded while a span (or an
# activate()d context) is live on this thread carries its trace id
telemetry.set_trace_id_provider(current_trace_id)
