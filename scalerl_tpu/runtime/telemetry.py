"""Unified telemetry plane: metrics registry, flight recorder, fleet merge.

Every subsystem grown so far shipped its own ad-hoc counters —
``hub.protocol_errors``, ``server.duplicate_results``,
``ShmRolloutRing.torn_reads``, the train-step guard's
``skipped_steps``/``nonfinite_grads``, per-queue ``stats()`` — with no
single place to read, export, or correlate them.  IMPALA (arxiv 1802.01561)
and the Podracer report (arxiv 2104.06272) both stress that actor-learner
throughput tuning lives or dies on cross-plane visibility (actor FPS vs.
learner steps/s vs. queue occupancy).  This module is that plane:

- :class:`MetricsRegistry` — a process-local, thread-safe registry of
  **counters**, **gauges**, **histograms** (bounded reservoir quantile
  sketch), and **rate meters** (``fps``, ``learn_steps_per_s``).  Subsystems
  either hold instrument objects (host-side integer bumps, JG001-clean by
  construction — no device value ever enters an instrument) or ``bind()`` a
  snapshot-time callable for object state that already exists (queue depths,
  ring occupancy).  ``snapshot()`` returns one merged nested tree.
- :class:`FlightRecorder` — a bounded ring buffer of recent structured
  events (reconnects, torn reads, watchdog probes, non-finite skips,
  checkpoint save/restore, chaos injections).  It is dumped alongside the
  faulthandler stack dump on watchdog stall, on divergence rollback, and on
  SIGTERM — the "what happened just before" half of every stall report.
- :class:`TelemetryAggregator` — the learner-side merge point for compact
  snapshots piggybacked on fleet heartbeat pongs and result-upload frames
  (codec v2 dict payloads; no new message round-trips).  Per-source latest
  plus key-wise aggregate series.
- Exporters — periodic JSONL (one snapshot per line) and a Prometheus-style
  text exposition file, both driven by one :class:`TelemetryExportLoop`
  thread off the same registry.

jax-free by design: fleet workers and spawn children import this for
pennies, and nothing here can ever issue a device transfer.  Device metrics
still arrive via the one batched transfer per chunk
(``runtime.dispatch.get_metrics``); trainers feed the already-host floats
into the registry (:func:`observe_train_metrics`).

Process-wide access: :func:`get_registry` / :func:`get_recorder` return the
default instances (created on first use); :func:`reset` swaps in fresh ones
(tests).  When ``SCALERL_TELEMETRY_DIR`` is set, the process writes a
``final_snapshot.json`` at exit, for whatever harness started it to read.
"""

from __future__ import annotations

import json
import math
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Mapping, Optional, Tuple

from scalerl_tpu.utils.logging import get_logger

logger = get_logger(__name__)

ENV_DIR = "SCALERL_TELEMETRY_DIR"
ENV_HOST_ID = "SCALERL_HOST_ID"

# instrument kind tags used by the Prometheus exposition writer
_KIND_COUNTER = "counter"
_KIND_GAUGE = "gauge"
_KIND_HISTOGRAM = "histogram"
_KIND_METER = "meter"


def _now() -> float:
    return time.monotonic()


_HOST_ID: Optional[str] = None


def host_id() -> str:
    """A stable per-process identity for merged multi-host artifacts
    (flight-event ordering, trace span files): ``SCALERL_HOST_ID`` when the
    deployment sets one, else ``<hostname>-<pid>`` — distinct per process,
    stable for the process lifetime."""
    global _HOST_ID
    if _HOST_ID is None:
        env = os.environ.get(ENV_HOST_ID, "")
        if env:
            _HOST_ID = env
        else:
            import socket as _socket

            _HOST_ID = f"{_socket.gethostname()}-{os.getpid()}"
    return _HOST_ID


# runtime/tracing.py registers its current-trace lookup here, so every
# flight event recorded while a span is active carries the trace id —
# without telemetry (imported by everything) importing the tracer
_TRACE_ID_PROVIDER: Optional[Callable[[], Optional[str]]] = None


def set_trace_id_provider(fn: Optional[Callable[[], Optional[str]]]) -> None:
    global _TRACE_ID_PROVIDER
    _TRACE_ID_PROVIDER = fn


# ---------------------------------------------------------------------------
# instruments


class Counter:
    """Monotonic event counter.  ``inc`` is a host-side integer add under a
    lock cheap enough for per-chunk call sites (the hot loops bump once per
    chunk/batch, never per element)."""

    kind = _KIND_COUNTER
    __slots__ = ("name", "_value", "_lock")

    def __init__(self, name: str) -> None:
        self.name = name
        self._value = 0.0
        self._lock = threading.Lock()

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        return self._value

    def read(self) -> float:
        return self._value


class Gauge:
    """Last-written value (replay size, eps, queue depth at log time)."""

    kind = _KIND_GAUGE
    __slots__ = ("name", "_value")

    def __init__(self, name: str) -> None:
        self.name = name
        self._value = 0.0

    def set(self, v: float) -> None:
        self._value = float(v)

    @property
    def value(self) -> float:
        return self._value

    def read(self) -> float:
        return self._value


class Histogram:
    """Count/sum/min/max plus a bounded quantile estimator — one of two
    backends, chosen at construction:

    - ``backend="reservoir"`` (default): deterministic systematic sampling
      (every k-th observation once full — no RNG so snapshots are
      reproducible in tests).  Adequate for SMALL-count distributions
      (step latencies, batch staleness); structurally biased at the tail
      once the count dwarfs the 256-slot reservoir.
    - ``backend="digest"``: a mergeable log-bucket sketch
      (``runtime/attribution.LatencyDigest`` — fixed γ-spaced buckets,
      DDSketch-style) whose quantiles stay within ``relative_error`` of
      the true value at ANY count, and whose merge across hosts/threads
      is exact integer addition.  The traffic-plane SLO instruments
      (``serving.latency_s``, ``router.latency_s`` — the autoscaler's p95
      signal) live here; a million-request p99 from a 256-sample
      reservoir is not a number worth gating on.
    """

    kind = _KIND_HISTOGRAM
    __slots__ = ("name", "_lock", "count", "sum", "min", "max", "_reservoir",
                 "_cap", "_stride", "backend", "_digest")

    def __init__(self, name: str, reservoir_size: int = 256,
                 backend: str = "reservoir",
                 relative_error: float = 0.01) -> None:
        if backend not in ("reservoir", "digest"):
            raise ValueError(f"unknown histogram backend {backend!r}")
        self.name = name
        self.backend = backend
        self._lock = threading.Lock()
        self.count = 0
        self.sum = 0.0
        self.min = math.inf
        self.max = -math.inf
        self._reservoir: List[float] = []
        self._cap = int(reservoir_size)
        self._stride = 1
        self._digest = None
        if backend == "digest":
            # deferred import: attribution imports telemetry for the
            # registry, so the reverse edge must not run at module load
            from scalerl_tpu.runtime.attribution import LatencyDigest

            self._digest = LatencyDigest(relative_error=relative_error)

    def observe(self, v: float) -> None:
        v = float(v)
        if self._digest is not None:
            with self._lock:
                self.count += 1
                self.sum += v
                self.min = min(self.min, v)
                self.max = max(self.max, v)
            self._digest.observe(v)
            return
        with self._lock:
            self.count += 1
            self.sum += v
            self.min = min(self.min, v)
            self.max = max(self.max, v)
            if len(self._reservoir) < self._cap:
                self._reservoir.append(v)
            else:
                # systematic thinning: keep a bounded, roughly uniform sample
                self._stride += 1
                if self.count % self._stride == 0:
                    self._reservoir[self.count % self._cap] = v

    def quantile(self, q: float) -> float:
        if self._digest is not None:
            return self._digest.quantile(q)
        with self._lock:
            if not self._reservoir:
                return 0.0
            data = sorted(self._reservoir)
        idx = min(len(data) - 1, max(0, int(q * (len(data) - 1))))
        return data[idx]

    def digest_wire(self) -> Optional[Dict[str, Any]]:
        """The mergeable digest snapshot (JSON-safe), or None on the
        reservoir backend — the fleet piggyback / artifact hook."""
        return self._digest.to_wire() if self._digest is not None else None

    def read(self) -> Dict[str, float]:
        with self._lock:
            if self.count == 0:
                return {"count": 0.0, "sum": 0.0, "mean": 0.0,
                        "min": 0.0, "max": 0.0, "p50": 0.0, "p95": 0.0,
                        "p99": 0.0}
            out = {
                "count": float(self.count),
                "sum": self.sum,
                "mean": self.sum / self.count,
                "min": self.min,
                "max": self.max,
            }
        out["p50"] = self.quantile(0.50)
        out["p95"] = self.quantile(0.95)  # the serving SLO quantile
        out["p99"] = self.quantile(0.99)
        if self._digest is not None:
            # the digest's tail stays trustworthy at any count — expose the
            # p999 the reservoir could never honestly report
            out["p999"] = self.quantile(0.999)
        return out


class RateMeter:
    """Sliding-window event rate (``fps``, ``learn_steps_per_s``).

    ``mark(n)`` records n events now; ``rate()`` is events/second over the
    trailing ``window_s`` seconds.  ``total`` is the lifetime event count
    (so the meter doubles as a counter in snapshots).
    """

    kind = _KIND_METER
    __slots__ = ("name", "window_s", "_lock", "_events", "total", "_t0")

    def __init__(self, name: str, window_s: float = 30.0) -> None:
        self.name = name
        self.window_s = float(window_s)
        self._lock = threading.Lock()
        self._events: Deque[Tuple[float, float]] = deque()
        self.total = 0.0
        self._t0 = _now()

    def mark(self, n: float = 1.0) -> None:
        t = _now()
        with self._lock:
            self.total += n
            self._events.append((t, float(n)))
            self._trim(t)

    def _trim(self, t: float) -> None:
        horizon = t - self.window_s
        while self._events and self._events[0][0] < horizon:
            self._events.popleft()

    def rate(self) -> float:
        t = _now()
        with self._lock:
            self._trim(t)
            if not self._events:
                return 0.0
            n = sum(c for _, c in self._events)
            # observed span, floored at 1 s so a fresh burst reports a
            # per-second rate instead of an absurd instantaneous one
            span = max(t - max(self._events[0][0], t - self.window_s), 1.0)
        return n / span

    def read(self) -> Dict[str, float]:
        return {"rate": self.rate(), "total": self.total}


Instrument = Any  # Counter | Gauge | Histogram | RateMeter


# ---------------------------------------------------------------------------
# registry


class MetricsRegistry:
    """Process-local, thread-safe instrument registry with a snapshot tree.

    Names are dotted paths (``hub.protocol_errors``, ``train.fps``); the
    snapshot nests on the dots.  Two ways in:

    - ``counter``/``gauge``/``histogram``/``meter`` return (creating once)
      the named instrument — the same name always yields the same object,
      so call sites don't need to thread instrument handles around.
    - ``bind(name, fn)`` registers a snapshot-time callable for state that
      already lives on an object (``queue.stats``, ``ring.stats``,
      ``aggregator.tree``).  ``fn`` may return a scalar or a dict subtree;
      a raising binding snapshots as an error string instead of killing the
      exporter (the object may have been torn down).
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._instruments: Dict[str, Instrument] = {}
        self._bindings: Dict[str, Callable[[], Any]] = {}

    # -- instrument access ---------------------------------------------
    def _get(self, name: str, factory: Callable[[str], Instrument]):
        with self._lock:
            inst = self._instruments.get(name)
            if inst is None:
                inst = factory(name)
                self._instruments[name] = inst
            return inst

    def counter(self, name: str) -> Counter:
        inst = self._get(name, Counter)
        if not isinstance(inst, Counter):
            raise TypeError(f"instrument {name!r} is a {inst.kind}, not a counter")
        return inst

    def gauge(self, name: str) -> Gauge:
        inst = self._get(name, Gauge)
        if not isinstance(inst, Gauge):
            raise TypeError(f"instrument {name!r} is a {inst.kind}, not a gauge")
        return inst

    def histogram(self, name: str, reservoir_size: int = 256,
                  backend: str = "reservoir",
                  relative_error: float = 0.01) -> Histogram:
        inst = self._get(
            name,
            lambda n: Histogram(n, reservoir_size, backend=backend,
                                relative_error=relative_error),
        )
        if not isinstance(inst, Histogram):
            raise TypeError(f"instrument {name!r} is a {inst.kind}, not a histogram")
        return inst

    def meter(self, name: str, window_s: float = 30.0) -> RateMeter:
        inst = self._get(name, lambda n: RateMeter(n, window_s))
        if not isinstance(inst, RateMeter):
            raise TypeError(f"instrument {name!r} is a {inst.kind}, not a meter")
        return inst

    def bind(self, name: str, fn: Callable[[], Any]) -> None:
        """Bind a snapshot-time callable at ``name`` (scalar or dict subtree).
        Rebinding replaces — short-lived objects (tests, respawned rings)
        simply shadow their predecessor."""
        with self._lock:
            self._bindings[name] = fn

    def unbind(self, name: str) -> None:
        with self._lock:
            self._bindings.pop(name, None)

    def set_gauges(self, values: Mapping[str, float], prefix: str = "") -> None:
        """Bulk gauge write: the trainer idiom for a host metric dict —
        every numeric value lands as ``<prefix><key>``."""
        for k, v in values.items():
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                continue
            if isinstance(v, float) and not math.isfinite(v):
                continue  # NaN/Inf gauges poison aggregations downstream
            try:
                self.gauge(prefix + k).set(float(v))
            except TypeError:
                # the name is already a meter/counter (e.g. train.fps as a
                # RateMeter) — that instrument is the source of truth; the
                # bulk gauge write must not fight it
                continue

    # -- snapshots -----------------------------------------------------
    def _values(self) -> Dict[str, Any]:
        with self._lock:
            instruments = dict(self._instruments)
            bindings = dict(self._bindings)
        flat: Dict[str, Any] = {}
        for name, inst in instruments.items():
            flat[name] = inst.read()
        for name, fn in bindings.items():
            try:
                flat[name] = fn()
            except Exception as e:  # noqa: BLE001 — a dead binding must not kill a snapshot
                flat[name] = f"<error: {e!r}>"
        return flat

    def snapshot(self) -> Dict[str, Any]:
        """One merged nested tree of every instrument and binding."""
        tree: Dict[str, Any] = {}
        for name, value in self._values().items():
            parts = name.split(".")
            node = tree
            for p in parts[:-1]:
                nxt = node.get(p)
                if not isinstance(nxt, dict):
                    nxt = {} if nxt is None else {"_value": nxt}
                    node[p] = nxt
                node = nxt
            leaf = parts[-1]
            if isinstance(node.get(leaf), dict) and isinstance(value, dict):
                node[leaf].update(value)
            else:
                node[leaf] = value
        return tree

    def scalars(self, prefix: str = "") -> Dict[str, float]:
        """Flat ``{dotted.name: float}`` view (histograms/meters expand to
        their summary fields) — the logger/exposition write path."""
        out: Dict[str, float] = {}

        def emit(name: str, value: Any) -> None:
            if isinstance(value, dict):
                for k, v in value.items():
                    emit(f"{name}.{k}", v)
            elif isinstance(value, bool):
                out[name] = float(value)
            elif isinstance(value, (int, float)):
                out[name] = float(value)

        for name, value in self._values().items():
            emit(prefix + name, value)
        return out

    def compact(self, prefix: str = "") -> Dict[str, float]:
        """Compact flat snapshot for the fleet piggyback: counters, meter
        totals/rates, and gauges only — histograms ship their count/mean.
        Small enough to ride every heartbeat pong without bloating frames."""
        out: Dict[str, float] = {}
        for name, value in self.scalars(prefix).items():
            # drop the per-quantile histogram fields from the wire payload
            # (.p999 is the digest backend's extra tail field)
            if name.endswith((".p50", ".p95", ".p99", ".p999", ".min",
                              ".max", ".sum")):
                continue
            out[name] = value
        return out


# ---------------------------------------------------------------------------
# flight recorder


class FlightRecorder:
    """Bounded ring buffer of recent structured events.

    ``record(kind, **fields)`` is cheap (deque append under a lock) and safe
    from any thread; the recorder keeps only the newest ``capacity`` events,
    so it can run for days and still dump a readable tail on failure.
    """

    def __init__(self, capacity: int = 256) -> None:
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._events: Deque[Dict[str, Any]] = deque(maxlen=self.capacity)
        self.total_recorded = 0

    def record(self, kind: str, **fields: Any) -> None:
        evt = {
            "t_wall": time.time(),
            "t_mono": time.monotonic(),
            "kind": kind,
            # merged multi-host timelines (trace_report, soak verdicts)
            # order on (host_id, seq) — deterministic even when the hosts'
            # wall clocks disagree
            "host_id": host_id(),
        }
        if _TRACE_ID_PROVIDER is not None:
            try:
                tid = _TRACE_ID_PROVIDER()
            except Exception:  # noqa: BLE001 — stamping must never fail a record
                tid = None
            if tid:
                evt["trace"] = tid
        if fields:
            evt.update(fields)
        with self._lock:
            evt["seq"] = self.total_recorded  # monotonic per process
            self._events.append(evt)
            self.total_recorded += 1

    def events(self, kind: Optional[str] = None) -> List[Dict[str, Any]]:
        """The retained tail, oldest first; ``kind`` filters to one event
        kind (``events("autoscale_decision")`` — the soak/chaos assertions)."""
        with self._lock:
            evts = list(self._events)
        if kind is None:
            return evts
        return [e for e in evts if e.get("kind") == kind]

    def clear(self) -> None:
        with self._lock:
            self._events.clear()

    def dump_text(self) -> str:
        evts = self.events()
        if not evts:
            return "<flight recorder empty>"
        lines = [
            f"flight recorder: last {len(evts)} events "
            f"({self.total_recorded} total recorded, capacity {self.capacity})"
        ]
        for e in evts:
            # host_id/seq are ordering stamps, constant/monotonic within one
            # process — noise in a single-process stall dump (trace stays:
            # it is the cross-reference into the span files)
            extra = {
                k: v
                for k, v in e.items()
                if k not in ("t_wall", "t_mono", "kind", "host_id", "seq")
            }
            stamp = time.strftime("%H:%M:%S", time.localtime(e["t_wall"]))
            lines.append(f"  [{stamp}] {e['kind']} {extra}" if extra
                         else f"  [{stamp}] {e['kind']}")
        return "\n".join(lines)

    def dump_json(self, path: str) -> str:
        """Write the event tail as JSON (``{"events": [...]}``); returns the
        path.  Best-effort: failures are logged, never raised — dumps run on
        failure paths (signal handlers, watchdog fires)."""
        try:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            with open(path, "w") as f:
                json.dump(
                    {
                        "total_recorded": self.total_recorded,
                        "capacity": self.capacity,
                        "events": self.events(),
                    },
                    f,
                    default=str,
                )
        except Exception as e:  # noqa: BLE001 — a dump failure must not mask the crash
            logger.warning("flight recorder dump to %s failed: %r", path, e)
        return path


# ---------------------------------------------------------------------------
# fleet aggregation (learner side)


class TelemetryAggregator:
    """Merge compact per-source snapshots into per-worker + aggregate series.

    Sources are fleet peers — ``gather:<base_worker_id>`` uplinks and the
    ``worker:<id>`` payloads they relay.  ``absorb`` keeps the latest
    snapshot per source (these are cumulative counters, so "latest" IS the
    series value) plus a last-seen stamp; ``aggregate`` sums each key across
    sources.  ``tree()`` is what the registry binding exposes under
    ``fleet.*`` in the merged snapshot.

    Elastic churn means dead sources: a preempted worker's series would
    otherwise sit in the learner's view forever (every respawn adds a
    fresh source id), so the aggregator is BOUNDED — ``max_sources > 0``
    evicts the stalest source when a new one would exceed the cap, and
    :meth:`evict_stale` drops every source silent past ``max_age_s``
    (``age_s`` in the tree is the staleness a human reads).
    """

    def __init__(self, max_sources: int = 0) -> None:
        self._lock = threading.Lock()
        self._latest: Dict[str, Dict[str, float]] = {}
        self._seen: Dict[str, float] = {}
        self.frames_absorbed = 0
        self.max_sources = int(max_sources)
        self.evicted = 0

    def absorb(self, source: str, compact: Mapping[str, Any]) -> None:
        if not isinstance(compact, Mapping):
            return
        clean = {
            k: float(v)
            for k, v in compact.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)
        }
        with self._lock:
            self._latest[str(source)] = clean
            self._seen[str(source)] = time.monotonic()
            self.frames_absorbed += 1
            while self.max_sources > 0 and len(self._latest) > self.max_sources:
                stalest = min(self._seen, key=self._seen.get)
                self._latest.pop(stalest, None)
                self._seen.pop(stalest, None)
                self.evicted += 1

    def evict_stale(self, max_age_s: float) -> int:
        """Drop every source silent for longer than ``max_age_s``; returns
        the count — the learner's fleet view stays bounded across elastic
        churn (dead gathers/workers age out instead of accumulating)."""
        horizon = time.monotonic() - max_age_s
        dropped = 0
        with self._lock:
            for src in [s for s, t in self._seen.items() if t < horizon]:
                self._latest.pop(src, None)
                self._seen.pop(src, None)
                dropped += 1
            self.evicted += dropped
        return dropped

    def absorb_payload(self, payload: Any) -> None:
        """Absorb one piggybacked ``{"src": ..., "v": {...}, "workers":
        {id: {...}}}`` payload (the fleet wire shape)."""
        if not isinstance(payload, Mapping):
            return
        src = payload.get("src")
        if src is not None:
            self.absorb(str(src), payload.get("v") or {})
        for wid, wsnap in (payload.get("workers") or {}).items():
            self.absorb(f"worker:{wid}", wsnap)

    def sources(self) -> List[str]:
        with self._lock:
            return sorted(self._latest)

    def aggregate(self) -> Dict[str, float]:
        agg: Dict[str, float] = {}
        with self._lock:
            snaps = list(self._latest.values())
        for snap in snaps:
            for k, v in snap.items():
                agg[k] = agg.get(k, 0.0) + v
        return agg

    def tree(self) -> Dict[str, Any]:
        with self._lock:
            per_worker = {src: dict(snap) for src, snap in self._latest.items()}
            seen = dict(self._seen)
        now = time.monotonic()
        return {
            "sources": len(per_worker),
            "frames_absorbed": self.frames_absorbed,
            "evicted": self.evicted,
            "aggregate": self.aggregate(),
            "per_worker": {
                src: {**snap, "age_s": round(now - seen.get(src, now), 3)}
                for src, snap in per_worker.items()
            },
        }


# ---------------------------------------------------------------------------
# exporters


class JsonlExporter:
    """Append one ``{"t": ..., "snapshot": {...}}`` line per write."""

    def __init__(self, path: str) -> None:
        self.path = path
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)

    def write(self, snapshot: Mapping[str, Any]) -> None:
        line = json.dumps({"t": time.time(), "snapshot": snapshot}, default=str)
        with open(self.path, "a") as f:
            f.write(line + "\n")


class PrometheusExporter:
    """Write a Prometheus text-exposition file (atomic tmp+rename).

    Names are sanitized to the ``[a-zA-Z_][a-zA-Z0-9_]*`` charset with the
    repo-wide ``scalerl_`` prefix; scrapers (or a human with ``cat``) get
    the whole plane in one file.
    """

    def __init__(self, path: str) -> None:
        self.path = path
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)

    @staticmethod
    def _sanitize(name: str) -> str:
        out = []
        for ch in name:
            out.append(ch if ch.isalnum() or ch == "_" else "_")
        s = "".join(out)
        if not s or not (s[0].isalpha() or s[0] == "_"):
            s = "_" + s
        return "scalerl_" + s

    def write(self, scalars: Mapping[str, float]) -> None:
        lines = []
        for name in sorted(scalars):
            v = scalars[name]
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                continue
            if isinstance(v, float) and not math.isfinite(v):
                v = 0.0
            lines.append(f"{self._sanitize(name)} {v}")
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            f.write("\n".join(lines) + "\n")
        os.replace(tmp, self.path)


class TelemetryExportLoop:
    """Background thread writing JSONL + Prometheus exposition every
    ``interval_s`` seconds from one registry.  ``flush()`` writes
    immediately (end-of-run / tests); ``stop()`` flushes once more so the
    files always hold the final state."""

    def __init__(
        self,
        out_dir: str,
        interval_s: float = 30.0,
        registry: Optional[MetricsRegistry] = None,
    ) -> None:
        self.out_dir = out_dir
        self.interval_s = float(interval_s)
        self.registry = registry
        self.jsonl = JsonlExporter(os.path.join(out_dir, "telemetry.jsonl"))
        self.prom = PrometheusExporter(os.path.join(out_dir, "metrics.prom"))
        self.writes = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def _registry(self) -> MetricsRegistry:
        return self.registry if self.registry is not None else get_registry()

    def flush(self) -> None:
        reg = self._registry()
        try:
            self.jsonl.write(reg.snapshot())
            self.prom.write(reg.scalars())
            self.writes += 1
        except Exception:  # noqa: BLE001 — exporter must never kill the run
            logger.exception("telemetry export failed")

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            self.flush()

    def start(self) -> "TelemetryExportLoop":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, name="telemetry-export", daemon=True
            )
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None
        self.flush()

    def __enter__(self) -> "TelemetryExportLoop":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()


# ---------------------------------------------------------------------------
# process-wide defaults

_LOCK = threading.Lock()
_REGISTRY: Optional[MetricsRegistry] = None
_RECORDER: Optional[FlightRecorder] = None
_ENV_DUMP_INSTALLED = False


def _maybe_install_env_dump() -> None:
    """When ``SCALERL_TELEMETRY_DIR`` is set, write a final snapshot +
    flight-recorder tail at interpreter exit."""
    global _ENV_DUMP_INSTALLED
    if _ENV_DUMP_INSTALLED:
        return
    _ENV_DUMP_INSTALLED = True
    out_dir = os.environ.get(ENV_DIR, "")
    if not out_dir:
        return
    import atexit

    def _dump() -> None:
        try:
            write_final_snapshot(out_dir)
        except Exception:  # noqa: BLE001 — exit hooks must be silent
            pass

    atexit.register(_dump)


def write_final_snapshot(out_dir: str) -> str:
    """Write ``final_snapshot.json`` (merged tree + flight tail) to
    ``out_dir``; returns the path."""
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "final_snapshot.json")
    payload = {
        "t": time.time(),
        "pid": os.getpid(),
        "snapshot": get_registry().snapshot(),
        "flight_recorder": get_recorder().events(),
    }
    tmp = path + f".tmp{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(payload, f, default=str)
    os.replace(tmp, path)
    return path


def get_registry() -> MetricsRegistry:
    global _REGISTRY
    if _REGISTRY is None:
        with _LOCK:
            if _REGISTRY is None:
                _REGISTRY = MetricsRegistry()
    _maybe_install_env_dump()
    return _REGISTRY


def get_recorder() -> FlightRecorder:
    global _RECORDER
    if _RECORDER is None:
        with _LOCK:
            if _RECORDER is None:
                _RECORDER = FlightRecorder(
                    int(os.environ.get("SCALERL_FLIGHT_EVENTS", "256") or 256)
                )
    return _RECORDER


def reset() -> None:
    """Fresh default registry + recorder (tests)."""
    global _REGISTRY, _RECORDER
    with _LOCK:
        _REGISTRY = MetricsRegistry()
        _RECORDER = FlightRecorder()


def record_event(kind: str, **fields: Any) -> None:
    """Record one structured event on the default flight recorder."""
    get_recorder().record(kind, **fields)


def snapshot() -> Dict[str, Any]:
    """The merged tree of the default registry (module-level convenience)."""
    return get_registry().snapshot()


def compact_snapshot(prefix: str = "") -> Dict[str, float]:
    return get_registry().compact(prefix)


def flight_dump_path(tag: str) -> str:
    """Where failure-path flight dumps land: ``SCALERL_TELEMETRY_DIR`` when
    set, else the system tempdir."""
    import tempfile

    out_dir = os.environ.get(ENV_DIR, "") or tempfile.gettempdir()
    return os.path.join(out_dir, f"scalerl_flight_{tag}_{os.getpid()}.json")


def observe_train_metrics(host_metrics: Optional[Mapping[str, Any]]) -> None:
    """Fold one chunk/step's already-host metric dict into the registry.

    Accumulates the train-step guard counters (``skipped_steps``,
    ``nonfinite_grads``) and records a flight event when a chunk skipped
    non-finite updates.  Host floats only — callers pass the output of
    ``runtime.dispatch.get_metrics`` (or any plain dict), never device
    values, so this can never add a transfer to a hot loop.
    """
    if not host_metrics:
        return
    reg = get_registry()

    def _num(key: str) -> float:
        v = host_metrics.get(key, 0.0)
        try:
            f = float(v)
        except (TypeError, ValueError):
            return 0.0
        return f if math.isfinite(f) else 0.0

    skipped = _num("skipped_steps")
    nonfinite = _num("nonfinite_grads")
    if skipped > 0.0:
        reg.counter("train.skipped_steps").inc(skipped)
        record_event("nonfinite_skip", skipped_steps=skipped,
                     nonfinite_grads=nonfinite)
    if nonfinite > 0.0:
        reg.counter("train.nonfinite_grads").inc(nonfinite)


def observe_staleness(lag_steps: float, plane: str = "") -> float:
    """Set the unified ``staleness`` gauge: LEARNER STEPS BEHIND THE NEWEST
    GENERATION — the one staleness definition every distribution path
    reports (docs/OBSERVABILITY.md).

    ``serving.staleness``, genrl's generation lag, and the disagg snapshot
    lag used to each carry their own name and unit; they now all funnel
    here (computed via ``ParamSnapshotPlane.staleness_steps``, whose
    bounded generation -> learner-step map converts a served generation tag
    into learner steps).  ``plane`` additionally stamps
    ``staleness_plane.<plane>`` so a multi-plane process can still tell the
    reporters apart; the unified gauge always holds the latest report.
    """
    lag = float(max(lag_steps, 0.0))
    reg = get_registry()
    reg.gauge("staleness").set(lag)
    if plane:
        reg.gauge(f"staleness_plane.{plane}").set(lag)
    return lag
