"""What every cell's run shares: spans, the compile clock, the window.

Nothing here knows a cell, a configuration or a metric by name.  A traffic
driver gets one :class:`Context` and calls ``open_window`` when its first
measured operation starts, ``tick`` after each completed unit of work, and
``close_window`` when ``tick`` says the time is up.
"""

from __future__ import annotations

import json
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, Iterator, List, Optional, Tuple

ROOT = Path(__file__).resolve().parent.parent
BENCH = Path(__file__).resolve().parent


def load_json(kind: str, name: str) -> Dict[str, Any]:
    """``benchmark/<kind>/<name>.json``: files are found by name."""
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"no {kind[:-1]} named {name!r}: {path} is missing")
    return json.loads(path.read_text())


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module, found by name."""
    import importlib.util

    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"no {kind} module named {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class CompileClock:
    """Program builds as JAX itself reports them (copied from
    ``chip_smoke.py``): every ``backend_compile_duration`` event is one
    program compiled or read from the persistent cache; hits and misses
    of that cache are counted beside it."""

    def __init__(self) -> None:
        import jax.monitoring

        self.seconds = 0.0
        self.builds = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.builds += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> Dict[str, float]:
        return {
            "seconds": self.seconds, "builds": self.builds,
            "hits": self.hits, "misses": self.misses,
        }


class Spans:
    """The benchmark's own host spans: a ``TraceAnnotation`` (so a traced
    run carries them on the profiler's clock) and an in-memory record on
    the host's monotonic clock."""

    def __init__(self) -> None:
        self.log: List[Tuple[str, float, float]] = []
        self._rolling: Dict[str, Tuple[Any, float]] = {}

    @contextmanager
    def span(self, name: str) -> Iterator[None]:
        import jax

        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.log.append((name, t0, time.perf_counter()))

    def roll(self, name: str) -> None:
        """End the open rolling span ``name`` and start the next one: for
        work seen only through a callback at each unit's end."""
        import jax

        now = time.perf_counter()
        previous = self._rolling.pop(name, None)
        if previous is not None:
            annotation, t0 = previous
            annotation.__exit__(None, None, None)
            self.log.append((name, t0, now))
        annotation = jax.profiler.TraceAnnotation(name)
        annotation.__enter__()
        self._rolling[name] = (annotation, now)

    def end_rolling(self) -> None:
        for annotation, _t0 in self._rolling.values():
            annotation.__exit__(None, None, None)
        self._rolling.clear()

    def durations(self, name: str, since: float, until: float) -> List[float]:
        return [
            t1 - t0 for n, t0, t1 in self.log
            if n == name and t0 >= since and t1 <= until
        ]


@dataclass
class Context:
    """One run of one cell."""

    workload: Dict[str, Any]  # benchmark/workloads/<cell>.json
    config: Dict[str, Any]  # benchmark/configs/<config>.json
    params: Dict[str, Any]  # the traffic parameters in force (rehearsal sizes applied)
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    process_start: float
    trace_dir: Path
    reference: Any = None  # benchmark/reference/<name>.py
    spans: Spans = field(default_factory=Spans)
    clock: Optional[CompileClock] = None
    t_open: Optional[float] = None
    t_close: Optional[float] = None
    setup_s: Optional[float] = None
    setup_compile: Optional[Dict[str, float]] = None
    window_compile: Optional[Dict[str, float]] = None
    trace_path: Optional[str] = None
    trace_counters: Dict[str, Any] = field(default_factory=dict)
    _tracing: Any = None

    def log(self, *parts: Any) -> None:
        print(f"[bench +{time.perf_counter() - self.process_start:7.2f}s]", *parts, flush=True)

    # -- the measured window ---------------------------------------------
    def open_window(self) -> None:
        """The first measured operation starts now: set-up ends here."""
        self.t_open = time.perf_counter()
        self.setup_s = self.t_open - self.process_start
        self.setup_compile = self.clock.snapshot()
        self.log(f"window opens; setup_s={self.setup_s:.2f}")

    def elapsed(self) -> float:
        return time.perf_counter() - self.t_open

    def tick(self, counters: Optional[Dict[str, Any]] = None) -> bool:
        """Call after each completed unit of work.  True once the window
        has lasted ``seconds``: the unit just completed is its last.  In a
        traced run the profiler is started ``trace_seconds`` before that,
        so the trace covers the window's tail and stopping it costs the
        window nothing; ``counters`` (cumulative) are remembered at the
        trace's two ends for the readers that need them."""
        elapsed = self.elapsed()
        if self.trace and self._tracing is None:
            lead = float(self.params.get("trace_seconds", 3.0))
            if elapsed >= self.seconds - lead:
                self._start_trace(counters)
        return elapsed >= self.seconds

    def _start_trace(self, counters: Optional[Dict[str, Any]]) -> None:
        import jax
        import trace_reduce

        trace_reduce.start_trace(str(self.trace_dir))
        self._tracing = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
        self._tracing.__enter__()
        self.trace_counters["start"] = dict(counters or {})

    def close_window(self, counters: Optional[Dict[str, Any]] = None) -> None:
        self.t_close = time.perf_counter()
        if self._tracing is not None:
            import trace_reduce

            self._tracing.__exit__(None, None, None)
            self.trace_counters["end"] = dict(counters or {})
            self.trace_path = trace_reduce.stop_trace(str(self.trace_dir))
        now = self.clock.snapshot()
        self.window_compile = {
            k: now[k] - self.setup_compile[k] for k in now
        }
        self.log(
            f"window closed after {self.t_close - self.t_open:.3f}s; "
            f"program builds inside: {self.window_compile['builds']}"
        )
        # every run says how even its units were: a stall shows here
        for name in sorted({n for n, _t0, _t1 in self.spans.log}):
            took = self.spans.durations(name, self.t_open, self.t_close)
            if took:
                self.log(
                    f"  {name}: {len(took)} in the window, median "
                    f"{1e3 * percentile(took, 50):.1f} ms, longest {1e3 * max(took):.1f} ms"
                )

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open


def percentile(values: List[float], q: float) -> Optional[float]:
    if not values:
        return None
    import numpy as np

    return float(np.percentile(np.asarray(values, dtype=np.float64), q))
