"""Device-side tracing: jax.profiler integration.

The TPU half of the observability story (SURVEY.md §5): the reference had
only host timers (``scalerl/utils/profile.py``) — ported as
``utils.timers`` — with no device tracing at all.  Here ``trace()`` wraps
``jax.profiler.trace`` (XPlane/perfetto output for TensorBoard's profile
plugin).  Importing this module also gives ``runtime/tracing.py`` (which
stays jax-free) its profiler half: every ``tracing.span`` then opens a
``jax.profiler.TraceAnnotation`` named ``scalerl.<span>``, so the
program's own phases line up against the device streams in any captured
trace.  The annotation is a no-op object while no profiler runs.
"""

from __future__ import annotations

import contextlib
from typing import Iterator, Optional

import jax

from scalerl_tpu.runtime import tracing

tracing.set_annotator(jax.profiler.TraceAnnotation)


@contextlib.contextmanager
def trace(log_dir: str, create_perfetto_link: bool = False) -> Iterator[None]:
    """Capture a device+host profile into ``log_dir``.

    View with TensorBoard's profile plugin, or pass
    ``create_perfetto_link=True`` for a perfetto URL (blocks at exit).
    """
    jax.profiler.start_trace(log_dir, create_perfetto_link=create_perfetto_link)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def step_marker(step: int) -> "jax.profiler.StepTraceAnnotation":
    """Mark one train step (enables per-step breakdowns in the viewer)."""
    return jax.profiler.StepTraceAnnotation("train", step_num=step)


@contextlib.contextmanager
def maybe_trace(log_dir: Optional[str]) -> Iterator[None]:
    """``trace`` when a directory is configured, no-op otherwise — lets
    trainers accept a ``--profile-dir`` flag unconditionally."""
    if log_dir:
        with trace(log_dir):
            yield
    else:
        yield
