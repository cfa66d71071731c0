"""Shared utilities: logging, metrics, schedulers, profiling, pytree ops.

Exports resolve lazily (PEP 562): ``profiling`` and ``tree`` import jax at
module level, but the jax-free planes (fleet shells, the chaos injector,
the disagg generation hosts, telemetry) import ``utils.logging`` and
friends from worker processes that must not pay the multi-second jax
import — the package itself therefore stays import-light.
"""

from typing import Any

_EXPORTS = {
    "get_logger": "scalerl_tpu.utils.logging",
    "EpisodeMetrics": "scalerl_tpu.utils.metrics",
    "calculate_mean": "scalerl_tpu.utils.metrics",
    "calculate_vectorized_scores": "scalerl_tpu.utils.metrics",
    "LinearDecayScheduler": "scalerl_tpu.utils.schedulers",
    "MultiStepScheduler": "scalerl_tpu.utils.schedulers",
    "PiecewiseScheduler": "scalerl_tpu.utils.schedulers",
    "maybe_trace": "scalerl_tpu.utils.profiling",
    "step_marker": "scalerl_tpu.utils.profiling",
    "trace": "scalerl_tpu.utils.profiling",
    "Timer": "scalerl_tpu.utils.timers",
    "Timings": "scalerl_tpu.utils.timers",
    "hard_target_update": "scalerl_tpu.utils.tree",
    "param_count": "scalerl_tpu.utils.tree",
    "soft_target_update": "scalerl_tpu.utils.tree",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name: str) -> Any:
    module_name = _EXPORTS.get(name)
    if module_name is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    return getattr(importlib.import_module(module_name), name)


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))
