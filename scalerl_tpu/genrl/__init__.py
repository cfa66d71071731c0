"""Token-level sequence-RL plane: generate -> score -> learn.

The scenario-diversity tier ROADMAP names after MindSpeed RL's distributed
dataflow (arxiv 2507.19017): autoregressive generation from the transformer
policy (paged KV cache, continuous batching, one jitted macro-step),
sequence packing into the prioritized sequence replay, and a token-level
PPO learner with per-token importance ratios against the stored behavior
logprobs.  ``genrl`` is a graftlint HOT package: the decode loop performs
exactly ONE batched host read per macro-step.

Exports resolve lazily (PEP 562): the engine pulls in jax at import time,
but the disaggregated-dataflow shells (``genrl/disagg.py``) are jax-free by
design and run in fleet children that must not pay the jax import — so the
package itself stays import-light and ``scalerl_tpu.genrl.disagg`` can be
imported without touching the device stack.
"""

from typing import Any

_EXPORTS = {
    "CompletedSequence": "scalerl_tpu.genrl.continuous",
    "ContinuousConfig": "scalerl_tpu.genrl.continuous",
    "ContinuousEngine": "scalerl_tpu.genrl.continuous",
    "PageAllocator": "scalerl_tpu.genrl.paging",
    "PrefixCache": "scalerl_tpu.genrl.prefix_cache",
    "pack_completions": "scalerl_tpu.genrl.rollout",
    "sequence_field_shapes": "scalerl_tpu.genrl.rollout",
    # pad-free packed learner layout (ISSUE 15)
    "PackedLearnerBatch": "scalerl_tpu.genrl.rollout",
    "greedy_pack": "scalerl_tpu.genrl.rollout",
    "pack_learner_batch": "scalerl_tpu.genrl.rollout",
    "packed_field_shapes": "scalerl_tpu.genrl.rollout",
    "packed_rows_from_completions": "scalerl_tpu.genrl.rollout",
    "TokenRecallTask": "scalerl_tpu.genrl.task",
    # the disaggregated dataflow (jax-free shells)
    "ContinuousEngineShell": "scalerl_tpu.genrl.disagg",
    "DisaggConfig": "scalerl_tpu.genrl.disagg",
    "GenerationHost": "scalerl_tpu.genrl.disagg",
    "GenerationTierExecutor": "scalerl_tpu.genrl.disagg",
    "LocalGenerationFleet": "scalerl_tpu.genrl.disagg",
    "SequenceLearner": "scalerl_tpu.genrl.disagg",
    "disagg_signal_source": "scalerl_tpu.genrl.disagg",
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
