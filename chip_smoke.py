"""Chip smoke: the two hot paths, once, on the TPU, through ``examples/``.

    python chip_smoke.py

One process (a chip belongs to one process at a time), no fallback, nothing
caught: any exception in any phase is the exit code.  Off a TPU it fails
before doing anything else.  What it runs, at the widest shapes the repo
already runs on an accelerator, with random weights from a seed:

1. fused IMPALA — ``examples/train_impala.py`` on SyntheticPixel-v0
   (84x84x4 uint8, AtariNet 512, bf16 torso), 512 envs x 20 steps, three
   trainer calls, final checkpoint included;
2. sequence RL — ``examples/train_sequence_rl.py`` with the continuous
   engine, group sampling and the packed learner (vocab 1024, d_model 256,
   4 layers, 8 heads, 128-token prompts, 128 new tokens, 64 lanes), three
   rounds: paged decode attention, shared-prefix tail prefill,
   sequence-replay PER sampling, segment-flash forward and backward.

On a host with four chips it then runs both again over the mesh (IMPALA
``dp=4``, learner ``dp=2 x mp=2``) and prints where every buffer sits.

Each phase prints compile seconds, run seconds and frames or tokens done.
These are log lines, not metrics.  The last line of stdout is one JSON
object naming the device JAX reports.
"""

from __future__ import annotations

import importlib.metadata
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT))

# every user-set selector between duplicate kernel paths: the smoke must see
# what ``auto`` picks on this backend, so none of them may be set
_SELECTORS = (
    "SCALERL_PAGED_ATTN",
    "SCALERL_SEGMENT_ATTN",
    "SCALERL_PER_METHOD",
    "SCALERL_PER_UPDATE",
    "SCALERL_ITER_MODE",
)

_COMMON = ["--platform", "tpu", "--logger-backend", "none", "--seed", "0"]
IMPALA_ARGV = _COMMON + [
    "--env-backend", "jax", "--env-id", "SyntheticPixel-v0",
    "--hidden-size", "512", "--use-lstm", "false", "--compute-dtype", "bfloat16",
    "--num-envs", "512", "--rollout-length", "20",
    # one trainer call = 20 x 512 x 10 frames: three calls
    "--max-timesteps", "307200",
]
SEQRL_ARGV = _COMMON + [
    "--genrl-engine", "continuous", "--learner-packing",
    "--samples-per-prompt", "8", "--vocab-size", "1024",
    "--d-model", "256", "--n-layers", "4", "--n-heads", "8",
    "--prompt-len", "128", "--max-new-tokens", "128",
    "--genrl-lanes", "64", "--genrl-batch", "64",
    "--genrl-sample-batch", "64", "--genrl-buffer-sequences", "128",
    "--genrl-rounds", "3",
]


def _example(name: str):
    """Import ``examples/<name>.py`` (a script directory, not a package)."""
    spec = importlib.util.spec_from_file_location(
        name, ROOT / "examples" / f"{name}.py"
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class _CompileClock:
    """Seconds XLA spent compiling (or reading the persistent cache), and
    the cache's hit and miss counts, from JAX's own monitoring events."""

    def __init__(self) -> None:
        import jax.monitoring

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        # the backend compile alone: tracing and lowering events nest, so
        # their sum would count the same second twice
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.seconds, self.hits, self.misses


def _run_phase(clock: _CompileClock, name: str, fn, done_key: str, unit: str):
    """Run one phase, print its log line, return ``(trainer, metrics)``."""
    print(f"[{name}] start", flush=True)
    c0, h0, m0 = clock.snapshot()
    t0 = time.perf_counter()
    trainer, metrics = fn()
    wall = time.perf_counter() - t0
    c1, h1, m1 = clock.snapshot()
    print(
        f"[{name}] compile_s={c1 - c0:.1f} run_s={wall - (c1 - c0):.1f} "
        f"{unit}={metrics[done_key]:.0f} cache_hits={h1 - h0} "
        f"cache_misses={m1 - m0}",
        flush=True,
    )
    return trainer, metrics


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _check_impala(trainer, metrics, frames: int) -> None:
    _require(math.isfinite(metrics["total_loss"]), f"loss {metrics['total_loss']}")
    _require(metrics["env_frames"] == frames, f"frames {metrics['env_frames']}")
    for key in ("nonfinite_chunks", "skipped_steps", "nonfinite_grads"):
        _require(metrics.get(key, 0.0) == 0.0, f"{key}={metrics.get(key)}")
    ckpt = Path(trainer.model_save_dir) / "ckpt_final"
    _require(ckpt.exists(), f"no final checkpoint at {ckpt}")


def _check_seqrl(metrics, rounds: int) -> None:
    _require(math.isfinite(metrics["total_loss"]), f"loss {metrics['total_loss']}")
    _require(metrics["rounds"] == rounds, f"rounds {metrics['rounds']}")
    _require(metrics["decode_tokens"] > 0, "no decode tokens")
    for key in ("nonfinite_grads", "skipped_steps"):
        _require(metrics[key] == 0.0, f"{key}={metrics[key]}")


def _check_kernels(trainer) -> None:
    """Print what each resolver chose and hold the lowered programs to it:
    a Mosaic ``tpu_custom_call`` wherever the resolver said ``pallas`` — a
    kernel swapped for its reference, or interpreted, cannot pass."""
    from scalerl_tpu.ops.pallas_attention import resolve_segment_attn
    from scalerl_tpu.ops.pallas_paged_attention import resolve_paged_attn
    from scalerl_tpu.ops.pallas_per import (
        resolve_sample_method,
        resolve_update_method,
    )
    from scalerl_tpu.runtime.device_loop import resolve_iter_mode

    chosen = {
        "decode": resolve_paged_attn("auto"),
        "learn": resolve_segment_attn("auto"),
        "sample": resolve_sample_method("auto"),
    }
    print(
        "resolvers:", dict(
            paged_attn=chosen["decode"], segment_attn=chosen["learn"],
            per_sample=chosen["sample"],
            per_update=resolve_update_method("auto"),
            iter_mode=resolve_iter_mode("auto"),
        ),
        flush=True,
    )
    for name, lowered in trainer.lowered_programs().items():
        calls = lowered.as_text().count("tpu_custom_call")
        print(f"program {name}: resolver={chosen[name]} tpu_custom_call x{calls}")
        _require(
            (calls > 0) == (chosen[name] == "pallas"),
            f"{name}: resolver says {chosen[name]} but the lowered program "
            f"holds {calls} tpu_custom_call(s)",
        )


def _placement(label: str, tree) -> dict:
    """Bytes per device of every addressable shard under ``tree``, beside
    the tree's logical size (equal per-device bytes mean a replica)."""
    import jax

    per_device: dict = {}
    logical = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        logical += getattr(leaf, "nbytes", 0)
        for shard in getattr(leaf, "addressable_shards", ()):
            per_device[shard.device.id] = (
                per_device.get(shard.device.id, 0) + shard.data.nbytes
            )
    print(
        f"placement {label}: logical={logical} "
        f"per_device={dict(sorted(per_device.items()))}",
        flush=True,
    )
    return per_device


def _four_chip_phases(clock: _CompileClock) -> None:
    """The mesh commands: Anakin dp=4, and the dp=2 x mp=2 learner.  Buffers
    the mesh is meant to spread must have shards on every device; what the
    design keeps on one device (generation is single-chip) is printed."""
    import jax

    everywhere = {d.id for d in jax.devices()}

    # the one-chip lanes and frame budget on each of the four chips
    argv = IMPALA_ARGV + ["--mesh-shape", "dp=4"]
    for flag in ("--num-envs", "--max-timesteps"):
        argv[argv.index(flag) + 1] = str(4 * int(argv[argv.index(flag) + 1]))
    trainer, metrics = _run_phase(
        clock, "impala dp=4", lambda: _example("train_impala").main(argv),
        "env_frames", "frames",
    )
    _check_impala(
        trainer, metrics, int(argv[argv.index("--max-timesteps") + 1])
    )
    for label, tree in (
        ("impala params", trainer.agent.state.params),
        ("impala env lanes", trainer.carry),
    ):
        _require(set(_placement(label, tree)) == everywhere, f"{label} not on all devices")

    trainer, metrics = _run_phase(
        clock, "seqrl dp=2 mp=2",
        lambda: _example("train_sequence_rl").main(
            SEQRL_ARGV + ["--dp-size", "2", "--mp-size", "2"]
        ),
        "decode_tokens", "decode_tokens_last_round",
    )
    _check_seqrl(metrics, 3)
    _check_kernels(trainer)
    learner = _placement("learner state", trainer.agent.state)
    _require(set(learner) == everywhere, "learner state not on all devices")
    _placement("sequence replay", trainer.replay)
    _placement("engine params", trainer.engine._snapshot_params()[0])
    _placement("engine kv pools", trainer.engine._pools)


def main() -> None:
    from scalerl_tpu.utils.platform import setup_platform

    # no chip -> JAX's own "Unable to initialize backend 'tpu'" ends the run
    backend = setup_platform("tpu")
    if backend != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU backend, got {backend!r}")
    import jax

    clock = _CompileClock()
    devices = jax.devices()
    print(
        "JAX_PLATFORMS=%r platform=%s device_kind=%r count=%d" % (
            os.environ.get("JAX_PLATFORMS"), devices[0].platform,
            devices[0].device_kind, len(devices),
        )
    )
    print(
        "versions:",
        {p: importlib.metadata.version(p) for p in ("jax", "jaxlib", "libtpu")},
        "compilation cache:", jax.config.jax_compilation_cache_dir,
        flush=True,
    )
    set_selectors = [k for k in _SELECTORS if os.environ.get(k)]
    if set_selectors:
        raise SystemExit(f"unset {set_selectors}: the smoke runs what auto picks")
    # a stale shared object would pass the loader's mtime test: whatever
    # native code a later import loads is built from csrc/ as committed
    shutil.rmtree(ROOT / "scalerl_tpu" / "native" / "_build", ignore_errors=True)

    trainer, metrics = _run_phase(
        clock, "impala", lambda: _example("train_impala").main(IMPALA_ARGV),
        "env_frames", "frames",
    )
    _check_impala(
        trainer, metrics,
        int(IMPALA_ARGV[IMPALA_ARGV.index("--max-timesteps") + 1]),
    )

    trainer, metrics = _run_phase(
        clock, "seqrl", lambda: _example("train_sequence_rl").main(SEQRL_ARGV),
        "decode_tokens", "decode_tokens_last_round",
    )
    _check_seqrl(metrics, 3)
    _check_kernels(trainer)

    if len(devices) == 4:
        _four_chip_phases(clock)

    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": devices[0].platform,
                    "kind": devices[0].device_kind,
                    "count": len(devices),
                },
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
