"""Sequence-RL training entry point: token-PPO on the generation engine.

The token-level generate -> score -> learn plane (docs/SEQUENCE_RL.md):
the continuous-batching engine decodes a persistent lane pool over a paged
KV cache one jitted macro-step at a time, the hermetic recall/copy verifier
scores the completed sequences on the host, and the token-PPO learner trains off the prioritized
sequence replay with per-token importance ratios.  The dp×mp mesh
resolves from the args alone, exactly like the other trainer families.

Usage (CPU smoke run)::

    python examples/train_sequence_rl.py --genrl-rounds 100 \
        --vocab-size 8 --prompt-len 4 --max-new-tokens 4

Sharded learner (8 virtual devices, dp=4 × mp=2)::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/train_sequence_rl.py --dp-size 4 --mp-size 2 \
        --d-model 256 --n-layers 4 --genrl-rounds 200

The engine's geometry (paged KV lane pool; docs/SEQUENCE_RL.md
"Continuous batching")::

    python examples/train_sequence_rl.py \
        --genrl-lanes 32 --genrl-page-size 8 --genrl-macro-steps 4

GRPO-shaped group sampling over the shared-prefix CoW cache (ISSUE 14,
docs/SEQUENCE_RL.md "Prefix caching & group sampling") — each round
samples genrl_batch / samples_per_prompt distinct prompts and decodes
samples_per_prompt completions per prompt, the group forking off ONE
prompt prefill; steps-in-flight pipelines admission under decode::

    python examples/train_sequence_rl.py \
        --genrl-lanes 32 --samples-per-prompt 8 \
        --genrl-steps-in-flight 2

Pad-free packed learner (ISSUE 15, docs/SEQUENCE_RL.md "Packed
learner") — completed sequences bin-pack into fixed rows with per-token
segment ids, the learn step runs segment-blocked causal attention (the
Pallas flash kernel on TPU), and no learn FLOP is spent on pad::

    python examples/train_sequence_rl.py --learner-packing \
        --genrl-lanes 32
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from scalerl_tpu.config import GenRLArguments, parse_args


def main(argv=None):
    """Train from ``argv`` (default ``sys.argv[1:]``); returns
    ``(trainer, final metrics)`` so a caller — ``chip_smoke.py`` — can check
    the run it just drove."""
    args = parse_args(GenRLArguments, argv)
    from scalerl_tpu.utils.platform import setup_platform

    print("backend:", setup_platform(args.platform))

    from scalerl_tpu.trainer.sequence_rl import SequenceRLTrainer

    trainer = SequenceRLTrainer(args)
    result = trainer.train(args.genrl_rounds)
    print("final:", {k: round(float(v), 4) for k, v in result.items()})
    if args.save_model and not args.disable_checkpoint:
        path = trainer.agent.save_checkpoint(
            os.path.join(args.work_dir, "genrl_ckpt_final")
        )
        print("checkpoint:", path)
    return trainer, result


if __name__ == "__main__":
    main()
