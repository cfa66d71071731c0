"""Tiny token learners and packed batches for the mesh tests
(``test_parallel_compile_options.py``, ``test_sharded_learner.py``)."""

import jax
import jax.numpy as jnp
import numpy as np

from scalerl_tpu.agents.token_ppo import TokenPPOAgent
from scalerl_tpu.config import GenRLArguments, parse_args
from scalerl_tpu.genrl.rollout import packed_field_shapes
from scalerl_tpu.models.transformer import TransformerPolicy
from scalerl_tpu.trainer.sequence_rl import build_genrl_model

ROWS, S, VOCAB, WIDTH = 4, 32, 64, 128


def agent(width=WIDTH, seq=S, vocab=VOCAB, heads=4, allocate=True):
    """A 2-layer token learner, no kernel.  With ``allocate=False`` its
    train state is shapes alone (``jax.eval_shape`` around the constructor):
    enough to lower and compile, and nothing of a wide model is built."""
    args = GenRLArguments(
        vocab_size=vocab, d_model=width, n_layers=2, n_heads=heads, prompt_len=seq // 2,
        max_new_tokens=seq // 2, telemetry_interval_s=0.0, logger_backend="none",
    )
    model = TransformerPolicy(
        num_actions=vocab, vocab_size=vocab, d_model=width, num_heads=heads, num_layers=2,
        max_len=seq,
    )
    if allocate:
        return TokenPPOAgent(args, model)
    made = []
    shapes = jax.eval_shape(lambda: made.append(TokenPPOAgent(args, model)) or made[0].state)
    (agent,) = made
    agent.state = shapes
    return agent


def program_agent(*argv, seq=S, vocab=VOCAB):
    """A 2-layer token learner as the program's own arguments build it:
    ``--bf16-params true``, a routed block family."""
    args = parse_args(
        GenRLArguments,
        ["--vocab-size", str(vocab), "--d-model", "64", "--n-layers", "2", "--n-heads", "4",
         "--prompt-len", str(seq // 2), "--max-new-tokens", str(seq // 2),
         "--learner-packing", "true", "--learner-pack-len", str(seq),
         "--learner-packed-attn", "xla", "--logger-backend", "none", *argv],
    )
    args.validate()
    return TokenPPOAgent(args, build_genrl_model(args))


ROUTED = (
    "--block-family", "olmoe", "--head-dim", "16", "--moe-experts", "8",
    "--moe-experts-per-token", "3", "--moe-hidden", "32", "--router-aux-loss-coef", "0.01",
)


def packed_batch(seq=S, vocab=VOCAB, rows=ROWS):
    """Rows of two packed sequences each, a response at the end of both."""
    rng = np.random.default_rng(0)
    half = seq // 2
    seg = np.repeat(np.array([[1, 2]], np.int32), half, axis=1).repeat(rows, axis=0)
    pos = np.tile(np.arange(half, dtype=np.int32), (rows, 2))
    mask = (pos >= half // 2).astype(np.float32)
    batch = {
        "tokens": rng.integers(1, vocab, (rows, seq)).astype(np.int32),
        "segment_ids": seg,
        "positions": pos,
        "behavior_logp": np.log(rng.uniform(0.05, 0.5, (rows, seq))).astype(np.float32) * mask,
        "value": rng.normal(size=(rows, seq)).astype(np.float32) * mask,
        "mask": mask,
        "reward": rng.normal(size=(rows, seq)).astype(np.float32) * mask,
        "generation": np.zeros((rows, seq), np.int32),
    }
    assert set(batch) == set(packed_field_shapes(seq))
    return {k: jnp.asarray(v) for k, v in batch.items()}
