"""The generation engine's reference in the tests: greedy decoding by the
full forward (``model.apply`` on the whole left-padded sequence, one call a
token), which shares nothing with the engine's paged cache, admission or
macro-step program."""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from scalerl_tpu.models.transformer import (
    sequence_attention_mask,
    sequence_positions,
)


def left_padded(prompts, lengths, P, S):
    """``[B, S]`` rows with each prompt right-aligned in its first ``P``
    columns (the padded learner layout)."""
    seq = np.zeros((len(lengths), S), np.int32)
    for b, n in enumerate(np.asarray(lengths)):
        seq[b, P - n : P] = np.asarray(prompts[b])[:n]
    return seq


def full_forward(model, params, sequences, lengths, P):
    """The masked full forward over left-padded ``sequences``."""
    S = sequences.shape[1]
    lens = jnp.asarray(lengths, jnp.int32)
    return model.apply(
        params, jnp.asarray(sequences),
        positions=sequence_positions(lens, P, S),
        attn_mask=sequence_attention_mask(lens, P, S),
    )


def greedy_full_forward(model, params, prompts, lengths, P, R):
    """``R`` greedy tokens a prompt: the response tokens, their
    log-probabilities under the unscaled softmax, and the baseline read
    before each token, all ``[B, R]``."""
    lengths = np.asarray(lengths, np.int32)
    B, S = len(lengths), P + R
    seq = left_padded(prompts, lengths, P, S)
    forward = jax.jit(lambda s: full_forward(model, params, s, lengths, P))
    tokens = np.zeros((B, R), np.int32)
    logps = np.zeros((B, R), np.float32)
    values = np.zeros((B, R), np.float32)
    rows = np.arange(B)
    for t in range(R):
        out = forward(seq)
        logp = np.asarray(jax.nn.log_softmax(out.policy_logits[:, P - 1 + t], -1))
        tokens[:, t] = logp.argmax(-1)
        logps[:, t] = logp[rows, tokens[:, t]]
        values[:, t] = np.asarray(out.baseline[:, P - 1 + t])
        seq[:, P + t] = tokens[:, t]
    return SimpleNamespace(
        sequences=seq, response_tokens=tokens, behavior_logp=logps,
        values=values, response_len=np.full(B, R, np.int32),
    )
