"""Plain reference of one token-PPO learn step with a multi-token-prediction
term, on one sequence.

``reference/token_ppo.py`` (which stays as it is, and is handed in: this
file imports nothing of the benchmark) gives the clipped policy-gradient
loss with its value and entropy terms, and the optimiser's first step.
Composed here: the term a model with a multi-token-prediction module adds.
The module's output at position ``i`` predicts token ``i + 2``; ``L_mtp``
is the mean of ``-log p_i(t_{i+2})`` over the positions whose token ``i +
2`` exists and is a response token the loss mask counts (on one sequence
``i + 1`` and ``i + 2`` are always in ``i``'s own segment); the step's
loss is ``L_ppo + mtp_loss_coef x L_mtp`` and ``jax.grad`` of it is the
gradient the system's backward pass is held to: it reaches the trunk, the
embedding and the head through the module.
"""

from __future__ import annotations

from typing import Callable, Dict, Mapping

import jax
import jax.numpy as jnp


def mtp_term(mtp_logits, seq: Mapping):
    """``(L_mtp, top1_match)`` of ``mtp_logits [T, V]`` over ``seq``."""
    tokens, mask = seq["tokens"], seq["mask"][2:]
    logits = mtp_logits[:-2]
    n = jnp.maximum(jnp.sum(mask), 1.0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[2:, None], axis=-1)[:, 0]
    hit = (jnp.argmax(logits, axis=-1) == tokens[2:]).astype(jnp.float32)
    return jnp.sum(nll * mask) / n, jnp.sum(hit * mask) / n


def loss(plain, params, frozen, seq: Mapping, forward: Callable, hyper: Mapping):
    """``(total, parts)``: ``plain.loss`` plus ``hyper["mtp_loss_coef"]``
    times the term above.  ``forward(weights, tokens [1, T])`` gives
    ``(logits, values, mtp_logits)``, each with the leading 1; ``parts``
    gains ``mtp_loss`` and ``mtp_top1_match``."""
    kept = {}

    def trunk_only(w, tokens):
        logits, values, mtp_logits = forward(w, tokens)
        kept.setdefault("mtp", mtp_logits)  # the first call is the live weights'
        return logits, values

    total, parts = plain.loss(params, frozen, seq, trunk_only, hyper)
    term, top1 = mtp_term(kept["mtp"][0], seq)
    parts = dict(parts, mtp_loss=term, mtp_top1_match=top1)
    return total + hyper["mtp_loss_coef"] * term, parts


def follow(plain, before, after, seq: Mapping, forward: Callable, hyper: Mapping) -> Dict:
    """``plain.follow`` with the loss above: one learn step from the
    weights ``before`` on ``seq``, and the step the system took (to
    ``after``) beside it."""
    (total, parts), grads = jax.jit(
        lambda w, s: jax.value_and_grad(loss, argnums=1, has_aux=True)(
            plain, w, w, s, forward, hyper
        )
    )(before, seq)
    leaves = jax.tree_util.tree_leaves
    norm = float(sum(jax.jit(plain._dot)(g, g) for g in leaves(grads))) ** 0.5
    clip_scale = min(1.0, hyper["max_grad_norm"] / norm)

    @jax.jit
    def sums(g, b, a):
        moved, wanted = a - b, plain.first_update(g, clip_scale, hyper)
        return jnp.stack([
            plain._dot(g, moved), plain._dot(g, wanted),
            plain._dot(moved, moved), plain._dot(wanted, wanted),
        ])

    gm, gw, mm, ww = sum(
        jax.device_get(sums(g, b, a)).astype(float)
        for g, b, a in zip(leaves(grads), leaves(before), leaves(after))
    )
    return dict(
        jax.device_get(parts), total_loss=float(total), grad_norm=norm,
        update_gain=gm / gw, update_norm_ratio=(mm / ww) ** 0.5,
    )
