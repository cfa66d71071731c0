"""Plain reference of one token-PPO learn step on one sequence.

The clipped policy-gradient loss with a value and an entropy term (and the
KL anchor to the frozen initial weights where it is on), as the learner's
documentation states it, over one sequence alone: no packing, no segment
mask, no kernel.  ``jax.grad`` of it is the gradient the system's backward
pass is held to; ``first_update`` is the optimiser's first step (global-norm
clip, then Adam from zero moments) written out.  The model's forward is
handed in, so this file knows no configuration.
"""

from __future__ import annotations

from typing import Callable, Dict, Mapping

import jax
import jax.numpy as jnp

_ADAM_EPS = 1e-8  # optax.adam's default, which the program takes


def loss(params, frozen, seq: Mapping, forward: Callable, hyper: Mapping):
    """``(total, parts)`` over ``seq``: ``tokens [T]`` and, aligned at each
    token's own offset, ``mask`` (1 on the response tokens that count),
    ``behavior_logp``, ``value`` and ``reward``.  The output at ``t - 1``
    predicts token ``t``.  ``parts`` also carries the forward's ``logits
    [T, V]`` and ``values [T]``."""
    tokens = seq["tokens"]
    logits, values = forward(params, tokens[None])
    logits, values = logits[0], values[0]
    logp_all = jax.nn.log_softmax(logits[:-1], axis=-1)
    new_logp = jnp.take_along_axis(logp_all, tokens[1:, None], axis=-1)[:, 0]
    mask = seq["mask"][1:]
    n = jnp.maximum(jnp.sum(mask), 1.0)

    def mean(x):
        return jnp.sum(x * mask) / n

    reward = seq["reward"][1:]
    adv = reward - seq["value"][1:]
    if hyper["adv_norm"]:
        mu = mean(adv)
        adv = (adv - mu) / jnp.sqrt(mean(jnp.square(adv - mu)) + 1e-8)
    adv = jax.lax.stop_gradient(adv * mask)
    ratio = jnp.exp(new_logp - seq["behavior_logp"][1:])
    clip = hyper["clip_range"]
    pg_loss = -mean(jnp.minimum(ratio * adv, jnp.clip(ratio, 1.0 - clip, 1.0 + clip) * adv))
    value_loss = hyper["value_cost"] * 0.5 * mean(jnp.square(values[:-1] - reward))
    neg_entropy = jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1)
    total = pg_loss + value_loss + hyper["entropy_cost"] * mean(neg_entropy)
    if hyper["kl_cost"] > 0.0:
        anchor, _ = forward(frozen, tokens[None])
        anchor = jax.lax.stop_gradient(jax.nn.log_softmax(anchor[0, :-1], axis=-1))
        kl = jnp.sum(jnp.exp(logp_all) * (logp_all - anchor), axis=-1)
        total = total + hyper["kl_cost"] * mean(kl)
    parts = {
        "pg_loss": pg_loss, "value_loss": value_loss, "entropy": -mean(neg_entropy),
        "logits": logits, "values": values,
    }
    return total, parts


def _dot(x, y):
    # along the last axis first: no float32 sum runs over more than a row
    return jnp.sum(jnp.sum(x * y, axis=-1))


def first_update(grad, clip_scale, hyper: Mapping):
    """The optimiser's first step on one leaf: the gradient scaled down to
    the global norm ``max_grad_norm`` (``clip_scale``), then Adam with zero
    moments, whose bias-corrected first step is ``-lr g / (|g| + eps)``."""
    g = grad * clip_scale
    return -hyper["learning_rate"] * g / (jnp.abs(g) + _ADAM_EPS)


def follow(before, after, seq: Mapping, forward: Callable, hyper: Mapping) -> Dict:
    """One learn step from the weights ``before`` on ``seq``, and the
    step the system took (to ``after``) beside it: the loss and its parts,
    the forward's outputs, the gradient's norm, and what the system's move
    is of the reference's own, in length and, through ``<grads, move>``,
    in the fall of the loss it buys to first order.  Runs where its
    arguments live; the sums over the tree go leaf by leaf."""
    (total, parts), grads = jax.jit(
        lambda w, s: jax.value_and_grad(loss, has_aux=True)(w, w, s, forward, hyper)
    )(before, seq)
    leaves = jax.tree_util.tree_leaves
    norm = float(sum(jax.jit(_dot)(g, g) for g in leaves(grads))) ** 0.5
    clip_scale = min(1.0, hyper["max_grad_norm"] / norm)

    @jax.jit
    def sums(g, b, a):
        moved, wanted = a - b, first_update(g, clip_scale, hyper)
        return jnp.stack(
            [_dot(g, moved), _dot(g, wanted), _dot(moved, moved), _dot(wanted, wanted)]
        )

    gm, gw, mm, ww = sum(
        jax.device_get(sums(g, b, a)).astype(float)
        for g, b, a in zip(leaves(grads), leaves(before), leaves(after))
    )
    return dict(
        jax.device_get(parts), total_loss=float(total), grad_norm=norm,
        update_gain=gm / gw, update_norm_ratio=(mm / ww) ** 0.5,
    )
