"""Plain reference of the GPT-2 block as the program builds it.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
no kernel, no cache, no packing, no batching tricks.  It reads the
program's parameter tree by its names and nothing else of the program.
Departures of the block from the published GPT-2 are listed in
``configs/gpt2-*.json``; this file follows the block, because it is what
the system's outputs are held to.
"""

from __future__ import annotations

from typing import List, Mapping, Tuple

import jax
import jax.numpy as jnp

_LN_EPS = 1e-6  # flax LayerNorm default, which the program uses


def program_argv(cfg: Mapping) -> List[str]:
    """The configuration's sizes as the program's own arguments."""
    return [
        "--vocab-size", str(cfg["vocab_size"]),
        "--d-model", str(cfg["n_embd"]),
        "--n-layers", str(cfg["n_layer"]),
        "--n-heads", str(cfg["n_head"]),
    ]


def _layer_norm(x, scale):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + _LN_EPS) * scale


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x**3)))


def forward(params: Mapping, tokens: jnp.ndarray, n_head: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Causal forward over ``tokens [B, T]`` at positions ``0..T-1``.
    Returns ``(logits [B, T, V], values [B, T])``."""
    p = params["params"]
    with jax.default_matmul_precision("highest"):
        B, T = tokens.shape
        x = p["token_embed"]["embedding"][tokens] + p["pos_embed"][:T][None]
        x = x.astype(jnp.float32)
        d = x.shape[-1]
        hd = d // n_head
        causal = jnp.tril(jnp.ones((T, T), bool))
        n_layer = sum(1 for k in p if k.startswith("block_"))
        for i in range(n_layer):
            b = p[f"block_{i}"]
            h = _layer_norm(x, b["LayerNorm_0"]["scale"])
            qkv = h @ b["qkv"]["kernel"]
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(B, T, n_head, hd)
            k = k.reshape(B, T, n_head, hd)
            v = v.reshape(B, T, n_head, hd)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(hd))
            s = jnp.where(causal[None, None], s, -jnp.inf)
            a = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(B, T, d)
            x = x + o @ b["proj"]["kernel"]
            h = _layer_norm(x, b["LayerNorm_1"]["scale"])
            h = _gelu_new(h @ b["mlp_in"]["kernel"] + b["mlp_in"]["bias"])
            x = x + h @ b["mlp_out"]["kernel"] + b["mlp_out"]["bias"]
        x = _layer_norm(x, p["final_norm"]["scale"])
        logits = x @ p["policy_head"]["kernel"] + p["policy_head"]["bias"]
        values = (x @ p["value_head"]["kernel"] + p["value_head"]["bias"])[..., 0]
    return logits, values


def token_logprobs(params: Mapping, tokens: jnp.ndarray, n_head: int):
    """Log-probability the reference gives each token ``t >= 1`` of each
    row given the tokens before it, and the value before it:
    ``(logp [B, T-1], values [B, T-1])``, aligned at token ``t``."""
    logits, values = forward(params, tokens, n_head)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return picked, values[:, :-1]
