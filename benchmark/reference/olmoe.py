"""Plain reference of the OLMoE block as the program builds it.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
a scan over the experts with a mask, no sort, no kernel, no cache, no
packing.  It reads the program's parameter tree by its names and nothing
else of the program.  The equations are HF ``modeling_olmoe.py``'s, written
from memory (there is no network here); where the program departs from
them, ``configs/olmoe-1b-7b.json`` lists it under ``departures`` and this
file follows the program, because it is what the system's outputs are
held to.

A layer: ``h = RMSNorm(x)``; ``q, k, v = h Wq, h Wk, h Wv`` (one fused
``qkv`` matrix, no bias); ``q = RMSNorm_q(q)``, ``k = RMSNorm_k(k)`` over
the whole projection, each with its own scale, before the head split;
rotary embedding on the whole head (rotate-half, ``inv_freq_i =
theta^(-2i/D)``); causal softmax attention scaled by ``1/sqrt(D)``;
``x = x + o Wo``; ``h = RMSNorm(x)``; router probabilities ``softmax(h
Wr)`` over all experts, the ``k`` largest kept as they are
(``norm_topk_prob`` false); ``y = sum_j p_j (silu(h Wg_j) * (h Wu_j))
Wd_j``; ``x = x + y``.  Then the final RMSNorm and the program's two
heads.

Weights may arrive in a lower precision and on the host: every layer is
one jitted call that takes only its own block, and an expert's matrices
are raised to float32 one expert at a time, so the reference never holds
more than a layer beside its activations.
"""

from __future__ import annotations

from functools import partial
from typing import List, Mapping, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


def program_argv(cfg: Mapping) -> List[str]:
    """The configuration's sizes as the program's own arguments."""
    return [
        "--block-family", "olmoe",
        "--vocab-size", str(cfg["vocab_size"]),
        "--d-model", str(cfg["hidden_size"]),
        "--n-layers", str(cfg["num_hidden_layers"]),
        "--n-heads", str(cfg["num_attention_heads"]),
        "--head-dim", str(cfg["hidden_size"] // cfg["num_attention_heads"]),
        "--rms-norm-eps", str(cfg["rms_norm_eps"]),
        "--rope-theta", str(cfg["rope_theta"]),
        "--moe-experts", str(cfg["num_experts"]),
        "--moe-experts-per-token", str(cfg["num_experts_per_tok"]),
        "--moe-hidden", str(cfg["intermediate_size"]),
        "--moe-norm-topk-prob", str(bool(cfg["norm_topk_prob"])).lower(),
        "--router-aux-loss-coef", str(cfg["router_aux_loss_coef"]),
    ]


class Geometry(NamedTuple):
    """What the forward needs beside the weights."""

    n_head: int
    top_k: int
    eps: float
    theta: float
    norm_topk_prob: bool
    # None: the reference.  A dtype name ("float8_e4m3fn"): both operands
    # of every weight matmul are first rounded to it, which is how the
    # cell's check reads what a precision BELOW the configuration's would
    # cost (its bounds have to call that reading not correct)
    round_to: Optional[str] = None


def geometry(cfg: Mapping, round_to: Optional[str] = None) -> Geometry:
    return Geometry(
        int(cfg["num_attention_heads"]), int(cfg["num_experts_per_tok"]),
        float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]),
        bool(cfg["norm_topk_prob"]), round_to,
    )


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _mm(a, b, round_to: Optional[str]):
    """``a @ b`` in float32; under ``round_to`` both are rounded first."""
    a, b = _f32(a), _f32(b)
    if round_to is not None:
        a = a.astype(round_to).astype(jnp.float32)
        b = b.astype(round_to).astype(jnp.float32)
    return a @ b


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * _f32(scale)


def _rope(x, positions, theta):
    """``x [B, T, H, D]`` at ``positions [B, T]``, rotate-half."""
    D = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angle = positions.astype(jnp.float32)[:, :, None, None] * inv_freq  # [B, T, 1, D/2]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)
    rotated = jnp.concatenate([-x[..., D // 2 :], x[..., : D // 2]], axis=-1)
    return x * cos + rotated * sin


def router_choice(probs, top_k: int, norm_topk_prob: bool):
    """``(weights [.., E], gap [..])``: each expert's combine weight (its
    probability where it is among the ``top_k`` largest, else 0), and the
    distance from the last kept probability to the first one left out, as
    a share of the last kept."""
    ranked = jnp.sort(probs, axis=-1)
    kept, left_out = ranked[..., -top_k], ranked[..., -top_k - 1]
    weights = jnp.where(probs >= kept[..., None], probs, 0.0)
    if norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, (kept - left_out) / kept


@partial(jax.jit, static_argnames=("geo",))
def layer(block: Mapping, x, positions, mask, geo: Geometry):
    """One block on ``x [B, T, d]`` (float32): ``(x, router_probs [B, T, E],
    weights [B, T, E], gap [B, T])``.  ``mask [B, T, T]`` says which keys a
    query may attend."""
    with jax.default_matmul_precision("highest"):
        B, T, _ = x.shape
        h = _rms_norm(x, block["attn_norm"]["scale"], geo.eps)
        rt = geo.round_to
        q, k, v = jnp.split(_mm(h, block["qkv"]["kernel"], rt), 3, axis=-1)
        q = _rms_norm(q, block["q_norm"]["scale"], geo.eps)
        k = _rms_norm(k, block["k_norm"]["scale"], geo.eps)
        hd = q.shape[-1] // geo.n_head
        q, k, v = (a.reshape(B, T, geo.n_head, hd) for a in (q, k, v))
        q, k = _rope(q, positions, geo.theta), _rope(k, positions, geo.theta)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(hd))
        # finite, so that a row with no key to attend (a packed row's pad
        # tail) stays finite and cannot reach the rows that mask it out
        s = jnp.where(mask[:, None], s, -1e30)
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(B, T, geo.n_head * hd)
        x = x + _mm(o, block["proj"]["kernel"], rt)
        h = _rms_norm(x, block["ffn_norm"]["scale"], geo.eps)
        bank = block["experts"]
        probs = jax.nn.softmax(_mm(h, bank["router"], rt), axis=-1)
        weights, gap = router_choice(probs, geo.top_k, geo.norm_topk_prob)

        def one_expert(y, expert):  # every token through every expert, masked
            w_gate, w_up, w_down, weight = expert
            gate = jax.nn.silu(_mm(h, w_gate, rt)) * _mm(h, w_up, rt)
            return y + weight[..., None] * _mm(gate, w_down, rt), None

        y, _ = jax.lax.scan(
            one_expert, jnp.zeros_like(x),
            (bank["w_gate"], bank["w_up"], bank["w_down"], jnp.moveaxis(weights, -1, 0)),
        )
        return x + y, probs, weights, gap


@partial(jax.jit, static_argnames=("geo",))
def heads(p_norm, p_policy, p_value, x, geo: Geometry):
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, p_norm["scale"], geo.eps)
        logits = _mm(x, p_policy["kernel"], geo.round_to) + _f32(p_policy["bias"])
        values = (_mm(x, p_value["kernel"], geo.round_to) + _f32(p_value["bias"]))[..., 0]
    return logits, values


def trunk(params: Mapping, tokens, geo: Geometry, positions=None, mask=None):
    """The blocks alone: ``(x [B, T, d], routing)``, ``routing`` a list
    with one ``(router_probs, weights, gap)`` a layer.  Causal over
    positions ``0..T-1`` unless ``positions`` and ``mask`` say otherwise
    (packed rows)."""
    p = params["params"]
    tokens = jnp.asarray(tokens)
    B, T = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    if mask is None:
        mask = jnp.broadcast_to(jnp.tril(jnp.ones((T, T), bool)), (B, T, T))
    x = _f32(jnp.asarray(p["token_embed"]["embedding"])[tokens])
    routing = []
    n_layer = sum(1 for name in p if name.startswith("block_"))
    for i in range(n_layer):
        x, probs, weights, gap = layer(p[f"block_{i}"], x, positions, mask, geo)
        routing.append((probs, weights, gap))
    return x, routing


def forward(params: Mapping, tokens, geo: Geometry, positions=None, mask=None):
    """``(logits [B, T, V], values [B, T], routing)``."""
    p = params["params"]
    x, routing = trunk(params, tokens, geo, positions, mask)
    logits, values = heads(p["final_norm"], p["policy_head"], p["value_head"], x, geo)
    return logits, values, routing


def token_logprobs(params: Mapping, tokens, geo: Geometry):
    """Log-probability the reference gives each token ``t >= 1`` of each
    row given the tokens before it, the value before it, and the router's
    gap at every (layer, token): ``(logp [B, T-1], values [B, T-1], gaps
    [layers, B, T])``.  The heads run a row at a time, so that no more
    than one row's ``[T, V]`` logits exist at once."""
    p = params["params"]
    tokens = jnp.asarray(tokens)
    x, routing = trunk(params, tokens, geo)
    picked, values = [], []
    for b in range(tokens.shape[0]):
        logits, value = heads(p["final_norm"], p["policy_head"], p["value_head"], x[b : b + 1], geo)
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        picked.append(jnp.take_along_axis(logp, tokens[b : b + 1, 1:, None], axis=-1)[..., 0])
        values.append(value[:, :-1])
    return jnp.concatenate(picked), jnp.concatenate(values), jnp.stack([g for _p, _w, g in routing])


def balance(routing, real_tokens) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The load-balancing term and the largest expert's load over the
    tokens ``real_tokens [B, T]`` names, all layers together: ``E x sum_e
    f_e P_e`` with ``f_e`` the share of the ``k x tokens`` assignments
    that went to expert ``e`` (a constant: no gradient) and ``P_e`` the
    mean router probability of ``e``; and ``E x max_e f_e``."""
    m = jnp.asarray(real_tokens, jnp.float32)[..., None]
    picked = sum(jnp.sum((w > 0) * m, axis=(0, 1)) for _p, w, _g in routing)
    prob = sum(jnp.sum(p * m, axis=(0, 1)) for p, _w, _g in routing)
    share = jax.lax.stop_gradient(picked / jnp.sum(picked))
    mean_prob = prob / (jnp.sum(m) * len(routing))
    E = share.shape[-1]
    return E * jnp.sum(share * mean_prob), E * jnp.max(share)


def ppo_loss(token_ppo, params, frozen, seq: Mapping, geo: Geometry, hyper: Mapping):
    """``reference/token_ppo.py``'s loss over one sequence plus
    ``hyper["router_aux_loss_coef"]`` times the load-balancing term over
    all of the sequence's tokens: ``(total, parts)``; ``parts`` gains
    ``moe_aux_loss`` and ``moe_max_load``.  ``token_ppo`` is that module
    (handed in: this file imports nothing of the benchmark)."""
    kept = {}

    def fwd(w, tokens):
        logits, values, routing = forward(w, tokens, geo)
        kept.setdefault("routing", routing)  # the first call is the live weights'
        return logits, values

    total, parts = token_ppo.loss(params, frozen, seq, fwd, hyper)
    aux, max_load = balance(kept["routing"], jnp.ones((1, seq["tokens"].shape[0])))
    parts = dict(parts, moe_aux_loss=aux, moe_max_load=max_load)
    return total + hyper["router_aux_loss_coef"] * aux, parts
