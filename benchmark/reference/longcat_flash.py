"""Plain reference of the LongCat-Flash block as the program builds it.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
un-absorbed latent attention, a scan over the held experts with a mask, no
sort, no kernel, no cache, no packing.  It reads the program's parameter
tree by its names and nothing else of the program.  There is no network
here, so the equations below are written from the catalog's row (its
``config`` keys and ``described_as``) and from memory of the family's
``modeling_longcat_flash.py`` / DeepSeek-V2's MLA; every remembered point
is listed in ``configs/longcat-flash-chat.json`` under ``assumed``, and
where the program departs from the source that file says so under
``departures`` and this file follows the program.

**Latent attention (MLA)**, ``d`` the hidden size, ``H`` heads:
``c_q = RMSNorm(h W_qa)``; ``q = s_q (c_q W_qb)`` with ``s_q = sqrt(d /
q_lora_rank)`` (``mla_scale_q_lora``), a head ``[q_nope (128) | q_pe
(64)]``; ``[c | k_pe] = h W_kva`` (``kv_lora_rank | qk_rope_head_dim``);
``c = s_kv RMSNorm(c)`` with ``s_kv = sqrt(d / kv_lora_rank)``
(``mla_scale_kv_lora``); ``[k_nope | v]`` a head ``= c W_kvb`` (``128 |
128``); rotary embedding on ``q_pe`` and on the ONE ``k_pe`` every head
shares, pairs interleaved (feature ``2i`` with ``2i + 1``), ``inv_freq_i =
theta^(-2i/64)``; ``score = (q_nope . k_nope + q_pe . k_pe) / sqrt(192)``;
causal softmax; ``o = concat_heads(p v) W_o``.  No bias anywhere.

**The layer** (shortcut-connected MoE: a dense path beside the experts),
every ``N`` an RMSNorm with a scale of its own, ``FFN`` a SwiGLU ``(silu(h
Wg) * (h Wu)) Wd`` of width ``ffn_hidden_size``::

    x1 = x + MLA_0(N(x));   h = N(x1);   m = MoE(h)
    x2 = x1 + FFN_0(h)
    x3 = x2 + MLA_1(N(x2))
    out = x3 + FFN_1(N(x3)) + m

**The router**: ``p = softmax(h W_r)`` in float32 over all
``n_routed_experts + zero_expert_num`` outputs; the picks are the
``moe_topk`` largest of ``p + b`` (``b`` the router's choice bias, which
only chooses); a pick weighs ``routed_scaling_factor x p_i``, not
renormalised; ``m = sum_{picked i < n_routed} w_i SwiGLU_i(h) +
sum_{picked i >= n_routed} w_i h`` (the zero-compute experts are
identities).

**The share.**  :class:`Geometry` says which of the routed experts the
weights hold (``first_expert .. first_expert + held``, the banks' leading
axis).  The first sum runs over those alone; what the absent experts would
have added is left out, and the identity experts are counted in full: what
one expert-parallel rank computes for the tokens that live on it.  With
``held = n_routed`` this is the uncut layer.

Weights may arrive in a lower precision and on the host: every layer is
one jitted call that takes only its own block, and a matrix is raised to
float32 where it is multiplied, so the reference never holds more than a
layer beside its activations.
"""

from __future__ import annotations

from functools import partial
from typing import List, Mapping, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


def program_argv(cfg: Mapping) -> List[str]:
    """The configuration's sizes as the program's own arguments.
    ``n_routed_experts`` is what this chip holds; the router's width is
    the published count beside it."""
    return [
        "--block-family", "longcat",
        "--vocab-size", str(cfg["vocab_size"]),
        "--d-model", str(cfg["hidden_size"]),
        "--n-layers", str(cfg["num_layers"]),
        "--n-heads", str(cfg["num_attention_heads"]),
        "--rms-norm-eps", str(cfg["rms_norm_eps"]),
        "--rope-theta", str(cfg["rope_theta"]),
        "--mla-q-lora-rank", str(cfg["q_lora_rank"]),
        "--mla-kv-lora-rank", str(cfg["kv_lora_rank"]),
        "--mla-qk-nope-head-dim", str(cfg["qk_nope_head_dim"]),
        "--mla-qk-rope-head-dim", str(cfg["qk_rope_head_dim"]),
        "--mla-v-head-dim", str(cfg["v_head_dim"]),
        "--ffn-hidden", str(cfg["ffn_hidden_size"]),
        "--moe-hidden", str(cfg["expert_ffn_hidden_size"]),
        "--moe-experts", str(cfg["n_routed_experts_published"]),
        "--moe-experts-held", str(cfg["n_routed_experts"]),
        "--moe-first-expert", str(cfg["first_expert"]),
        "--moe-zero-experts", str(cfg["zero_expert_num"]),
        "--moe-experts-per-token", str(cfg["moe_topk"]),
        "--moe-routed-scaling", str(cfg["routed_scaling_factor"]),
        "--moe-norm-topk-prob", "false",
        "--router-aux-loss-coef", str(cfg["router_aux_loss_coef"]),
    ]


class Geometry(NamedTuple):
    """What the forward needs beside the weights."""

    n_head: int
    nope: int
    rope: int
    v_dim: int
    kv_rank: int
    n_routed: int  # computed experts the router scores (the published count)
    first_expert: int  # the share the banks hold ...
    held: int  # ... and how many of them
    top_k: int
    scaling: float
    eps: float
    theta: float
    # None: the reference.  A dtype name ("float8_e4m3fn"): both operands
    # of every weight matmul are first rounded to it, which is how the
    # cell's check reads what a precision BELOW the configuration's would
    # cost (its bounds have to call that reading not correct)
    round_to: Optional[str] = None


def geometry(cfg: Mapping, round_to: Optional[str] = None) -> Geometry:
    return Geometry(
        int(cfg["num_attention_heads"]), int(cfg["qk_nope_head_dim"]),
        int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"]), int(cfg["kv_lora_rank"]),
        int(cfg["n_routed_experts_published"]), int(cfg["first_expert"]),
        int(cfg["n_routed_experts"]), int(cfg["moe_topk"]),
        float(cfg["routed_scaling_factor"]), float(cfg["rms_norm_eps"]),
        float(cfg["rope_theta"]), round_to,
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
    """``x [B, T, H, D]`` at ``positions [B, T]``, pairs interleaved:
    features ``(2i, 2i + 1)`` turn by ``position x theta^(-2i/D)``."""
    D = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angle = positions.astype(jnp.float32)[:, :, None, None] * inv_freq  # [B, T, 1, D/2]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return turned.reshape(x.shape)


def router_choice(probs, bias, top_k: int, scaling: float):
    """``(weights [.., R], gap [..])``: each output's combine weight
    (``scaling`` x its probability where ``probs + bias`` is among the
    ``top_k`` largest, else 0), and the distance from the last kept score
    to the first one left out, as a share of the last kept."""
    score = probs + _f32(bias)
    ranked = jnp.sort(score, axis=-1)
    kept, left_out = ranked[..., -top_k], ranked[..., -top_k - 1]
    weights = jnp.where(score >= kept[..., None], scaling * probs, 0.0)
    return weights, (kept - left_out) / kept


def _attention(p: Mapping, h, positions, mask, d_model: int, geo: Geometry):
    B, T, _ = h.shape
    H, rt = geo.n_head, geo.round_to
    c_q = _rms_norm(_mm(h, p["q_a"]["kernel"], rt), p["q_a_norm"]["scale"], geo.eps)
    q_rank = c_q.shape[-1]
    q = (d_model / q_rank) ** 0.5 * _mm(c_q, p["q_b"]["kernel"], rt)
    q = q.reshape(B, T, H, geo.nope + geo.rope)
    kv = _mm(h, p["kv_a"]["kernel"], rt)
    c = (d_model / geo.kv_rank) ** 0.5 * _rms_norm(
        kv[..., : geo.kv_rank], p["kv_a_norm"]["scale"], geo.eps
    )
    kvb = _mm(c, p["kv_b"], rt).reshape(B, T, H, geo.nope + geo.v_dim)
    k_nope, v = kvb[..., : geo.nope], kvb[..., geo.nope :]
    q_pe = _rope(q[..., geo.nope :], positions, geo.theta)
    k_pe = _rope(kv[..., None, geo.kv_rank :], positions, geo.theta)  # [B, T, 1, rope]
    s = (
        jnp.einsum("bqhd,bkhd->bhqk", q[..., : geo.nope], k_nope)
        + jnp.einsum("bqhd,bkd->bhqk", q_pe, k_pe[:, :, 0])
    ) / jnp.sqrt(float(geo.nope + geo.rope))
    # finite, so that a row with no key to attend (a packed row's pad
    # tail) stays finite and cannot reach the rows that mask it out
    s = jnp.where(mask[:, None], s, -1e30)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(B, T, H * geo.v_dim)
    return _mm(o, p["proj"]["kernel"], rt)


def _ffn(p: Mapping, h, rt):
    gate = jax.nn.silu(_mm(h, p["gate"]["kernel"], rt)) * _mm(h, p["up"]["kernel"], rt)
    return _mm(gate, p["down"]["kernel"], rt)


def _moe(bank: Mapping, h, geo: Geometry):
    """``(m, probs [B, T, R], weights [B, T, R], gap [B, T])``."""
    rt = geo.round_to
    probs = jax.nn.softmax(_mm(h, bank["router"], rt), axis=-1)
    weights, gap = router_choice(probs, bank["router_bias"], geo.top_k, geo.scaling)
    held = weights[..., geo.first_expert : geo.first_expert + geo.held]

    def one_expert(y, expert):  # every token through every held expert, masked
        w_gate, w_up, w_down, weight = expert
        gate = jax.nn.silu(_mm(h, w_gate, rt)) * _mm(h, w_up, rt)
        return y + weight[..., None] * _mm(gate, w_down, rt), None

    y, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (bank["w_gate"], bank["w_up"], bank["w_down"], jnp.moveaxis(held, -1, 0)),
    )
    # the zero-compute experts are identities: their picks add w x h
    y = y + jnp.sum(weights[..., geo.n_routed :], axis=-1, keepdims=True) * h
    return y, probs, weights, gap


@partial(jax.jit, static_argnames=("geo",))
def layer(block: Mapping, x, positions, mask, geo: Geometry):
    """One double layer on ``x [B, T, d]`` (float32): ``(x, router_probs
    [B, T, R], weights [B, T, R], gap [B, T])``.  ``mask [B, T, T]`` says
    which keys a query may attend."""
    with jax.default_matmul_precision("highest"):
        d = x.shape[-1]
        rt = geo.round_to
        x = x + _attention(
            block["attn_0"], _rms_norm(x, block["attn_norm_0"]["scale"], geo.eps),
            positions, mask, d, geo,
        )
        h = _rms_norm(x, block["ffn_norm_0"]["scale"], geo.eps)
        m, probs, weights, gap = _moe(block["experts"], h, geo)
        x = x + _ffn(block["ffn_0"], h, rt)
        x = x + _attention(
            block["attn_1"], _rms_norm(x, block["attn_norm_1"]["scale"], geo.eps),
            positions, mask, d, geo,
        )
        x = x + _ffn(block["ffn_1"], _rms_norm(x, block["ffn_norm_1"]["scale"], geo.eps), rt) + m
        return x, probs, weights, gap


@partial(jax.jit, static_argnames=("geo",))
def heads(p_norm, p_policy, p_value, x, geo: Geometry):
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, p_norm["scale"], geo.eps)
        logits = _mm(x, p_policy["kernel"], geo.round_to) + _f32(p_policy["bias"])
        values = (_mm(x, p_value["kernel"], geo.round_to) + _f32(p_value["bias"]))[..., 0]
    return logits, values


def trunk(params: Mapping, tokens, geo: Geometry, positions=None, mask=None):
    """The layers alone: ``(x [B, T, d], routing)``, ``routing`` a list
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
    """The load-balancing term and the largest output's load over the
    tokens ``real_tokens [B, T]`` names, all layers together: ``R x sum_e
    f_e P_e`` over the router's ``R`` outputs, with ``f_e`` the share of
    the ``k x tokens`` picks that went to output ``e`` (a constant: no
    gradient) and ``P_e`` its mean probability; and ``R x max_e f_e``."""
    m = jnp.asarray(real_tokens, jnp.float32)[..., None]
    picked = sum(jnp.sum((w > 0) * m, axis=(0, 1)) for _p, w, _g in routing)
    prob = sum(jnp.sum(p * m, axis=(0, 1)) for p, _w, _g in routing)
    share = jax.lax.stop_gradient(picked / jnp.sum(picked))
    mean_prob = prob / (jnp.sum(m) * len(routing))
    R = share.shape[-1]
    return R * jnp.sum(share * mean_prob), R * jnp.max(share)


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
