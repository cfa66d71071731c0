"""Plain reference of the JoyAI-LLM-Flash stack as the program builds it.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
un-absorbed latent attention, a loop over the held experts with a mask, no
sort, no kernel, no cache, no packing.  It reads the program's parameter
tree by its names and nothing else of the program.  There is no network
here, so the equations below are written from the catalog's row (its
``config`` keys, which are the DeepSeek-V3 family's one for one) and from
memory of that family's published description; every remembered point is
listed in ``configs/joyai-llm-flash.json`` under ``assumed``, and where
the program departs from the source that file says so under
``departures`` and this file follows the program.

**Latent attention (MLA)** in a plain layer, ``d`` the hidden size, ``H``
heads: ``c_q = RMSNorm(h W_qa)`` (``q_lora_rank``); ``q = c_q W_qb``, a
head ``[q_nope (128) | q_pe (64)]``; ``[c | k_pe] = h W_kva``
(``kv_lora_rank | qk_rope_head_dim``); ``c = RMSNorm(c)``; ``[k_nope | v]``
a head ``= c W_kvb`` (``128 | 128``); rotary embedding on ``q_pe`` and on
the ONE ``k_pe`` every head shares, pairs interleaved (feature ``2i`` with
``2i + 1``, ``rope_interleave``), ``inv_freq_i = theta^(-2i/64)``, no
scaling; ``score = (q_nope . k_nope + q_pe . k_pe) / sqrt(192)``; causal
softmax in float32; ``o = concat_heads(p v) W_o``.  No bias and no
``mla_scale`` factor anywhere.

**The layer** (pre-norm; every ``N`` an RMSNorm with a scale of its own)::

    x1 = x + MLA(N(x));   out = x1 + F(N(x1))

``F`` is a SwiGLU ``(silu(h Wg) * (h Wu)) Wd`` of width
``intermediate_size`` in the ``first_k_dense_replace`` leading layers and
the mixture of experts in every later one.

**The mixture of experts**: ``s = sigmoid(h W_r)`` in float32 over all
``n_routed_experts`` outputs; the picks ``I`` are the ``num_experts_per_tok``
largest of ``s + b`` (``b`` the router's choice bias, which only chooses;
``n_group`` 1, so the grouped step is the identity); ``w_i =
routed_scaling_factor x s_i / (sum_{j in I} s_j + 1e-20)``
(``norm_topk_prob``); ``y = sum_{i in I} w_i SwiGLU_i(h) +
SwiGLU_shared(h)``, every expert of width ``moe_intermediate_size`` and
the shared one ``n_shared_experts`` times that.

**The share.**  :class:`Geometry` says which of the routed experts the
weights hold (``first_expert .. first_expert + held``, the banks' leading
axis).  The first sum runs over those alone while the normalising sum
stays over all the picks; what the absent experts would have added is
left out, and the shared expert is counted in full: what one
expert-parallel rank computes for the tokens that live on it.  With
``held = n_routed`` this is the uncut layer.

**The multi-token-prediction module** (one; the DeepSeek-V3 report,
section 2.2, as remembered): with ``x_i`` the trunk's last layer output at
position ``i`` (before the final norm) and ``t`` the tokens, ``h'_i = W_eh
[RMSNorm_h(x_i) ; RMSNorm_e(Emb(t_{i+1}))]`` (``2d -> d``), the embedding's
half zero where the sequence has no token ``i + 1``; ``h''_i =
Layer_mtp(h'_i)``, one routed layer as above with weights of its own, at
position ``i``'s rotary angle and under the same causal mask; ``logits_i =
Head(RMSNorm_mtp(h''_i))`` with the trunk's embedding and policy head;
it predicts ``t_{i+2}``.

Weights may arrive in a lower precision and on the host: every layer is
one jitted call that takes only its own block, and a matrix is raised to
float32 where it is multiplied, so the reference never holds more than a
layer beside its activations.
"""

from __future__ import annotations

from functools import partial
from typing import List, Mapping, NamedTuple, Optional

import jax
import jax.numpy as jnp


def program_argv(cfg: Mapping) -> List[str]:
    """The configuration's sizes as the program's own arguments.
    ``n_routed_experts`` is what this chip holds; the router's width is
    the published count beside it."""
    return [
        "--block-family", "joyai",
        "--vocab-size", str(cfg["vocab_size"]),
        "--d-model", str(cfg["hidden_size"]),
        "--n-layers", str(cfg["num_hidden_layers"]),
        "--dense-layers", str(cfg["first_k_dense_replace"]),
        "--n-heads", str(cfg["num_attention_heads"]),
        "--rms-norm-eps", str(cfg["rms_norm_eps"]),
        "--rope-theta", str(cfg["rope_theta"]),
        "--mla-q-lora-rank", str(cfg["q_lora_rank"]),
        "--mla-kv-lora-rank", str(cfg["kv_lora_rank"]),
        "--mla-qk-nope-head-dim", str(cfg["qk_nope_head_dim"]),
        "--mla-qk-rope-head-dim", str(cfg["qk_rope_head_dim"]),
        "--mla-v-head-dim", str(cfg["v_head_dim"]),
        "--ffn-hidden", str(cfg["intermediate_size"]),
        "--moe-hidden", str(cfg["moe_intermediate_size"]),
        "--moe-experts", str(cfg["n_routed_experts_published"]),
        "--moe-experts-held", str(cfg["n_routed_experts"]),
        "--moe-first-expert", str(cfg["first_expert"]),
        "--moe-shared-experts", str(cfg["n_shared_experts"]),
        "--moe-experts-per-token", str(cfg["num_experts_per_tok"]),
        "--moe-scoring", str(cfg["scoring_func"]),
        "--moe-routed-scaling", str(cfg["routed_scaling_factor"]),
        "--moe-norm-topk-prob", str(bool(cfg["norm_topk_prob"])).lower(),
        "--router-aux-loss-coef", str(cfg["router_aux_loss_coef"]),
        "--mtp-layers", str(cfg["num_nextn_predict_layers"]),
        "--mtp-loss-coef", str(cfg["mtp_loss_coef"]),
    ]


class Geometry(NamedTuple):
    """What the forward needs beside the weights."""

    n_head: int
    nope: int
    rope: int
    v_dim: int
    kv_rank: int
    n_routed: int  # experts the router scores (the published count)
    first_expert: int  # the share the banks hold ...
    held: int  # ... and how many of them
    top_k: int
    scaling: float
    eps: float
    theta: float
    # None: the reference.  A dtype name ("float8_e4m3fn"): both operands
    # of every weight matmul are first rounded to it, which is how a
    # cell's check reads what a precision BELOW the configuration's would
    # cost (its bounds have to call that reading not correct)
    round_to: Optional[str] = None


def geometry(cfg: Mapping, round_to: Optional[str] = None) -> Geometry:
    return Geometry(
        int(cfg["num_attention_heads"]), int(cfg["qk_nope_head_dim"]),
        int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"]), int(cfg["kv_lora_rank"]),
        int(cfg["n_routed_experts_published"]), int(cfg["first_expert"]),
        int(cfg["n_routed_experts"]), int(cfg["num_experts_per_tok"]),
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


def router_choice(scores, bias, top_k: int, scaling: float):
    """``(weights [.., E], gap [..])``: each output's combine weight
    (``scaling`` x its score over the sum of the picked scores, where
    ``scores + bias`` is among the ``top_k`` largest, else 0), and the
    distance from the last kept ``score + bias`` to the first one left
    out, as a share of the last kept."""
    choose = scores + _f32(bias)
    ranked = jnp.sort(choose, axis=-1)
    kept, left_out = ranked[..., -top_k], ranked[..., -top_k - 1]
    picked = choose >= kept[..., None]
    total = jnp.sum(jnp.where(picked, scores, 0.0), axis=-1, keepdims=True)
    weights = jnp.where(picked, scaling * scores / (total + 1e-20), 0.0)
    return weights, (kept - left_out) / kept


def _attention(p: Mapping, h, positions, mask, geo: Geometry):
    B, T, _ = h.shape
    H, rt = geo.n_head, geo.round_to
    c_q = _rms_norm(_mm(h, p["q_a"]["kernel"], rt), p["q_a_norm"]["scale"], geo.eps)
    q = _mm(c_q, p["q_b"]["kernel"], rt).reshape(B, T, H, geo.nope + geo.rope)
    kv = _mm(h, p["kv_a"]["kernel"], rt)
    c = _rms_norm(kv[..., : geo.kv_rank], p["kv_a_norm"]["scale"], geo.eps)
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
    """The routed experts alone (no shared one): ``(y, scores [B, T, E],
    weights [B, T, E], gap [B, T])``."""
    rt = geo.round_to
    scores = jax.nn.sigmoid(_mm(h, bank["router"], rt))
    weights, gap = router_choice(scores, bank["router_bias"], geo.top_k, geo.scaling)
    y = jnp.zeros_like(h)
    for e in range(geo.held):  # every token through every held expert, masked
        gate = jax.nn.silu(_mm(h, bank["w_gate"][e], rt)) * _mm(h, bank["w_up"][e], rt)
        y = y + weights[..., geo.first_expert + e, None] * _mm(gate, bank["w_down"][e], rt)
    return y, scores, weights, gap


@partial(jax.jit, static_argnames=("geo",))
def layer(block: Mapping, x, positions, mask, geo: Geometry):
    """One plain layer on ``x [B, T, d]`` (float32): ``(x, scores, weights,
    gap)``, the last three None in a dense layer.  Which kind it is the
    block's own names say.  ``mask [B, T, T]`` says which keys a query may
    attend."""
    with jax.default_matmul_precision("highest"):
        x = x + _attention(
            block["attn"], _rms_norm(x, block["attn_norm"]["scale"], geo.eps),
            positions, mask, geo,
        )
        h = _rms_norm(x, block["ffn_norm"]["scale"], geo.eps)
        if "experts" not in block:
            return x + _ffn(block["ffn"], h, geo.round_to), None, None, None
        y, scores, weights, gap = _moe(block["experts"], h, geo)
        if "shared" in block:
            y = y + _ffn(block["shared"], h, geo.round_to)
        return x + y, scores, weights, gap


@partial(jax.jit, static_argnames=("geo",))
def heads(p_norm, p_policy, p_value, x, geo: Geometry):
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, p_norm["scale"], geo.eps)
        logits = _mm(x, p_policy["kernel"], geo.round_to) + _f32(p_policy["bias"])
        values = (_mm(x, p_value["kernel"], geo.round_to) + _f32(p_value["bias"]))[..., 0]
    return logits, values


@partial(jax.jit, static_argnames=("geo",))
def _mtp_input(p: Mapping, x, next_emb, has_next, geo: Geometry):
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, p["h_norm"]["scale"], geo.eps)
        e = _rms_norm(_f32(next_emb), p["e_norm"]["scale"], geo.eps) * has_next[..., None]
        return _mm(jnp.concatenate([h, e], axis=-1), p["eh_proj"]["kernel"], geo.round_to)


def _causal(tokens, positions, mask):
    B, T = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    if mask is None:
        mask = jnp.broadcast_to(jnp.tril(jnp.ones((T, T), bool)), (B, T, T))
    return positions, mask


def trunk(params: Mapping, tokens, geo: Geometry, positions=None, mask=None):
    """The layers alone: ``(x [B, T, d], routing)``, ``routing`` a list
    with one ``(scores, weights, gap)`` a ROUTED layer.  Causal over
    positions ``0..T-1`` unless ``positions`` and ``mask`` say otherwise
    (packed rows)."""
    p = params["params"]
    tokens = jnp.asarray(tokens)
    positions, mask = _causal(tokens, positions, mask)
    x = _f32(jnp.asarray(p["token_embed"]["embedding"])[tokens])
    routing = []
    n_layer = sum(1 for name in p if name.startswith("block_"))
    for i in range(n_layer):
        x, scores, weights, gap = layer(p[f"block_{i}"], x, positions, mask, geo)
        if scores is not None:
            routing.append((scores, weights, gap))
    return x, routing


def forward(params: Mapping, tokens, geo: Geometry, positions=None, mask=None):
    """``(logits [B, T, V], values [B, T], routing)``: what generation
    computes; the multi-token-prediction module is not run."""
    p = params["params"]
    x, routing = trunk(params, tokens, geo, positions, mask)
    logits, values = heads(p["final_norm"], p["policy_head"], p["value_head"], x, geo)
    return logits, values, routing


def forward_mtp(
    params: Mapping, tokens, geo: Geometry, positions=None, mask=None, has_next=None
):
    """``(logits, values, mtp_logits [B, T, V], routing)``: the trunk and
    the multi-token-prediction module behind it, whose routing is the
    list's last entry.  ``has_next [B, T]`` says whether token ``i + 1``
    belongs to ``i``'s own sequence (default: all but the last)."""
    p = params["params"]
    tokens = jnp.asarray(tokens)
    positions, mask = _causal(tokens, positions, mask)
    x, routing = trunk(params, tokens, geo, positions, mask)
    logits, values = heads(p["final_norm"], p["policy_head"], p["value_head"], x, geo)
    if has_next is None:
        has_next = jnp.broadcast_to(jnp.arange(tokens.shape[1]) < tokens.shape[1] - 1, tokens.shape)
    next_emb = jnp.asarray(p["token_embed"]["embedding"])[jnp.roll(tokens, -1, axis=1)]
    y = _mtp_input(p["mtp"], x, next_emb, jnp.asarray(has_next, jnp.float32), geo)
    y, scores, weights, gap = layer(p["mtp"]["block"], y, positions, mask, geo)
    mtp_logits, _unused = heads(p["mtp_final_norm"], p["policy_head"], p["value_head"], y, geo)
    return logits, values, mtp_logits, routing + [(scores, weights, gap)]


def token_logprobs(params: Mapping, tokens, geo: Geometry):
    """Log-probability the reference gives each token ``t >= 1`` of each
    row given the tokens before it, the value before it, and the router's
    gap at every (routed layer, token): ``(logp [B, T-1], values [B, T-1],
    gaps [layers, B, T])``."""
    tokens = jnp.asarray(tokens)
    logits, values, routing = forward(params, tokens, geo)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return picked, values[:, :-1], jnp.stack([g for _s, _w, g in routing])


def picks(routing, real_tokens, geo: Geometry):
    """What the routers did with the tokens ``real_tokens [B, T]`` names,
    all routed layers of ``routing`` together: ``(held, absent,
    max_load)``, the picks of experts held here, the picks of the others,
    and the largest expert's share of all picks times the expert count."""
    m = jnp.asarray(real_tokens, jnp.float32)[..., None]
    picked = sum(jnp.sum((w > 0) * m, axis=(0, 1)) for _s, w, _g in routing)
    held = jnp.sum(picked[geo.first_expert : geo.first_expert + geo.held])
    share = picked / jnp.sum(picked)
    return held, jnp.sum(picked) - held, share.shape[-1] * jnp.max(share)
