"""Plain reference of the Qwen3-Next stack as the program builds it.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
the gated delta rule ONE TOKEN AT A TIME exactly as it is written below
(decay, ``S^T k``, rank-one write, ``S^T q``: no chunk, no WY form, no
one-read form), key/value heads repeated under their query heads, full
softmax attention, a scan over the held experts with a mask, no sort, no
kernel, no cache, no packing.  It reads the program's parameter tree by
its names and nothing else of the program.  There is no network here, so
the equations are written from the catalog's row (its ``config`` keys and
``described_as``) and from memory of the ``qwen3_next`` family's
``modeling_qwen3_next.py``; every remembered point is listed in
``configs/qwen3-next-80b-a3b.json`` under ``assumed``, and where the
program departs from the source that file says so under ``departures`` and
this file follows the program.

**The stack**: layer ``i`` (from 0) is full attention where ``(i + 1) %
full_attention_interval == 0``, else Gated DeltaNet; every layer ``x <- x +
Mixer(N(x)); x <- x + MoE(N(x))``; a final ``N``, then the untied head.
``N`` is RMSNorm in float32 with scale ``1 + w`` (``w`` is what is stored).

**Gated DeltaNet** (``G`` key heads of ``N``, ``H`` value heads of ``P``;
key head ``j`` serves value heads ``j H / G`` on)::

    [q | k | v | z] = u W_in                (d -> G N + G N + H P + H P)
    [b | a] = u W_ba                        (d -> H + H)
    [q | k | v]_t = silu(sum_{j<K} w_j [q | k | v]_{t-K+1+j})   (causal, depthwise, no bias)
    beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)   (a value head each)
    q, k <- x / sqrt(sum x^2 + 1e-6) a head;  q <- q N^-0.5
    S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;  o_t = S^T q_t   (S in R^{N x P})
    out = (RMSNorm_P(o_t) w_norm silu(z_t)) W_out               (the norm a head, THEN the gate)

**Gated attention**: ``[q | gate]`` a head ``= u W_q``, ``[k | v] = u
W_kv``; q and k RMSNorm'ed over each head's features (scale ``1 + w``);
the first ``rotary_dim`` features of q and k rotated (rotate-half inside
them), the rest untouched; scores ``/ sqrt(head_dim)``, causal, softmax;
``out = (o sigmoid(gate)) W_o``.

**MoE**: ``p = softmax(u W_r)`` over all ``n_routed``; top-k, weights
renormalised over the k picks; expert ``(silu(u W_g) (u W_u)) W_d``; plus
``sigmoid(u w_s) SharedSwiGLU(u)``.

**The share.**  :class:`Geometry` says which of the routed experts the
weights hold (``first_expert .. first_expert + held``, the banks' leading
axis).  The expert sum runs over those alone and the normalising sum over
all ``k`` picks; what the absent experts would have added is left out.
The shared expert and both mixers are computed where the token lives: in
full.  With ``held = n_routed`` this is the uncut layer.

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


def rotary_dim(cfg: Mapping) -> int:
    return int(round(float(cfg["partial_rotary_factor"]) * int(cfg["head_dim"])))


def program_argv(cfg: Mapping) -> List[str]:
    """The configuration's sizes as the program's own arguments.
    ``num_experts`` is what this chip holds; the router's width is the
    published count beside it.  The delta rule's sizes go in under the
    ``--ssm-*`` names (value heads, value head size, key head size, key
    heads: the meaning the Mamba mixer gives them)."""
    return [
        "--block-family", "qwen3_next",
        "--vocab-size", str(cfg["vocab_size"]),
        "--d-model", str(cfg["hidden_size"]),
        "--n-layers", str(cfg["num_hidden_layers"]),
        "--full-attention-interval", str(cfg["full_attention_interval"]),
        "--n-heads", str(cfg["num_attention_heads"]),
        "--kv-heads", str(cfg["num_key_value_heads"]),
        "--head-dim", str(cfg["head_dim"]),
        "--rotary-dim", str(rotary_dim(cfg)),
        "--rope-theta", str(cfg["rope_theta"]),
        "--rms-norm-eps", str(cfg["rms_norm_eps"]),
        "--ssm-heads", str(cfg["linear_num_value_heads"]),
        "--ssm-head-dim", str(cfg["linear_value_head_dim"]),
        "--ssm-state", str(cfg["linear_key_head_dim"]),
        "--ssm-groups", str(cfg["linear_num_key_heads"]),
        "--ssm-conv", str(cfg["linear_conv_kernel_dim"]),
        "--ssm-chunk", str(cfg["gdn_chunk_size"]),
        "--moe-hidden", str(cfg["moe_intermediate_size"]),
        "--moe-experts", str(cfg["num_experts_published"]),
        "--moe-experts-held", str(cfg["num_experts"]),
        "--moe-first-expert", str(cfg["first_expert"]),
        "--moe-shared-experts", "1",
        "--moe-shared-width", str(cfg["shared_expert_intermediate_size"]),
        "--moe-experts-per-token", str(cfg["num_experts_per_tok"]),
        "--moe-norm-topk-prob", str(bool(cfg["norm_topk_prob"])).lower(),
    ]


class Geometry(NamedTuple):
    """What the forward needs beside the weights."""

    layers: int
    interval: int
    n_head: int
    kv_heads: int
    head_dim: int
    rotary_dim: int
    theta: float
    gdn_heads: int  # value heads
    gdn_head_dim: int  # value head size
    gdn_key_dim: int  # key head size
    gdn_key_heads: int
    n_routed: int  # experts the router scores (the published count)
    first_expert: int  # the share the banks hold ...
    held: int  # ... and how many of them
    top_k: int
    eps: float
    # None: the reference.  A dtype name ("float8_e4m3fn"): both operands
    # of every weight matmul are first rounded to it, which is how the
    # cell's check reads what a precision BELOW the configuration's would
    # cost (its bounds have to call that reading not correct)
    round_to: Optional[str] = None
    # a dtype name ("bfloat16"): the recurrent state is rounded to it after
    # every token, which is how the check reads what a state kept BELOW
    # the configuration's float32 would cost
    state_round_to: Optional[str] = None
    # a planted fault: the delta-rule layers run their recurrence through
    # pad positions too, as a prefill would that handed over the state at
    # its bucket's end and not at the prompt's true length; how the check
    # shows that its limit on the tokens after the handoff has teeth
    state_through_pads: bool = False

    def kind(self, i: int) -> str:
        return "attention" if (i + 1) % self.interval == 0 else "gdn"


def geometry(
    cfg: Mapping, round_to: Optional[str] = None, state_round_to: Optional[str] = None,
    state_through_pads: bool = False,
) -> Geometry:
    return Geometry(
        int(cfg["num_hidden_layers"]), int(cfg["full_attention_interval"]),
        int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]),
        int(cfg["head_dim"]), rotary_dim(cfg), float(cfg["rope_theta"]),
        int(cfg["linear_num_value_heads"]), int(cfg["linear_value_head_dim"]),
        int(cfg["linear_key_head_dim"]), int(cfg["linear_num_key_heads"]),
        int(cfg["num_experts_published"]), int(cfg["first_expert"]),
        int(cfg["num_experts"]), int(cfg["num_experts_per_tok"]),
        float(cfg["rms_norm_eps"]), round_to, state_round_to, state_through_pads,
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


def _rms_norm(x, w, eps):
    """``x / rms(x) (1 + w)``: the stored scale is zero-centred."""
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * (1.0 + _f32(w))


def _swiglu(p: Mapping, h, rt):
    return _mm(
        jax.nn.silu(_mm(h, p["gate"]["kernel"], rt)) * _mm(h, p["up"]["kernel"], rt),
        p["down"]["kernel"], rt,
    )


def router_choice(probs, top_k: int, renormalise: bool = True):
    """``(weights [.., E], gap [..])``: each output's combine weight (its
    probability over the sum of the picked ones, where it is among the
    ``top_k`` largest, else 0), and the distance from the last kept
    probability to the first one left out, as a share of the last kept."""
    ranked = jnp.sort(probs, axis=-1)
    kept, left_out = ranked[..., -top_k], ranked[..., -top_k - 1]
    picked = probs >= kept[..., None]
    weights = jnp.where(picked, probs, 0.0)
    if renormalise:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return weights, (kept - left_out) / kept


def delta_rule_token(S, q, k, v, g, beta):
    """One token of the rule, literally: ``S [.., N, P]``, ``q``/``k``
    ``[.., N]``, ``v [.., P]``, ``g``/``beta`` ``[..]``.  ``(o, S)``."""
    S = jnp.exp(g)[..., None, None] * S
    d = beta[..., None] * (v - jnp.einsum("...np,...n->...p", S, k))
    S = S + k[..., :, None] * d[..., None, :]
    return jnp.einsum("...np,...n->...p", S, q), S


def _gated_delta_net(p: Mapping, u, geo: Geometry, real=None):
    """The mixer on ``u [B, T, d]``, the recurrence one token at a time
    from a zero state and a zero convolution window.  Where ``real [B,
    T]`` is False (a pad position inside a row) the state and the window
    pass through unchanged and the output there means nothing."""
    B, T, _ = u.shape
    rt = geo.round_to
    H, P, N, G = geo.gdn_heads, geo.gdn_head_dim, geo.gdn_key_dim, geo.gdn_key_heads
    keys, values, per = G * N, H * P, H // G
    conv_w = _f32(p["conv_w"])  # [K, C]
    K, channels = conv_w.shape
    qkvz = _mm(u, p["in_proj"]["kernel"], rt)
    ba = _mm(u, p["ba_proj"]["kernel"], rt)
    qkv, z = qkvz[..., :channels], qkvz[..., channels:]
    beta = jax.nn.sigmoid(ba[..., :H])  # [B, T, H]
    g = -jnp.exp(_f32(p["A_log"])) * jax.nn.softplus(ba[..., H:] + _f32(p["dt_bias"]))

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)

    def token(carry, inputs):
        S0, window0 = carry  # [B, H, N, P], [B, K - 1, C]
        qkv_t, g_t, beta_t, real_t = inputs  # [B, C], [B, H], [B, H], [B]
        window = jnp.concatenate([window0, qkv_t[:, None]], axis=1)  # [B, K, C]
        act = jax.nn.silu(jnp.sum(window * conv_w, axis=1))
        q = jnp.repeat(l2(act[:, :keys].reshape(B, G, N)) * N ** -0.5, per, axis=1)
        k = jnp.repeat(l2(act[:, keys : 2 * keys].reshape(B, G, N)), per, axis=1)
        v = act[:, 2 * keys :].reshape(B, H, P)
        o, S = delta_rule_token(S0, q, k, v, g_t, beta_t)
        if geo.state_round_to is not None:
            # an explicit rounding: a cast there and back is one the TPU's
            # compiler may drop (it allows excess precision by default)
            kind = jnp.finfo(geo.state_round_to)
            S = jax.lax.reduce_precision(S, exponent_bits=kind.nexp, mantissa_bits=kind.nmant)
        S = jnp.where(real_t[:, None, None, None], S, S0)
        return (S, jnp.where(real_t[:, None, None], window[:, 1:], window0)), o

    if real is None or geo.state_through_pads:
        real = jnp.ones((B, T), bool)
    start = (jnp.zeros((B, H, N, P), jnp.float32), jnp.zeros((B, K - 1, channels), jnp.float32))
    time_major = lambda a: jnp.moveaxis(a, 1, 0)  # noqa: E731
    _, o = jax.lax.scan(token, start, tuple(map(time_major, (qkv, g, beta, real))))
    o = jnp.moveaxis(o, 0, 1)  # [B, T, H, P]
    normed = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + geo.eps)
    gated = normed * _f32(p["norm_scale"]) * jax.nn.silu(z).reshape(B, T, H, P)
    return _mm(gated.reshape(B, T, values), p["out_proj"]["kernel"], rt)


def _rotate(x, positions, width: int, theta: float):
    """The first ``width`` features of ``x [B, T, H, D]`` rotated to
    ``positions [B, T]`` (rotate-half inside them), the rest untouched."""
    half = width // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / width)
    angle = positions.astype(jnp.float32)[:, :, None, None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2, rest = x[..., :half], x[..., half:width], x[..., width:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _attention(p: Mapping, u, mask, positions, geo: Geometry):
    B, T, _ = u.shape
    H, KV, Dh, rt = geo.n_head, geo.kv_heads, geo.head_dim, geo.round_to
    qg = _mm(u, p["q"]["kernel"], rt).reshape(B, T, H, 2 * Dh)
    q, gate = qg[..., :Dh], qg[..., Dh:]
    kv = _mm(u, p["kv"]["kernel"], rt)
    k = kv[..., : KV * Dh].reshape(B, T, KV, Dh)
    v = kv[..., KV * Dh :].reshape(B, T, KV, Dh)
    q = _rms_norm(q, p["q_norm"]["scale"], geo.eps)
    k = _rms_norm(k, p["k_norm"]["scale"], geo.eps)
    q = _rotate(q, positions, geo.rotary_dim, geo.theta)
    k = _rotate(k, positions, geo.rotary_dim, geo.theta)
    k, v = jnp.repeat(k, H // KV, axis=2), jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(Dh))
    # finite, so that a row with no key to attend stays finite
    s = jnp.where(mask[:, None], s, -1e30)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", a, v) * jax.nn.sigmoid(gate)
    return _mm(o.reshape(B, T, H * Dh), p["proj"]["kernel"], rt)


def _experts(block: Mapping, u, geo: Geometry):
    """``(y, probs [B, T, R], weights [B, T, R], gap [B, T])``."""
    rt = geo.round_to
    bank = block["experts"]
    probs = jax.nn.softmax(_mm(u, bank["router"], rt), axis=-1)
    weights, gap = router_choice(probs, geo.top_k)
    held = weights[..., geo.first_expert : geo.first_expert + geo.held]

    def one_expert(y, expert):  # every token through every held expert, masked
        w_gate, w_up, w_down, weight = expert
        out = _mm(jax.nn.silu(_mm(u, w_gate, rt)) * _mm(u, w_up, rt), w_down, rt)
        return y + weight[..., None] * out, None

    y, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(u),
        (bank["w_gate"], bank["w_up"], bank["w_down"], jnp.moveaxis(held, -1, 0)),
    )
    if "shared" in block:  # always on, behind its sigmoid scalar
        y = y + jax.nn.sigmoid(_mm(u, block["shared_gate"]["kernel"], rt)) * _swiglu(block["shared"], u, rt)
    return y, probs, weights, gap


@partial(jax.jit, static_argnames=("kind", "geo"))
def layer(block: Mapping, x, mask, positions, kind: str, geo: Geometry, real=None):
    """One layer on ``x [B, T, d]`` (float32): ``(x, (probs, weights,
    gap))``.  ``kind`` is the mixer's (``gdn`` | ``attention``); ``mask
    [B, T, T]`` says which keys a query may attend and ``positions [B,
    T]`` where each token stands (attention alone reads them), ``real [B,
    T]`` which positions hold a token (the delta rule alone reads it)."""
    with jax.default_matmul_precision("highest"):
        u = _rms_norm(x, block["attn_norm"]["scale"], geo.eps)
        if kind == "gdn":
            x = x + _gated_delta_net(block["mixer"], u, geo, real)
        else:
            x = x + _attention(block, u, mask, positions, geo)
        u = _rms_norm(x, block["ffn_norm"]["scale"], geo.eps)
        y, probs, weights, gap = _experts(block, u, geo)
        return x + y, (probs, weights, gap)


@partial(jax.jit, static_argnames=("geo",))
def heads(p_norm, p_policy, p_value, x, geo: Geometry):
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, p_norm["scale"], geo.eps)
        logits = _mm(x, p_policy["kernel"], geo.round_to) + _f32(p_policy["bias"])
        values = (_mm(x, p_value["kernel"], geo.round_to) + _f32(p_value["bias"]))[..., 0]
    return logits, values


def trunk(params: Mapping, tokens, geo: Geometry, mask=None, real=None):
    """The layers alone: ``(x [B, T, d], routing)``, ``routing`` a list
    with one ``(probs, weights, gap)`` a layer.  Every row is one sequence
    from position 0 (the state starts at zero); causal unless ``mask``
    says otherwise.  With ``real [B, T]`` a row may hold pad positions
    anywhere: no key there is attended, the recurrence passes through
    them, and a real token's position is the count of real tokens before
    it, so the real positions read as if the pads were not there."""
    p = params["params"]
    tokens = jnp.asarray(tokens)
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    if mask is None:
        mask = jnp.broadcast_to(jnp.tril(jnp.ones((T, T), bool)), (B, T, T))
        if real is not None:
            real = jnp.asarray(real)
            mask = mask & real[:, None, :]
            positions = jnp.maximum(jnp.cumsum(real, axis=1) - 1, 0)
    x = _f32(jnp.asarray(p["token_embed"]["embedding"])[tokens])
    routing = []
    for i in range(geo.layers):
        x, routed = layer(p[f"block_{i}"], x, mask, positions, geo.kind(i), geo, real)
        routing.append(routed)
    return x, routing


def forward(params: Mapping, tokens, geo: Geometry, mask=None):
    """``(logits [B, T, V], values [B, T], routing)``."""
    p = params["params"]
    x, routing = trunk(params, tokens, geo, mask)
    logits, values = heads(p["final_norm"], p["policy_head"], p["value_head"], x, geo)
    return logits, values, routing


def token_logprobs(params: Mapping, tokens, geo: Geometry, real=None):
    """Log-probability the reference gives each token ``t >= 1`` of each
    row given the tokens before it, the value before it, and the router's
    gap at every (layer, token): ``(logp [B, T-1], values [B, T-1], gaps
    [layers, B, T])``.  The heads run a row at a time, so that no more
    than one row's ``[T, V]`` logits exist at once.  ``real``:
    :func:`trunk`'s."""
    p = params["params"]
    tokens = jnp.asarray(tokens)
    x, routing = trunk(params, tokens, geo, real=real)
    picked, values = [], []
    for b in range(tokens.shape[0]):
        logits, value = heads(p["final_norm"], p["policy_head"], p["value_head"], x[b : b + 1], geo)
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        picked.append(jnp.take_along_axis(logp, tokens[b : b + 1, 1:, None], axis=-1)[..., 0])
        values.append(value[:, :-1])
    return jnp.concatenate(picked), jnp.concatenate(values), jnp.stack([g for _s, _w, g in routing])


def balance(routing, real_tokens) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The load-balancing term and the largest output's load over the
    tokens ``real_tokens [B, T]`` names, all layers together: ``R x sum_e
    f_e P_e`` over the router's ``R`` outputs, with ``f_e`` the share of
    the ``k x tokens`` picks that went to output ``e`` (a constant: no
    gradient) and ``P_e`` its mean probability; and ``R x max_e f_e``."""
    m = jnp.asarray(real_tokens, jnp.float32)[..., None]
    picked = sum(jnp.sum((w > 0) * m, axis=(0, 1)) for _s, w, _g in routing)
    score = sum(jnp.sum(s * m, axis=(0, 1)) for s, _w, _g in routing)
    share = jax.lax.stop_gradient(picked / jnp.sum(picked))
    mean_score = score / (jnp.sum(m) * len(routing))
    R = share.shape[-1]
    return R * jnp.sum(share * mean_score), R * jnp.max(share)


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
