"""Plain reference of the ZAYA1 stack as the program builds it.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
shifted arrays for the two convolutions' taps and for the value shift, a
loop over a head's grouped taps, key/value heads repeated under their query
heads, full softmax attention, a scan over the experts with a mask, no sort,
no kernel, no cache, no pages, no packing.  It reads the program's
parameter tree by its names and nothing else of the program.  There is no
network here, so the equations are written from the catalog's row (its
``config`` keys and ``described_as``) and from memory of the family's two
public descriptions (Zyphra's "Compressed Convolutional Attention", arXiv
2510.04476, and the ZAYA1 report, arXiv 2511.17127); every remembered point
is listed in ``configs/zaya1-8b.json`` under ``assumed``, and where the
program departs from the source that file says so under ``departures`` and
this file follows the program.

**The stack**: every layer is one kind (``layer_types`` ``hybrid``), ``N``
an RMSNorm in float32, ``s``, ``c`` learned ``[d]`` vectors::

    a     = CCA(N1(x));          x = (s1 x + c1) + (s2 a + c2)
    m, r' = MoE(N2(x), r);       x = (s3 x + c3) + (s4 m + c4);     r' goes to the next layer
    final N, then the untied head.

**CCA** (``H`` query heads over ``KV`` key/value heads of ``D``; ``h =
N1(x)``; anything before a sequence's first token is zero)::

    q~_t = h_t W_q;   k~_t = h_t W_k;   u_t = [q~_t | k~_t]             (H + KV heads of D)
    v_t  = [h_t W_v1 | h_{t-1} W_v2], cut into the KV heads in that order      the value shift
    c_t  = w0 u_{t-1} + w1 u_t + b                        depthwise; c_{-1} = b: the input is padded once
    d_t[g] = c_{t-1}[g] A_g + c_t[g] B_g + b'_g           grouped: a head's D channels, never two heads
    q_t[i] = d_t[i] + (q~_t[i] + k~_t[i // (H/KV)]) / 2                 the q-k mean, on the latents
    k_t[j] = d_t[H + j] + (mean_{i // (H/KV) = j} q~_t[i] + k~_t[j]) / 2
    q <- sqrt(D) q / sqrt(|q|^2 + 1e-6);   k <- tau_j sqrt(D) k / sqrt(|k|^2 + 1e-6)
    the first ``rotary_dim`` features of a head of q and k rotated (rotate-half inside them)
    o = softmax(q k^T / sqrt(D)) v  (query head i reads key/value head i // (H/KV), causal);  out = o W_o

**MoE with the ZAYA router** (``g = N2(x)``)::

    r'     = g W_dn + b_dn  (+ gamma r  where a layer stands before this one)
    z      = RMSNorm(r');   logits = gelu(gelu(z W_1 + b_1) W_2 + b_2) W_3        (exact gelu)
    p      = softmax(logits);   e = argmax(p + beta);   m = p_e (silu(g Wg_e) (g Wu_e)) Wd_e

Weights may arrive in a lower precision and on the host: every layer is
one jitted call that takes only its own block, and a matrix is raised to
float32 where it is multiplied, so the reference never holds more than a
layer beside its activations; the head runs a row at a time and in column
blocks, so that no ``[T, vocabulary]`` array exists at once.
"""

from __future__ import annotations

from functools import partial
from typing import List, Mapping, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

_HEAD_BLOCK = 65536  # most columns of the head scored at once


def rotary_dim(cfg: Mapping) -> int:
    return int(round(float(cfg["partial_rotary_factor"]) * int(cfg["head_dim"])))


def rope_theta(cfg: Mapping) -> float:
    """The ``hybrid`` layers' base: every layer of this checkpoint."""
    return float(cfg["rope_parameters"]["hybrid"]["rope_theta"])


def program_argv(cfg: Mapping) -> List[str]:
    """The configuration's sizes as the program's own arguments."""
    return [
        "--block-family", "zaya",
        "--vocab-size", str(cfg["vocab_size"]),
        "--d-model", str(cfg["hidden_size"]),
        "--n-layers", str(cfg["num_hidden_layers"]),
        "--n-heads", str(cfg["num_attention_heads"]),
        "--kv-heads", str(cfg["num_key_value_heads"]),
        "--head-dim", str(cfg["head_dim"]),
        "--rotary-dim", str(rotary_dim(cfg)),
        "--rope-theta", str(rope_theta(cfg)),
        "--rms-norm-eps", str(cfg["rms_norm_eps"]),
        "--cca-time0", str(cfg["cca_time0"]),
        "--cca-time1", str(cfg["cca_time1"]),
        "--router-hidden", str(cfg["router_hidden_size"]),
        "--moe-hidden", str(cfg["moe_intermediate_size"]),
        "--moe-experts", str(cfg["num_experts"]),
        "--moe-experts-per-token", str(cfg["num_experts_per_tok"]),
        "--router-aux-loss-coef", str(cfg.get("router_aux_loss_coef", 0.0)),
    ]


class Geometry(NamedTuple):
    """What the forward needs beside the weights."""

    layers: int
    n_head: int
    kv_heads: int
    head_dim: int
    rotary_dim: int
    theta: float
    top_k: int
    eps: float
    # None: the reference.  A dtype name ("float8_e4m3fn"): both operands
    # of every weight matmul are first rounded to it, which is how the
    # cell's check reads what a precision BELOW the configuration's would
    # cost (its bounds have to call that reading not correct)
    round_to: Optional[str] = None
    # a dtype name ("bfloat16"): what a token reads of the tokens before
    # it through the window (their ``u`` and ``h W_v2``) is rounded to it,
    # which is how the check reads what a window kept BELOW the
    # configuration's float32 would cost
    state_round_to: Optional[str] = None
    # a planted fault: the convolutions and the value shift read pad
    # positions as if they were tokens, as a prefill would that handed
    # over the window at its bucket's end and not at the prompt's true
    # length; how the check shows that its limit on the tokens after the
    # hand-off has teeth
    state_through_pads: bool = False


def geometry(
    cfg: Mapping, round_to: Optional[str] = None, state_round_to: Optional[str] = None,
    state_through_pads: bool = False,
) -> Geometry:
    if int(cfg["cca_time0"]) != 2 or int(cfg["cca_time1"]) != 2:
        raise ValueError("the reference writes both convolutions out at two taps")
    return Geometry(
        int(cfg["num_hidden_layers"]), int(cfg["num_attention_heads"]),
        int(cfg["num_key_value_heads"]), int(cfg["head_dim"]), rotary_dim(cfg),
        rope_theta(cfg), int(cfg["num_experts_per_tok"]), float(cfg["rms_norm_eps"]),
        round_to, state_round_to, state_through_pads,
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
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * _f32(w)


def _merge(scale, bias, x, y):
    """``(s x + c) + (s' y + c')``: rows 0 the stream's, rows 1 the sublayer's."""
    s, c = _f32(scale), _f32(bias)
    return (s[0] * x + c[0]) + (s[1] * y + c[1])


def router_choice(probs, top_k: int, renormalise: bool = False):
    """``(weights [.., E], gap [..])``: each output's combine weight (its
    probability where it is among the ``top_k`` largest, else 0), and the
    distance from the last kept probability to the first one left out, as
    a share of the last kept."""
    ranked = jnp.sort(probs, axis=-1)
    kept, left_out = ranked[..., -top_k], ranked[..., -top_k - 1]
    weights = jnp.where(probs >= kept[..., None], probs, 0.0)
    if renormalise:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return weights, (kept - left_out) / kept


def _before(a, real, fill=0.0):
    """``a [B, T, C]`` of the REAL token before each position (``real [B,
    T]``: pad positions are passed by), ``fill`` where there is none."""
    T = a.shape[1]
    at = jnp.where(real, jnp.arange(T)[None, :], -1)
    # the last real position strictly before t
    prev = jax.lax.cummax(jnp.pad(at, ((0, 0), (1, 0)), constant_values=-1)[:, :T], axis=1)
    taken = jnp.take_along_axis(a, jnp.maximum(prev, 0)[..., None], axis=1)
    return jnp.where((prev >= 0)[..., None], taken, fill)


def _round(x, dtype: Optional[str]):
    if dtype is None:
        return x
    # an explicit rounding: a cast there and back is one the TPU's
    # compiler may drop (it allows excess precision by default)
    kind = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=kind.nexp, mantissa_bits=kind.nmant)


def cca_parts(p: Mapping, h, positions, geo: Geometry, real=None):
    """``(q [B, T, H, D], k [B, T, KV, D], v [B, T, KV, D], row [B, T, .])``
    of the attention on ``h [B, T, d]``: normed, rotated q and k, the
    shifted values, and ``row = [u | h W_v2]``, what a window holds of a
    token.  Where ``real [B, T]`` is False (a pad position inside a row)
    the token before a position is the last REAL one."""
    B, T, _ = h.shape
    H, KV, D, rt = geo.n_head, geo.kv_heads, geo.head_dim, geo.round_to
    per = H // KV
    if real is None or geo.state_through_pads:
        real = jnp.ones((B, T), bool)
    q_lat, k_lat = _mm(h, p["q"]["kernel"], rt), _mm(h, p["k"]["kernel"], rt)
    u = jnp.concatenate([q_lat, k_lat], axis=-1)  # [B, T, (H + KV) D]
    v1, v2 = _mm(h, p["v1"]["kernel"], rt), _mm(h, p["v2"]["kernel"], rt)
    # what the tokens before this one left for it
    seen_u, seen_v2 = _round(u, geo.state_round_to), _round(v2, geo.state_round_to)
    u1 = _before(seen_u, real)
    u2 = _before(u1, real)
    v = jnp.concatenate([v1, _before(seen_v2, real)], axis=-1).reshape(B, T, KV, D)
    w, b = _f32(p["conv_w"]), _f32(p["conv_b"])  # [2, C], [C]
    c = w[0] * u1 + w[1] * u + b
    c_prev = w[0] * u2 + w[1] * u1 + b  # b alone before the first token
    mix, mix_b = _f32(p["mix_w"]), _f32(p["mix_b"])  # [2, G, D, D], [C]
    heads = []
    for g in range(H + KV):  # a head's channels, never two heads
        at = slice(g * D, (g + 1) * D)
        heads.append(c_prev[..., at] @ mix[0, g] + c[..., at] @ mix[1, g])
    d = jnp.concatenate(heads, axis=-1) + mix_b
    q_lat = q_lat.reshape(B, T, H, D)
    k_lat = k_lat.reshape(B, T, KV, D)
    q = d[..., : H * D].reshape(B, T, H, D) + (q_lat + jnp.repeat(k_lat, per, axis=2)) / 2
    k = d[..., H * D :].reshape(B, T, KV, D) + (
        jnp.mean(q_lat.reshape(B, T, KV, per, D), axis=3) + k_lat
    ) / 2

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6) * D ** 0.5

    q, k = unit(q), unit(k) * _f32(p["k_temp"])[:, None]
    q = _rotate(q, positions, geo.rotary_dim, geo.theta)
    k = _rotate(k, positions, geo.rotary_dim, geo.theta)
    return q, k, v, jnp.concatenate([u, v2], axis=-1)


def _rotate(x, positions, width: int, theta: float):
    """The first ``width`` features of ``x [B, T, H, D]`` rotated to
    ``positions [B, T]`` (rotate-half inside them), the rest untouched."""
    half = width // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / width)
    angle = positions.astype(jnp.float32)[:, :, None, None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2, rest = x[..., :half], x[..., half:width], x[..., width:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _attention(p: Mapping, h, mask, positions, geo: Geometry, real=None):
    B, T, _ = h.shape
    H, KV, D = geo.n_head, geo.kv_heads, geo.head_dim
    q, k, v, _row = cca_parts(p, h, positions, geo, real)
    k, v = jnp.repeat(k, H // KV, axis=2), jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(D))
    # finite, so that a row with no key to attend stays finite
    s = jnp.where(mask[:, None], s, -1e30)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return _mm(o.reshape(B, T, H * D), p["proj"]["kernel"], geo.round_to)


def router(p: Mapping, g, r, geo: Geometry):
    """``(logits [B, T, E], r')``: the state handed on is the one BEFORE
    its norm.  ``r`` None: no layer stands before this one."""
    rt = geo.round_to
    state = _mm(g, p["reduce"]["kernel"], rt) + _f32(p["reduce"]["bias"])
    if r is not None:
        state = state + _f32(p["carry_scale"]) * r
    z = _rms_norm(state, p["norm_scale"], geo.eps)
    z = jax.nn.gelu(_mm(z, p["fc1"]["kernel"], rt) + _f32(p["fc1"]["bias"]), approximate=False)
    z = jax.nn.gelu(_mm(z, p["fc2"]["kernel"], rt) + _f32(p["fc2"]["bias"]), approximate=False)
    return _mm(z, p["score"]["kernel"], rt), state


def _experts(block: Mapping, g, r, geo: Geometry):
    """``(m, r', probs [B, T, E], weights [B, T, E], gap [B, T])``."""
    rt = geo.round_to
    bank = block["experts"]
    logits, r = router(block["router"], g, r, geo)
    probs = jax.nn.softmax(logits, axis=-1)
    # the bias chooses and does not weigh
    chosen, gap = router_choice(probs + _f32(bank["router_bias"]), geo.top_k)
    weights = jnp.where(chosen > 0, probs, 0.0)

    def one_expert(y, expert):  # every token through every expert, masked
        w_gate, w_up, w_down, weight = expert
        out = _mm(jax.nn.silu(_mm(g, w_gate, rt)) * _mm(g, w_up, rt), w_down, rt)
        return y + weight[..., None] * out, None

    m, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(g),
        (bank["w_gate"], bank["w_up"], bank["w_down"], jnp.moveaxis(weights, -1, 0)),
    )
    return m, r, probs, weights, gap


@partial(jax.jit, static_argnames=("geo",))
def layer(block: Mapping, x, r, mask, positions, geo: Geometry, real=None):
    """One layer on ``x [B, T, d]`` (float32) and the router's stream ``r
    [B, T, width]`` (None into the first): ``(x, r', (probs, weights,
    gap))``.  ``mask [B, T, T]`` says which keys a query may attend,
    ``positions [B, T]`` where each token stands, ``real [B, T]`` which
    positions hold a token (the window alone reads it)."""
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, block["attn_norm"]["scale"], geo.eps)
        a = _attention(block["attn"], h, mask, positions, geo, real)
        x = _merge(block["attn_res_scale"], block["attn_res_bias"], x, a)
        g = _rms_norm(x, block["ffn_norm"]["scale"], geo.eps)
        m, r, probs, weights, gap = _experts(block, g, r, geo)
        x = _merge(block["ffn_res_scale"], block["ffn_res_bias"], x, m)
        return x, r, (probs, weights, gap)


@partial(jax.jit, static_argnames=("geo",))
def final_norm(p_norm, x, geo: Geometry):
    return _rms_norm(x, p_norm["scale"], geo.eps)


@partial(jax.jit, static_argnames=("geo",))
def _score(kernel, bias, x, geo: Geometry):
    with jax.default_matmul_precision("highest"):
        return _mm(x, kernel, geo.round_to) + _f32(bias)


def _head_blocks(vocab: int) -> int:
    """The fewest equal column blocks of at most ``_HEAD_BLOCK``."""
    return next(n for n in range(1, vocab + 1) if vocab % n == 0 and vocab // n <= _HEAD_BLOCK)


def heads(p_norm, p_policy, p_value, x, geo: Geometry):
    """``(logits [B, T, V], values [B, T])`` of trunk outputs ``x``: the
    whole vocabulary at once (tests, small sizes)."""
    x = final_norm(p_norm, x, geo)
    logits = _score(p_policy["kernel"], p_policy["bias"], x, geo)
    values = _score(p_value["kernel"], p_value["bias"], x, geo)[..., 0]
    return logits, values


def trunk(params: Mapping, tokens, geo: Geometry, mask=None, real=None, upto=None):
    """The layers alone: ``(x [B, T, d], routing)``, ``routing`` a list
    with one ``(probs, weights, gap)`` a layer.  Every row is one sequence
    from position 0; causal unless ``mask`` says otherwise.  With ``real
    [B, T]`` a row may hold pad positions anywhere: no key there is
    attended, the convolutions and the value shift pass them by, and a
    real token's position is the count of real tokens before it, so the
    real positions read as if the pads were not there.  ``upto``: stop
    after that many layers of the stack the weights hold."""
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
    routing, r = [], None
    for i in range(geo.layers if upto is None else upto):
        x, r, routed = layer(p[f"block_{i}"], x, r, mask, positions, geo, real)
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
    [layers, B, T])``.  The head runs a row at a time and in column
    blocks: a running log-sum-exp and the picked column's score, so that
    no ``[T, V]`` array exists.  ``real``: :func:`trunk`'s."""
    p = params["params"]
    tokens = jnp.asarray(tokens)
    x, routing = trunk(params, tokens, geo, real=real)
    kernel, bias = p["policy_head"]["kernel"], p["policy_head"]["bias"]
    vocab = kernel.shape[1]
    blocks = _head_blocks(vocab)
    width = vocab // blocks
    picked, values = [], []
    for b in range(tokens.shape[0]):
        xb = final_norm(p["final_norm"], x[b : b + 1, :-1], geo)
        nxt = tokens[b : b + 1, 1:]
        lse = jnp.full(nxt.shape, -jnp.inf, jnp.float32)
        score = jnp.zeros(nxt.shape, jnp.float32)
        for n in range(blocks):
            at = slice(n * width, (n + 1) * width)
            logits = _score(kernel[:, at], bias[at], xb, geo)
            lse = jnp.logaddexp(lse, jax.nn.logsumexp(logits, axis=-1))
            inside = (nxt >= at.start) & (nxt < at.stop)
            local = jnp.clip(nxt - at.start, 0, width - 1)
            score = score + jnp.where(
                inside, jnp.take_along_axis(logits, local[..., None], axis=-1)[..., 0], 0.0
            )
        picked.append(score - lse)
        values.append(_score(p["value_head"]["kernel"], p["value_head"]["bias"], xb, geo)[..., 0])
    return jnp.concatenate(picked), jnp.concatenate(values), jnp.stack([g for _s, _w, g in routing])


def balance(routing, real_tokens) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The load-balancing term and the largest output's load over the
    tokens ``real_tokens [B, T]`` names, all layers together: ``R x sum_e
    f_e P_e`` over the router's ``R`` outputs, with ``f_e`` the share of
    the ``k x tokens`` picks that went to output ``e`` (a constant: no
    gradient) and ``P_e`` its mean probability; and ``R x max_e f_e``.
    The published model trains without the term (``router_aux_loss_coef``
    0); the program's learner reports it all the same."""
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
