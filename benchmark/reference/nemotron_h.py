"""Plain reference of the Nemotron-H stack as the program builds it.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
the state-space recurrence ONE TOKEN AT A TIME (no chunk), key/value heads
repeated under their query heads, a scan over the held experts with a
mask, no sort, no kernel, no cache, no packing.  It reads the program's
parameter tree by its names and nothing else of the program.  There is no
network here, so the equations below are written from the catalog's row
(its ``config`` keys and ``described_as``) and from memory of the
``nemotron_h`` family's ``modeling_nemotron_h.py`` / Mamba-2; every
remembered point is listed in ``configs/nemotron-3-nano-30b-a3b.json``
under ``assumed``, and where the program departs from the source that file
says so under ``departures`` and this file follows the program.

**The stack**: ``hybrid_override_pattern`` gives one character a layer
(``M`` Mamba-2, ``E`` experts, ``*`` attention, ``-`` a dense FFN); every
layer is ``x <- x + Mixer(RMSNorm(x))``, ONE mixer, one norm, one residual
add; after the last layer ``RMSNorm_f``, then the untied head.  No bias
anywhere but the convolution's.

**``M``, the Mamba-2 mixer** (``H`` heads of ``P``, ``d_inner = H P``,
``G`` groups, state ``N``, head ``h`` reads group ``h // (H / G)``)::

    [z | xBC | dt] = u W_in                  (d -> d_inner + (d_inner + 2 G N) + H)
    xBC_t = silu(b + sum_{j<K} w_j xBC_{t-K+1+j})     (causal, depthwise, K taps)
    xBC = [x (d_inner) | B (G N) | C (G N)]
    dt = softplus(dt + dt_bias);   A = -exp(A_log)    (a head each)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        (S in R^{P x N}, a head)
    y_t = S_t C_t + D x_t
    y = RMSNorm_groups(y * silu(z))          (within each of the G groups of
                                              d_inner / G channels; one scale)
    out = y W_out                            (d_inner -> d)

``d_inner`` is heads x head size (the row's ``expand`` is read by
nothing); nothing is clamped at run time.

**``*``, attention**: ``q = u W_q`` (``heads x head_dim``), ``[k | v] = u
W_kv`` (``kv_heads x head_dim`` each); query head ``i`` attends key/value
head ``i // (heads / kv_heads)``; scores ``/ sqrt(head_dim)``, causal,
softmax in float32; ``out = concat(p v) W_o``.  **No rotary and no
position table**: the Mamba layers carry the order.

**``E``, the experts**: ``s = sigmoid(u W_r)`` over all ``n_routed``;
``I = top_k(s + b)`` (one group: the group step is the identity; ``b``
chooses and does not weigh); ``w_i = scaling x s_i / (sum_{j in I} s_j +
1e-20)``; ``y = sum_{i in I} w_i relu(u W_up,i)^2 W_down,i + relu(u
W_up,s)^2 W_down,s``: two matrices an expert, no gate, and a shared expert
of its own width that is always on.

**The share.**  :class:`Geometry` says which of the routed experts the
weights hold (``first_expert .. first_expert + held``, the banks' leading
axis).  The first sum runs over those alone and the normalising sum over
all ``k`` picks; what the absent experts would have added is left out.
The shared expert, the Mamba and the attention layers are computed where
the token lives: in full.  With ``held = n_routed`` this is the uncut layer.

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

_MIXERS = {"M": "mamba", "E": "experts", "*": "attention", "-": "ffn"}


def program_argv(cfg: Mapping) -> List[str]:
    """The configuration's sizes as the program's own arguments.
    ``n_routed_experts`` is what this chip holds; the router's width is
    the published count beside it."""
    return [
        "--block-family", "nemotron_h",
        "--vocab-size", str(cfg["vocab_size"]),
        "--d-model", str(cfg["hidden_size"]),
        "--n-layers", str(cfg["num_hidden_layers"]),
        "--layer-pattern", str(cfg["hybrid_override_pattern"]),
        "--n-heads", str(cfg["num_attention_heads"]),
        "--kv-heads", str(cfg["num_key_value_heads"]),
        "--head-dim", str(cfg["head_dim"]),
        "--rms-norm-eps", str(cfg["norm_eps"]),
        "--ssm-heads", str(cfg["mamba_num_heads"]),
        "--ssm-head-dim", str(cfg["mamba_head_dim"]),
        "--ssm-state", str(cfg["ssm_state_size"]),
        "--ssm-groups", str(cfg["n_groups"]),
        "--ssm-conv", str(cfg["conv_kernel"]),
        "--ssm-chunk", str(cfg["chunk_size"]),
        "--ffn-hidden", str(cfg["intermediate_size"]),
        "--moe-hidden", str(cfg["moe_intermediate_size"]),
        "--moe-experts", str(cfg["n_routed_experts_published"]),
        "--moe-experts-held", str(cfg["n_routed_experts"]),
        "--moe-first-expert", str(cfg["first_expert"]),
        "--moe-shared-experts", str(cfg["n_shared_experts"]),
        "--moe-shared-width", str(cfg["moe_shared_expert_intermediate_size"]),
        "--moe-experts-per-token", str(cfg["num_experts_per_tok"]),
        "--moe-scoring", str(cfg["scoring_func"]),
        "--moe-routed-scaling", str(cfg["routed_scaling_factor"]),
        "--moe-norm-topk-prob", str(bool(cfg["norm_topk_prob"])).lower(),
        "--moe-expert-act", str(cfg["mlp_hidden_act"]),
    ]


class Geometry(NamedTuple):
    """What the forward needs beside the weights."""

    pattern: str
    n_head: int
    kv_heads: int
    head_dim: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_state: int
    ssm_groups: int
    n_routed: int  # experts the router scores (the published count)
    first_expert: int  # the share the banks hold ...
    held: int  # ... and how many of them
    top_k: int
    scaling: float
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
    # a planted fault: the Mamba layers run their recurrence through pad
    # positions too, as a prefill would that handed over the state at its
    # bucket's end and not at the prompt's true length; how the check
    # shows that its limit on the tokens after the handoff has teeth
    state_through_pads: bool = False


def geometry(
    cfg: Mapping, round_to: Optional[str] = None, state_round_to: Optional[str] = None,
    state_through_pads: bool = False,
) -> Geometry:
    return Geometry(
        str(cfg["hybrid_override_pattern"]), int(cfg["num_attention_heads"]),
        int(cfg["num_key_value_heads"]), int(cfg["head_dim"]),
        int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"]),
        int(cfg["ssm_state_size"]), int(cfg["n_groups"]),
        int(cfg["n_routed_experts_published"]), int(cfg["first_expert"]),
        int(cfg["n_routed_experts"]), int(cfg["num_experts_per_tok"]),
        float(cfg["routed_scaling_factor"]), float(cfg["norm_eps"]),
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


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * _f32(scale)


def _relu2(p_up, p_down, h, rt):
    return _mm(jnp.square(jax.nn.relu(_mm(h, p_up, rt))), p_down, rt)


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


def _mamba(p: Mapping, u, geo: Geometry, real=None):
    """The mixer on ``u [B, T, d]``, the recurrence one token at a time
    from a zero state and a zero convolution window.  Where ``real [B,
    T]`` is False (a pad position inside a row) the state and the window
    pass through unchanged and the output there means nothing."""
    B, T, _ = u.shape
    rt = geo.round_to
    H, P, G, N = geo.ssm_heads, geo.ssm_head_dim, geo.ssm_groups, geo.ssm_state
    inner, per = H * P, H // G
    conv_w, conv_b = _f32(p["conv_w"]), _f32(p["conv_b"])  # [K, C], [C]
    K, channels = conv_w.shape
    zxbcdt = _mm(u, p["in_proj"]["kernel"], rt)
    z = zxbcdt[..., :inner]
    xbc = zxbcdt[..., inner : inner + channels]
    dt = jax.nn.softplus(zxbcdt[..., inner + channels :] + _f32(p["dt_bias"]))  # [B, T, H]
    A = -jnp.exp(_f32(p["A_log"]))
    D = _f32(p["D"])

    def token(carry, inputs):
        S0, window0 = carry  # [B, H, P, N], [B, K - 1, C]
        xbc_t, dt_t, real_t = inputs  # [B, C], [B, H], [B]
        S = S0
        window = jnp.concatenate([window0, xbc_t[:, None]], axis=1)  # [B, K, C]
        act = jax.nn.silu(conv_b + jnp.sum(window * conv_w, axis=1))
        x = act[:, :inner].reshape(B, H, P)
        Bm = jnp.repeat(act[:, inner : inner + G * N].reshape(B, G, N), per, axis=1)
        Cm = jnp.repeat(act[:, inner + G * N :].reshape(B, G, N), per, axis=1)
        decay = jnp.exp(dt_t * A)  # [B, H]
        S = (
            decay[:, :, None, None] * S
            + (dt_t[:, :, None] * x)[..., None] * Bm[:, :, None, :]
        )
        if geo.state_round_to is not None:
            # an explicit rounding: a cast there and back is one the TPU's
            # compiler may drop (it allows excess precision by default)
            kind = jnp.finfo(geo.state_round_to)
            S = jax.lax.reduce_precision(S, exponent_bits=kind.nexp, mantissa_bits=kind.nmant)
        y = jnp.sum(S * Cm[:, :, None, :], axis=-1) + D[None, :, None] * x
        S = jnp.where(real_t[:, None, None, None], S, S0)
        return (S, jnp.where(real_t[:, None, None], window[:, 1:], window0)), y

    if real is None or geo.state_through_pads:
        real = jnp.ones((B, T), bool)
    start = (jnp.zeros((B, H, P, N), jnp.float32), jnp.zeros((B, K - 1, channels), jnp.float32))
    _, y = jax.lax.scan(
        token, start, (jnp.moveaxis(xbc, 1, 0), jnp.moveaxis(dt, 1, 0), jnp.moveaxis(real, 1, 0))
    )
    y = jnp.moveaxis(y, 0, 1).reshape(B, T, inner)
    gated = (y * jax.nn.silu(z)).reshape(B, T, G, inner // G)
    normed = gated * jax.lax.rsqrt(jnp.mean(jnp.square(gated), axis=-1, keepdims=True) + geo.eps)
    return _mm(normed.reshape(B, T, inner) * _f32(p["norm_scale"]), p["out_proj"]["kernel"], rt)


def _attention(p: Mapping, u, mask, geo: Geometry):
    B, T, _ = u.shape
    H, KV, Dh, rt = geo.n_head, geo.kv_heads, geo.head_dim, geo.round_to
    q = _mm(u, p["q"]["kernel"], rt).reshape(B, T, H, Dh)
    kv = _mm(u, p["kv"]["kernel"], rt)
    k = kv[..., : KV * Dh].reshape(B, T, KV, Dh)
    v = kv[..., KV * Dh :].reshape(B, T, KV, Dh)
    k, v = jnp.repeat(k, H // KV, axis=2), jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(Dh))
    # finite, so that a row with no key to attend stays finite
    s = jnp.where(mask[:, None], s, -1e30)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(B, T, H * Dh)
    return _mm(o, p["proj"]["kernel"], rt)


def _experts(block: Mapping, u, geo: Geometry):
    """``(y, scores [B, T, R], weights [B, T, R], gap [B, T])``."""
    rt = geo.round_to
    bank = block["experts"]
    scores = jax.nn.sigmoid(_mm(u, bank["router"], rt))
    weights, gap = router_choice(scores, bank["router_bias"], geo.top_k, geo.scaling)
    held = weights[..., geo.first_expert : geo.first_expert + geo.held]

    def one_expert(y, expert):  # every token through every held expert, masked
        w_up, w_down, weight = expert
        return y + weight[..., None] * _relu2(w_up, w_down, u, rt), None

    y, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(u),
        (bank["w_up"], bank["w_down"], jnp.moveaxis(held, -1, 0)),
    )
    if "shared" in block:  # always on, computed where the token lives
        y = y + _relu2(block["shared"]["up"]["kernel"], block["shared"]["down"]["kernel"], u, rt)
    return y, scores, weights, gap


@partial(jax.jit, static_argnames=("kind", "geo"))
def layer(block: Mapping, x, mask, kind: str, geo: Geometry, real=None):
    """One single-mixer layer of ``kind`` on ``x [B, T, d]`` (float32):
    ``(x, routing)``, ``routing`` ``(scores, weights, gap)`` of an expert
    layer and None of the others.  ``mask [B, T, T]`` says which keys a
    query may attend (attention layers alone read it), ``real [B, T]``
    which positions hold a token (Mamba layers alone read it)."""
    with jax.default_matmul_precision("highest"):
        u = _rms_norm(x, block["norm"]["scale"], geo.eps)
        routing = None
        if kind == "mamba":
            out = _mamba(block["mixer"], u, geo, real)
        elif kind == "attention":
            out = _attention(block, u, mask, geo)
        elif kind == "experts":
            out, scores, weights, gap = _experts(block, u, geo)
            routing = (scores, weights, gap)
        else:
            out = _relu2(block["ffn"]["up"]["kernel"], block["ffn"]["down"]["kernel"], u, geo.round_to)
        return x + out, routing


@partial(jax.jit, static_argnames=("geo",))
def heads(p_norm, p_policy, p_value, x, geo: Geometry):
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, p_norm["scale"], geo.eps)
        logits = _mm(x, p_policy["kernel"], geo.round_to) + _f32(p_policy["bias"])
        values = (_mm(x, p_value["kernel"], geo.round_to) + _f32(p_value["bias"]))[..., 0]
    return logits, values


def trunk(params: Mapping, tokens, geo: Geometry, mask=None, real=None):
    """The layers alone: ``(x [B, T, d], routing)``, ``routing`` a list
    with one ``(scores, weights, gap)`` an EXPERT layer, in layer order.
    Every row is one sequence from position 0 (the state starts at zero);
    causal unless ``mask`` says otherwise.  With ``real [B, T]`` a row may
    hold pad positions anywhere: no key there is attended and the
    recurrence passes through them (there is no position to shift: the
    stack has none), so the real positions read as if the pads were not
    there."""
    p = params["params"]
    tokens = jnp.asarray(tokens)
    B, T = tokens.shape
    if mask is None:
        mask = jnp.broadcast_to(jnp.tril(jnp.ones((T, T), bool)), (B, T, T))
        if real is not None:
            mask = mask & jnp.asarray(real)[:, None, :]
    x = _f32(jnp.asarray(p["token_embed"]["embedding"])[tokens])
    routing = []
    for i, ch in enumerate(geo.pattern):
        x, routed = layer(p[f"block_{i}"], x, mask, _MIXERS[ch], geo, real)
        if routed is not None:
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
    gap at every (expert layer, token): ``(logp [B, T-1], values [B, T-1],
    gaps [expert layers, B, T])``.  The heads run a row at a time, so that
    no more than one row's ``[T, V]`` logits exist at once.  ``real``:
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
    tokens ``real_tokens [B, T]`` names, all expert layers together: ``R x
    sum_e f_e P_e`` over the router's ``R`` outputs, with ``f_e`` the share
    of the ``k x tokens`` picks that went to output ``e`` (a constant: no
    gradient) and ``P_e`` its mean score; and ``R x max_e f_e``."""
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
