"""Plain reference of the Xing4.0-29B-A4B stack as the program builds it.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
the equations below as written, un-absorbed latent attention with a full
softmax, a loop over the experts with a mask, a Python loop over the
Sinkhorn iterations on ``[.., n, n]``, YaRN from its numbers; no cache, no
pages, no kernel, no packing.  It reads the program's parameter tree by its
names and imports nothing of the program.  There is no network here, so
the equations are written from the catalog's row (its ``config`` keys) and
from memory of the papers behind them (Manifold-Constrained
Hyper-Connections, arXiv 2512.24880, over Hyper-Connections, arXiv
2409.19606; the DeepSeek-V3 layer; YaRN as that family computes it); every
remembered point is listed in ``configs/xing4.0-29b-a4b.json`` under
``assumed``, and where the program departs from the source that file says
so under ``departures`` and this file follows the program.

**The residual stream** of a token is ``X [n, d]`` (``n = hc_mult`` rows).
``X_0`` is the token's embedding in every row.  Every sublayer ``F``
(attention, then the FFN, each with its pre-norm inside) has a
hyper-connection of its own, per token::

    u      = RMSNorm_{n d}(vec(X))                a learned scale [n d]; vec row-major
    Hpre~  = a_pre  (u Phi_pre)  + b_pre          Phi_pre  [n d, n]
    Hpost~ = a_post (u Phi_post) + b_post         Phi_post [n d, n]
    Hres~  = a_res mat(u Phi_res) + b_res         Phi_res  [n d, n n]; mat row-major
    H_pre  = sigmoid(Hpre~);   H_post = 2 sigmoid(Hpost~)
    M_0    = exp(clip(Hres~, clamp_min, clamp_max))
    M_t    = T_r(T_c(M_{t-1})),  t = 1..hc_sinkhorn_iters
             T_c: each column over (its sum + hc_eps);  T_r: each row likewise
    h      = sum_i H_pre[i] X[i]                  the sublayer's input
    X[i]  <- sum_j M[i, j] X[j] + H_post[i] F(h)

(the program stores ``[Phi_pre | Phi_post | Phi_res]`` as one ``phi`` and
the three ``a`` as ``alpha``).  After the last layer ``x = sum_i X[i]``,
then the final norm and the heads.

**Latent attention (MLA)**, ``H`` heads: ``c_q = RMSNorm(h W_qa)``; ``q =
c_q W_qb``, a head ``[q_nope | q_pe]``; ``[c | k_pe] = h W_kva``; ``c =
RMSNorm(c)``; ``[k_nope | v]`` a head ``= c W_kvb``; rotary on ``q_pe`` and
the ONE ``k_pe`` all heads share, pairs interleaved (``2i`` with ``2i +
1``); ``score = (q_nope . k_nope + q_pe . k_pe) x scale``; causal softmax;
``o = concat(p v) W_o``.  **YaRN**: ``f_i = theta^(-2i/D)``, ``dim(r) = D
ln(L0 / (2 pi r)) / (2 ln theta)``, ``low = max(floor(dim(beta_fast)),
0)``, ``high = min(ceil(dim(beta_slow)), D - 1)``, ``ramp_i = clip((i -
low) / (high - low), 0, 1)``, ``inv_freq_i = f_i (1 - ramp_i) + (f_i / s)
ramp_i``; ``m(s, a) = 0.1 a ln s + 1``; cos and sin times ``m(s, mscale) /
m(s, mscale_all_dim)``; ``scale = m(s, mscale_all_dim)^2 / sqrt(nope +
rope)``.

**The mixture of experts**, ``g`` the normed input: ``s = sigmoid(g W_r)``;
the picks are the ``num_experts_per_tok`` largest of ``s + beta`` (beta
only chooses); ``w_e = routed_scaling_factor x s_e / (sum of the picked s
+ 1e-20)``; ``m = sum_e w_e SwiGLU_e(g) + SwiGLU_shared(g)``.  The
``first_k_dense_replace`` leading layers have a SwiGLU of
``intermediate_size`` instead.  Every expert is held.

The multi-token-prediction module is not built (the configuration's
departure 2).  Weights may arrive in a lower precision and on the host:
every layer is one jitted call that takes only its own block, and a matrix
is raised to float32 where it is multiplied.
"""

from __future__ import annotations

import math
from functools import partial
from typing import List, Mapping, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

_HEAD_BLOCK = 16384  # columns of the policy head scored at once


def program_argv(cfg: Mapping) -> List[str]:
    """The configuration's sizes as the program's own arguments."""
    ys = cfg["rope_scaling"]
    return [
        "--block-family", "xing4",
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
        "--moe-experts", str(cfg["n_routed_experts"]),
        "--moe-shared-experts", str(cfg["n_shared_experts"]),
        "--moe-experts-per-token", str(cfg["num_experts_per_tok"]),
        "--moe-scoring", str(cfg["scoring_func"]),
        "--moe-routed-scaling", str(cfg["routed_scaling_factor"]),
        "--moe-norm-topk-prob", str(bool(cfg["norm_topk_prob"])).lower(),
        "--router-aux-loss-coef", str(cfg["router_aux_loss_coef"]),
        "--hc-mult", str(cfg["hc_mult"]),
        "--hc-sinkhorn-iters", str(cfg["hc_sinkhorn_iters"]),
        "--hc-eps", str(cfg["hc_eps"]),
        "--hc-clamp-min", str(cfg["mhc_h_res_clamp_min"]),
        "--hc-clamp-max", str(cfg["mhc_h_res_clamp_max"]),
        "--rope-factor", str(ys["factor"]),
        "--rope-original-max", str(ys["original_max_position_embeddings"]),
        "--rope-beta-fast", str(ys["beta_fast"]),
        "--rope-beta-slow", str(ys["beta_slow"]),
        "--rope-mscale", str(ys["mscale"]),
        "--rope-mscale-all-dim", str(ys["mscale_all_dim"]),
    ]


class Yarn(NamedTuple):
    factor: float
    original_max: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float


class Geometry(NamedTuple):
    """What the forward needs beside the weights."""

    n_head: int
    nope: int
    rope: int
    v_dim: int
    kv_rank: int
    top_k: int
    scaling: float
    eps: float
    theta: float
    streams: int
    hc_iters: int
    hc_eps: float
    clamp: Tuple[float, float]
    yarn: Optional[Yarn]
    # None: the reference.  A dtype name ("float8_e4m3fn"): both operands
    # of every weight matmul of the blocks and the heads are first rounded
    # to it, which is how a cell's check reads what a precision BELOW the
    # configuration's would cost (its bounds have to call that reading not
    # correct).  The hyper-connections' maps are float32 in the
    # configuration and stay so under it
    round_to: Optional[str] = None
    # a dtype name: the flattened norm ``u`` is rounded to it before the
    # maps' projection (the control that says whether a check can tell
    # float32 maps from lower ones)
    map_round_to: Optional[str] = None
    # a planted fault, for the tests and controls that must fail:
    # "identity_res" (H_res = I), "rows_first" (T_c after T_r),
    # "plain_rope" (theta^(-2i/D) alone), "no_m2" (the scale without m^2)
    fault: str = ""


def geometry(
    cfg: Mapping,
    round_to: Optional[str] = None,
    hc_iters: Optional[int] = None,
    map_round_to: Optional[str] = None,
    fault: str = "",
) -> Geometry:
    ys = cfg.get("rope_scaling")
    yarn = None
    if ys:
        yarn = Yarn(
            float(ys["factor"]), int(ys["original_max_position_embeddings"]),
            float(ys["beta_fast"]), float(ys["beta_slow"]), float(ys["mscale"]),
            float(ys["mscale_all_dim"]),
        )
    return Geometry(
        int(cfg["num_attention_heads"]), int(cfg["qk_nope_head_dim"]),
        int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"]), int(cfg["kv_lora_rank"]),
        int(cfg["num_experts_per_tok"]), float(cfg["routed_scaling_factor"]),
        float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]), int(cfg["hc_mult"]),
        int(cfg["hc_sinkhorn_iters"]) if hc_iters is None else int(hc_iters),
        float(cfg["hc_eps"]),
        (float(cfg["mhc_h_res_clamp_min"]), float(cfg["mhc_h_res_clamp_max"])),
        yarn, round_to, map_round_to, fault,
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


# -- YaRN ---------------------------------------------------------------


def yarn_numbers(D: int, theta: float, yarn: Yarn):
    """``(inv_freq [D/2], low, high, amplitude, m_all)``: the blended
    inverse frequencies, the ramp's two ends, the factor on cos and sin
    and ``m(s, mscale_all_dim)`` (whose square multiplies the softmax
    scale)."""

    def dim(rotations):
        return D * math.log(yarn.original_max / (2.0 * math.pi * rotations)) / (2.0 * math.log(theta))

    def m(a):
        return 0.1 * a * math.log(yarn.factor) + 1.0 if yarn.factor > 1 else 1.0

    low = max(math.floor(dim(yarn.beta_fast)), 0)
    high = min(math.ceil(dim(yarn.beta_slow)), D - 1)
    i = jnp.arange(D // 2, dtype=jnp.float32)
    f = theta ** (-2.0 * i / D)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return f * (1.0 - ramp) + (f / yarn.factor) * ramp, low, high, m(yarn.mscale) / m(yarn.mscale_all_dim), m(yarn.mscale_all_dim)


def softmax_scale(geo: Geometry) -> float:
    scale = 1.0 / math.sqrt(geo.nope + geo.rope)
    if geo.yarn is not None and geo.fault != "no_m2":
        scale *= yarn_numbers(geo.rope, geo.theta, geo.yarn)[4] ** 2
    return scale


def _rope(x, positions, geo: Geometry):
    """``x [B, T, H, D]`` at ``positions [B, T]``, pairs interleaved:
    features ``(2i, 2i + 1)`` turn by ``position x inv_freq_i``."""
    D = x.shape[-1]
    if geo.yarn is None or geo.fault == "plain_rope":
        inv_freq = geo.theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
        amplitude = 1.0
    else:
        inv_freq, _low, _high, amplitude, _m = yarn_numbers(D, geo.theta, geo.yarn)
    angle = positions.astype(jnp.float32)[:, :, None, None] * inv_freq  # [B, T, 1, D/2]
    cos, sin = amplitude * jnp.cos(angle), amplitude * jnp.sin(angle)
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return turned.reshape(x.shape)


# -- the hyper-connection -----------------------------------------------


def sinkhorn(m, iters: int, eps: float, rows_first: bool = False):
    """``iters`` rounds of column then row normalisation of ``m [.., n,
    n]`` (entry ``[i, j]``: row ``i``, column ``j``)."""
    for _ in range(iters):
        for axis in ((-1, -2) if rows_first else (-2, -1)):
            # axis -2 runs over the rows of one column: a column's sum
            m = m / (jnp.sum(m, axis=axis, keepdims=True) + eps)
    return m


def hyper_maps(p: Mapping, X, geo: Geometry):
    """``(H_pre [B, T, n], H_post [B, T, n], H_res [B, T, n, n])`` of a
    stream ``X [B, T, n, d]``."""
    B, T, n, d = X.shape
    u = _rms_norm(X.reshape(B, T, n * d), p["scale"], geo.eps)
    if geo.map_round_to is not None:
        u = u.astype(geo.map_round_to).astype(jnp.float32)
    z = u @ _f32(p["phi"])
    a, b = _f32(p["alpha"]), _f32(p["b"])
    pre = jax.nn.sigmoid(a[0] * z[..., :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * z[..., n : 2 * n] + b[n : 2 * n])
    logit = a[2] * z[..., 2 * n :].reshape(B, T, n, n) + b[2 * n :].reshape(n, n)
    if geo.fault == "identity_res":
        return pre, post, jnp.broadcast_to(jnp.eye(n, dtype=jnp.float32), (B, T, n, n))
    m = jnp.exp(jnp.clip(logit, geo.clamp[0], geo.clamp[1]))
    return pre, post, sinkhorn(m, geo.hc_iters, geo.hc_eps, geo.fault == "rows_first")


def hyper_read(pre, X):
    return jnp.einsum("bti,btid->btd", pre, X)


def hyper_write(post, res, X, y):
    return jnp.einsum("btij,btjd->btid", res, X) + post[..., None] * y[:, :, None, :]


# -- the sublayers ------------------------------------------------------


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
    q_pe = _rope(q[..., geo.nope :], positions, geo)
    k_pe = _rope(kv[..., None, geo.kv_rank :], positions, geo)  # [B, T, 1, rope]
    s = (
        jnp.einsum("bqhd,bkhd->bhqk", q[..., : geo.nope], k_nope)
        + jnp.einsum("bqhd,bkd->bhqk", q_pe, k_pe[:, :, 0])
    ) * softmax_scale(geo)
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
    for e in range(bank["w_gate"].shape[0]):  # every token through every expert, masked
        gate = jax.nn.silu(_mm(h, bank["w_gate"][e], rt)) * _mm(h, bank["w_up"][e], rt)
        y = y + weights[..., e, None] * _mm(gate, bank["w_down"][e], rt)
    return y, scores, weights, gap


@partial(jax.jit, static_argnames=("geo",))
def layer(block: Mapping, X, positions, mask, geo: Geometry):
    """One layer on a stream ``X [B, T, n, d]`` (float32): ``(X, scores,
    weights, gap)``, the last three None in a dense layer.  Which kind it
    is the block's own names say.  ``mask [B, T, T]`` says which keys a
    query may attend."""
    with jax.default_matmul_precision("highest"):
        pre, post, res = hyper_maps(block["attn_hc"], X, geo)
        h = _rms_norm(hyper_read(pre, X), block["attn_norm"]["scale"], geo.eps)
        X = hyper_write(post, res, X, _attention(block["attn"], h, positions, mask, geo))
        pre, post, res = hyper_maps(block["ffn_hc"], X, geo)
        g = _rms_norm(hyper_read(pre, X), block["ffn_norm"]["scale"], geo.eps)
        if "experts" not in block:
            return hyper_write(post, res, X, _ffn(block["ffn"], g, geo.round_to)), None, None, None
        y, scores, weights, gap = _moe(block["experts"], g, geo)
        if "shared" in block:
            y = y + _ffn(block["shared"], g, geo.round_to)
        return hyper_write(post, res, X, y), scores, weights, gap


@partial(jax.jit, static_argnames=("geo",))
def final_norm(p_norm, x, geo: Geometry):
    return _rms_norm(x, p_norm["scale"], geo.eps)


@partial(jax.jit, static_argnames=("geo",))
def _score(kernel, bias, x, geo: Geometry):
    with jax.default_matmul_precision("highest"):
        return _mm(x, kernel, geo.round_to) + _f32(bias)


def heads(p_norm, p_policy, p_value, x, geo: Geometry):
    """``(logits [B, T, V], values [B, T])`` of read-out rows ``x``: the
    whole vocabulary at once (tests, small sizes)."""
    x = final_norm(p_norm, x, geo)
    logits = _score(p_policy["kernel"], p_policy["bias"], x, geo)
    values = _score(p_value["kernel"], p_value["bias"], x, geo)[..., 0]
    return logits, values


def _causal(tokens, positions, mask):
    B, T = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    if mask is None:
        mask = jnp.broadcast_to(jnp.tril(jnp.ones((T, T), bool)), (B, T, T))
    return positions, mask


def trunk(
    params: Mapping, tokens, geo: Geometry, positions=None, mask=None,
    blocks: Optional[Sequence[int]] = None,
):
    """The layers and the read-out: ``(x [B, T, d], routing)``, ``x`` the
    sum of the stream's rows after the last layer and ``routing`` a list
    with one ``(scores, weights, gap)`` a ROUTED layer.  Causal over
    positions ``0..T-1`` unless ``positions`` and ``mask`` say otherwise
    (packed rows).  ``blocks``: the numbers of the ``block_<i>`` to run,
    in order (default: all the weights hold)."""
    p = params["params"]
    tokens = jnp.asarray(tokens)
    positions, mask = _causal(tokens, positions, mask)
    x = _f32(jnp.asarray(p["token_embed"]["embedding"])[tokens])
    X = jnp.broadcast_to(x[:, :, None, :], x.shape[:2] + (geo.streams, x.shape[-1]))
    routing = []
    if blocks is None:
        blocks = range(sum(1 for name in p if name.startswith("block_")))
    for i in blocks:
        X, scores, weights, gap = layer(p[f"block_{i}"], X, positions, mask, geo)
        if scores is not None:
            routing.append((scores, weights, gap))
    return jnp.sum(X, axis=2), routing


def forward(params: Mapping, tokens, geo: Geometry, positions=None, mask=None, blocks=None):
    """``(logits [B, T, V], values [B, T], routing)``."""
    p = params["params"]
    x, routing = trunk(params, tokens, geo, positions, mask, blocks)
    logits, values = heads(p["final_norm"], p["policy_head"], p["value_head"], x, geo)
    return logits, values, routing


def _head_blocks(vocab: int) -> int:
    """The fewest equal column blocks of at most ``_HEAD_BLOCK``."""
    return next(n for n in range(1, vocab + 1) if vocab % n == 0 and vocab // n <= _HEAD_BLOCK)


def token_logprobs(params: Mapping, tokens, geo: Geometry):
    """Log-probability the reference gives each token ``t >= 1`` of each
    row given the tokens before it, the value before it, and the router's
    gap at every (routed layer, token): ``(logp [B, T-1], values [B, T-1],
    gaps [layers, B, T])``.  The head runs a row at a time and in column
    blocks: a running log-sum-exp and the picked column's score, so that
    no ``[T, V]`` array exists."""
    p = params["params"]
    tokens = jnp.asarray(tokens)
    x, routing = trunk(params, tokens, geo)
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
    """The load-balancing term and the largest expert's load over the
    tokens ``real_tokens [B, T]`` names, all routed layers together: ``E x
    sum_e f_e P_e`` with ``f_e`` the share of the picks that went to
    expert ``e`` (a constant: no gradient) and ``P_e`` its mean score; and
    ``E x max_e f_e``.  The published model trains without the term
    (``noaux_tc``); the program's learner reports it all the same."""
    m = jnp.asarray(real_tokens, jnp.float32)[..., None]
    picked = sum(jnp.sum((w > 0) * m, axis=(0, 1)) for _s, w, _g in routing)
    score = sum(jnp.sum(s * m, axis=(0, 1)) for s, _w, _g in routing)
    share = jax.lax.stop_gradient(picked / jnp.sum(picked))
    mean_score = score / (jnp.sum(m) * len(routing))
    E = share.shape[-1]
    return E * jnp.sum(share * mean_score), E * jnp.max(share)


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
