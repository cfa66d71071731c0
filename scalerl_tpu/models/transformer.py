"""Decoder-only transformer policy for long-horizon trajectories.

No counterpart in the reference (its sequence machinery tops out at a
2-layer LSTM, ``scalerl/algorithms/utils/atari_model.py:109-120``); this is
the long-context model family the TPU build adds: a causal transformer over
the trajectory time axis producing per-step policy logits and baseline, with
an attention implementation that can be swapped for sequence-parallel
:func:`scalerl_tpu.ops.ring_attention.ring_attention` under ``shard_map``.

Design notes for sequence parallelism: everything except attention is
position-wise (LayerNorm, MLP, heads), so the module is valid when the time
axis is sharded across the ``sp`` mesh axis — callers pass ``positions``
(global step indices) so positional embeddings stay correct per shard.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from scalerl_tpu.models.routed_ffn import RoutedExperts
from scalerl_tpu.ops.pallas_attention import flash_attention
from scalerl_tpu.ops.pallas_paged_attention import (
    gather_pages,
    latent_attention,
    latent_pool_width,
    paged_attention_reference,
    paged_latent_attention_reference,
)
from scalerl_tpu.ops.ring_attention import full_attention

# (q, k, v) -> attention output, all [B, T, H, D]
AttentionFn = Callable[[jnp.ndarray, jnp.ndarray, jnp.ndarray], jnp.ndarray]


@dataclasses.dataclass(frozen=True)
class BlockSpec:
    """What kind of block the token model stacks, as data.

    The defaults are the GPT-2 block (LayerNorm, a learned position
    table, one fused-qkv MHA at head size ``d_model / heads``, a GELU
    MLP): same parameter names, same tree.  Every other kind sits at one
    of two points that all attention paths share: after the q/k/v
    projections and before the cache write (q/k norm, rotary positions:
    K enters a cache normed and rotated, so cached, paged and packed
    paths read it as it is), and where the MLP sits (the routed experts).

    The attention kind and the layer kind are apart.  ``attention="mla"``
    is multi-head latent attention (:class:`_LatentAttention`): low-rank
    q and kv projections, a rotated key part all heads share, and a cache
    of one ``kv_lora_rank + qk_rope_head_dim`` row a token; it runs in a
    plain layer (:class:`_Block`: one attention, one FFN) or in the
    double one.  ``mla_scale`` multiplies q by ``sqrt(d / q_lora_rank)``
    and the normed latent by ``sqrt(d / kv_lora_rank)`` (LongCat's two
    factors; a model without those keys has neither).  ``layer="scmoe"``
    is the shortcut-connected double layer (:class:`_ShortcutBlock`): two
    attentions and two dense SwiGLU FFNs of width ``ffn_hidden`` in a
    row, with one routed-experts branch leaving after the first attention
    and joining after the second FFN.  A router scores ``num_experts +
    zero_experts`` outputs by ``scoring``, of which this program holds the
    banks of ``experts_held`` (``models/routed_ffn.py``);
    ``shared_experts`` always-on experts of the routed width sit beside
    them as one dense SwiGLU.  ``ffn="swiglu"`` is a dense SwiGLU of width
    ``ffn_hidden`` in a plain layer: a stack's leading dense layers
    (:func:`layer_specs`).
    """

    norm: str = "layernorm"  # layernorm | rmsnorm
    norm_eps: float = 1e-6
    positions: str = "learned"  # learned (a table added to the embedding) | rope
    rope_theta: float = 10000.0
    qk_norm: str = "none"  # none | rmsnorm (over the projection's whole width)
    head_dim: Optional[int] = None  # None: d_model // num_heads
    ffn: str = "mlp"  # mlp (GELU, mlp_ratio x d_model) | experts (routed SwiGLU) | swiglu
    num_experts: int = 0
    experts_per_token: int = 0
    expert_width: int = 0
    norm_topk_prob: bool = False
    attention: str = "mha"  # mha | mla
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_pairing: str = "half"  # half (i with i + D/2) | interleaved (2i with 2i + 1)
    layer: str = "plain"  # plain (attention, FFN) | scmoe
    ffn_hidden: int = 0
    zero_experts: int = 0
    experts_held: int = 0  # 0: every routed expert
    first_expert: int = 0
    router_bias: bool = False
    routed_scaling: float = 1.0
    mla_scale: bool = False
    scoring: str = "softmax"  # softmax | sigmoid (the router's, over all outputs)
    shared_experts: int = 0


def block_spec(
    family: str,
    *,
    head_dim: Optional[int] = None,
    norm_eps: float = 1e-5,
    rope_theta: float = 10000.0,
    num_experts: int = 0,
    experts_per_token: int = 0,
    expert_width: int = 0,
    norm_topk_prob: bool = False,
    q_lora_rank: int = 0,
    kv_lora_rank: int = 0,
    qk_nope_head_dim: int = 0,
    qk_rope_head_dim: int = 0,
    v_head_dim: int = 0,
    ffn_hidden: int = 0,
    zero_experts: int = 0,
    experts_held: int = 0,
    first_expert: int = 0,
    routed_scaling: float = 1.0,
    scoring: str = "softmax",
    shared_experts: int = 0,
) -> BlockSpec:
    """The block a named family stacks; the sizes only the family reads
    are ignored by the others (``gpt2`` keeps its own epsilon).  For a
    family whose stack has more than one kind of layer this is the kind
    that repeats (``joyai``: the routed layer) and :func:`layer_specs`
    gives the stack."""
    if family == "gpt2":
        return BlockSpec(head_dim=head_dim)
    if family == "olmoe":
        if not 1 <= experts_per_token <= num_experts or expert_width < 1:
            raise ValueError(
                "the olmoe block needs 1 <= experts_per_token <= num_experts "
                f"and an expert width, got {experts_per_token}/{num_experts}/"
                f"{expert_width}"
            )
        return BlockSpec(
            norm="rmsnorm", norm_eps=norm_eps, positions="rope",
            rope_theta=rope_theta, qk_norm="rmsnorm", head_dim=head_dim,
            ffn="experts", num_experts=num_experts,
            experts_per_token=experts_per_token, expert_width=expert_width,
            norm_topk_prob=norm_topk_prob,
        )
    if family == "longcat":
        held = experts_held or num_experts
        sizes = (
            q_lora_rank, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
            v_head_dim, ffn_hidden, expert_width,
        )
        if min(sizes) < 1 or qk_rope_head_dim % 2:
            raise ValueError(
                "the longcat block needs its five latent-attention sizes (an "
                "even rotary part), a dense FFN width and an expert width, "
                f"got {sizes}"
            )
        if not (
            1 <= experts_per_token <= num_experts + zero_experts
            and zero_experts >= 0
            and 0 <= first_expert
            and 1 <= held
            and first_expert + held <= num_experts
        ):
            raise ValueError(
                "the longcat router picks experts_per_token of num_experts + "
                "zero_experts outputs and holds experts first_expert .. "
                f"first_expert + experts_held of the first num_experts, got "
                f"{experts_per_token}/{num_experts}/{zero_experts}/"
                f"{first_expert}/{held}"
            )
        return BlockSpec(
            norm="rmsnorm", norm_eps=norm_eps, positions="rope",
            rope_theta=rope_theta, ffn="experts", num_experts=num_experts,
            experts_per_token=experts_per_token, expert_width=expert_width,
            norm_topk_prob=norm_topk_prob, attention="mla",
            q_lora_rank=q_lora_rank, kv_lora_rank=kv_lora_rank,
            qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            rope_pairing="interleaved", layer="scmoe", ffn_hidden=ffn_hidden,
            zero_experts=zero_experts, experts_held=held,
            first_expert=first_expert, router_bias=True,
            routed_scaling=routed_scaling, mla_scale=True,
        )
    if family == "joyai":
        held = experts_held or num_experts
        sizes = (
            q_lora_rank, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
            v_head_dim, expert_width,
        )
        if min(sizes) < 1 or qk_rope_head_dim % 2:
            raise ValueError(
                "the joyai block needs its five latent-attention sizes (an "
                f"even rotary part) and an expert width, got {sizes}"
            )
        if not (
            1 <= experts_per_token <= num_experts
            and 0 <= first_expert
            and 1 <= held
            and first_expert + held <= num_experts
            and shared_experts >= 0
            and scoring in ("softmax", "sigmoid")
        ):
            raise ValueError(
                "the joyai router picks experts_per_token of num_experts by "
                "a softmax or sigmoid score and holds experts first_expert "
                ".. first_expert + experts_held beside shared_experts "
                f"always-on ones, got {experts_per_token}/{num_experts}/"
                f"{scoring}/{first_expert}/{held}/{shared_experts}"
            )
        return BlockSpec(
            norm="rmsnorm", norm_eps=norm_eps, positions="rope",
            rope_theta=rope_theta, ffn="experts", num_experts=num_experts,
            experts_per_token=experts_per_token, expert_width=expert_width,
            norm_topk_prob=norm_topk_prob, attention="mla",
            q_lora_rank=q_lora_rank, kv_lora_rank=kv_lora_rank,
            qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            rope_pairing="interleaved", ffn_hidden=ffn_hidden,
            experts_held=held, first_expert=first_expert, router_bias=True,
            routed_scaling=routed_scaling, scoring=scoring,
            shared_experts=shared_experts,
        )
    raise ValueError(
        f"block family must be gpt2 | olmoe | longcat | joyai, got {family!r}"
    )


def layer_specs(
    spec: BlockSpec, num_layers: int, dense_layers: int = 0
) -> Tuple[BlockSpec, ...]:
    """The stack as a per-layer list: ``dense_layers`` leading layers of
    ``spec``'s attention around a dense SwiGLU of width ``ffn_hidden``,
    then ``spec`` itself to ``num_layers``."""
    if not 0 <= dense_layers <= num_layers or (dense_layers and spec.ffn_hidden < 1):
        raise ValueError(
            "dense_layers must lie in 0..num_layers and needs ffn_hidden, got "
            f"{dense_layers}/{num_layers}/{spec.ffn_hidden}"
        )
    dense = dataclasses.replace(
        spec, ffn="swiglu", num_experts=0, experts_per_token=0, expert_width=0,
        experts_held=0, first_expert=0, router_bias=False, routed_scaling=1.0,
        norm_topk_prob=False, scoring="softmax", shared_experts=0,
    )
    return (dense,) * dense_layers + (spec,) * (num_layers - dense_layers)


class TransformerOutput(NamedTuple):
    policy_logits: jnp.ndarray  # [B, T, num_actions]
    baseline: jnp.ndarray  # [B, T]
    # [B, T, num_actions] of the multi-token-prediction module: position
    # i's distribution over token i + 2; only a forward called with
    # ``mtp=True`` on a model that carries a module has it
    mtp_logits: Optional[jnp.ndarray] = None


class PagedKVCache(NamedTuple):
    """Block-paged key/value cache: a fixed pool shared by every lane.

    ``k``/``v``: one lane-dense ``[num_pages, page_size, H*D]`` pool per
    transformer block: a token's heads lie side by side on the minor axis,
    so the TPU runtime stores the pool row-major and the paged-decode
    kernel reads it in place (``ops/pallas_paged_attention.py``; a
    ``[.., H, D]`` pool with ``D < 128`` was stored page-index-minor and
    copied whole, twice, by every decode program).  Consumers split the
    heads out of the rows they gathered, never out of the pool.  Lanes own
    *pages*, not contiguous rows: a host-side allocator
    (``genrl/paging.py``) hands each lane an ordered page list, and the
    decode path writes token ``p`` of a lane into page
    ``table[p // page_size]`` at slot ``p % page_size`` — so KV memory
    scales with LIVE tokens across all lanes instead of
    ``max_bucket x lanes`` (the vLLM shape).  Page 0 is the allocator's
    null page: dead-lane and pad writes are routed there and it is never
    read (every read is masked by a lane's true length).
    """

    k: Tuple[jnp.ndarray, ...]
    v: Tuple[jnp.ndarray, ...]


def init_paged_kv_cache(
    num_pages: int,
    page_size: int,
    num_layers: int,
    num_heads: int,
    head_dim: int,
    dtype=jnp.float32,
) -> PagedKVCache:
    """Zeroed lane-dense page pools (page 0 = the never-read null page)."""
    shape = (num_pages, page_size, num_heads * head_dim)
    return PagedKVCache(
        k=tuple(jnp.zeros(shape, dtype) for _ in range(num_layers)),
        v=tuple(jnp.zeros(shape, dtype) for _ in range(num_layers)),
    )


class LatentKVCache(NamedTuple):
    """The paged cache of a latent-attention (``mla``) model: per
    attention ONE lane-dense ``[num_pages, page_size, W]`` pool of
    ``[c | rotated k_pe | zeros]`` rows (``W`` =
    ``latent_pool_width(kv_lora_rank + qk_rope_head_dim)``, 640 for the
    published 576), which every head shares and which holds the values
    too: no V pool.  The pools lie in layer order, one a plain layer and
    two a ``scmoe`` layer (its two attentions).  Pages, tables, the null page
    and every rule of :class:`PagedKVCache` are the same: sharing, forks
    and the prefix cache are page-index facts and do not see the kind."""

    rows: Tuple[jnp.ndarray, ...]


def prompt_attention_mask(lengths: jnp.ndarray, total_len: int) -> jnp.ndarray:
    """``[B, T, T]`` causal mask over RIGHT-padded (compact) prompts, for
    the paged prefill: lane ``b``'s real tokens occupy columns ``[0, lengths[b])``, so position ``i`` attends
    causally within the real prefix and pad-tail rows degrade to uniform
    (finite, outputs unused)."""
    cols = jnp.arange(total_len)[None, None, :]
    rows = jnp.arange(total_len)[None, :, None]
    return (cols <= rows) & (cols < lengths[:, None, None])


def sequence_attention_mask(
    lengths: jnp.ndarray, prompt_pad: int, total_len: int
) -> jnp.ndarray:
    """``[B, S, S]`` causal mask over a full left-padded sequence (the
    padded learner layout, ``genrl/rollout.py``'s ``pack_completions``), so
    the training forward recomputes exactly the distribution the
    generation engine sampled from (pad-prefix columns excluded)."""
    cols = jnp.arange(total_len)[None, None, :]
    rows = jnp.arange(total_len)[None, :, None]
    pad = (prompt_pad - lengths)[:, None, None]
    return (cols >= pad) & (cols <= rows)


def sequence_positions(
    lengths: jnp.ndarray, prompt_pad: int, total_len: int
) -> jnp.ndarray:
    """``[B, S]`` position ids for left-padded sequences: the first real
    token of every lane gets position 0 (pad positions clamp to 0 — they
    are masked out of attention and their outputs unused)."""
    pad = (prompt_pad - lengths)[:, None]
    return jnp.clip(jnp.arange(total_len)[None, :] - pad, 0, total_len - 1)


def packed_attention_mask(segment_ids: jnp.ndarray) -> jnp.ndarray:
    """``[B, S, S]`` segment-blocked causal mask over PACKED rows (the
    pad-free learner layout, ``genrl/rollout.py``): token ``i`` attends to
    ``j <= i`` iff both carry the same nonzero segment id.  Pad tokens
    (id 0) attend nowhere — their rows degrade to uniform under
    :func:`_masked_attention` (finite, outputs unused) and to exact zeros
    under the Pallas segment kernel; the loss mask excludes them either
    way."""
    seg = segment_ids.astype(jnp.int32)
    S = seg.shape[1]
    causal = jnp.arange(S)[None, :, None] >= jnp.arange(S)[None, None, :]
    return (
        causal
        & (seg[:, :, None] == seg[:, None, :])
        & (seg[:, :, None] > 0)
    )


def _masked_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: jnp.ndarray,
    out_dtype,
) -> jnp.ndarray:
    """Explicit masked attention: q ``[B, T, H, D]`` against k/v
    ``[B, S, H, D]`` with a ``[B, T, S]`` validity mask (True = attend).

    Scores/softmax run in float32 regardless of the compute dtype — the
    decode path feeds sampling logits, where bf16 softmax drift would show
    up directly in the behavior logprobs the learner's importance ratios
    divide by.  Fully-masked rows degrade to a uniform distribution (finite
    by construction) instead of NaN.
    """
    head_dim = q.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.float32(head_dim))
    scores = (
        jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32), k.astype(jnp.float32))
        * scale
    )
    scores = jnp.where(mask[:, None, :, :], scores, jnp.float32(-1e30))
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", probs, v.astype(jnp.float32))
    return out.astype(out_dtype)


class RMSNorm(nn.Module):
    """``x / rms(x) * scale`` over the last axis, computed in float32
    (scale included) and rounded once to ``dtype``."""

    epsilon: float
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        scale = self.param(
            "scale", nn.initializers.ones, (x.shape[-1],), jnp.float32
        )
        x = x.astype(jnp.float32)
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return (x * lax.rsqrt(ms + self.epsilon) * scale).astype(self.dtype)


def _norm(spec: BlockSpec, dtype, name: Optional[str] = None) -> nn.Module:
    if spec.norm == "rmsnorm":
        return RMSNorm(spec.norm_eps, dtype=dtype, name=name)
    return nn.LayerNorm(use_bias=False, dtype=dtype, name=name)


def rotary_fn(
    positions: jnp.ndarray, head_dim: int, theta: float, pairing: str = "half"
) -> Callable:
    """``x [B, T, H, D] -> x`` rotated to ``positions [B, T]``: the
    rotate-half pairing (feature ``i`` with ``i + D/2``), ``inv_freq_i =
    theta^(-2i/D)``, angle ``position x inv_freq``; computed in float32
    and rounded once.  The angles are made once a forward and shared by
    every block.  Under ``pairing="interleaved"`` pair ``i`` is features
    ``(2i, 2i + 1)``; the result is laid out half-wise (all first members,
    then all second: the DeepSeek family's own arrangement), which q and k
    share, so every score is that of the interleaved rotation."""
    half = head_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / head_dim)
    angle = positions.astype(jnp.float32)[:, :, None, None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)

    def rotate(x):
        if pairing == "interleaved":
            xf = x.astype(jnp.float32)
            x1, x2 = xf[..., 0::2], xf[..., 1::2]
        else:
            x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
        return out.astype(x.dtype)

    return rotate


def _scatter_rows(pool: jnp.ndarray, flat_idx: jnp.ndarray, rows: jnp.ndarray):
    """This call's rows into pool pages: a flat single-axis scatter (page
    id x page size + offset) into the lane-dense pool.  The reshape is a
    bitcast, and XLA:CPU lowers 1-level row scatters measurably faster
    than the 2-level fancy-index form."""
    N, ps, width = pool.shape
    return (
        pool.reshape(N * ps, width)
        .at[flat_idx]
        .set(rows.astype(pool.dtype).reshape(-1, width))
        .reshape(pool.shape)
    )


def _routed_experts(spec: BlockSpec, dt) -> RoutedExperts:
    """The routed FFN a spec describes, under the name every layer kind
    gives it."""
    return RoutedExperts(
        spec.num_experts, spec.experts_per_token, spec.expert_width,
        spec.norm_topk_prob, zero_experts=spec.zero_experts,
        held=spec.experts_held, first_expert=spec.first_expert,
        choice_bias=spec.router_bias, routed_scaling=spec.routed_scaling,
        scoring=spec.scoring, name="experts", **dt,
    )


class _Block(nn.Module):
    d_model: int
    num_heads: int
    mlp_ratio: int
    attn_fn: AttentionFn
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    paged_attn_fn: Optional[Callable] = None
    segment_attn_fn: Optional[Callable] = None
    spec: BlockSpec = BlockSpec()
    # rotary positions of this forward's tokens (:func:`rotary_fn`), made
    # by the model from the ``positions`` every caller passes; None under
    # a learned position table
    rotary: Optional[Callable] = None

    @nn.compact
    def __call__(
        self,
        x: jnp.ndarray,
        attn_mask: Optional[jnp.ndarray] = None,
        paged_cache: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
        page_ids: Optional[jnp.ndarray] = None,
        page_offsets: Optional[jnp.ndarray] = None,
        page_table: Optional[jnp.ndarray] = None,
        attn_lengths: Optional[jnp.ndarray] = None,
        prefix_starts: Optional[jnp.ndarray] = None,
        segment_ids: Optional[jnp.ndarray] = None,
    ):
        """Full forward (no cache) or paged incremental step.

        With a mask but no cache it runs explicit masked attention against
        its own k/v (the learner-side forward over left-padded sequences).

        With ``paged_cache=(k_pages, v_pages)`` the block scatters this
        call's keys/values into pool pages — lane ``b``'s token ``t`` lands
        in ``(page_ids[b, t], page_offsets[b, t])``; dead-lane/pad writes
        are routed to the null page by the caller — then attends either
        *locally* against its own k/v under ``attn_mask`` (paged prefill: a
        fresh prompt's whole context is in-program, no pool read needed) or
        *through the pool* via ``paged_attn_fn(q, k_pages, v_pages,
        page_table, attn_lengths)`` (paged single-token decode); returns
        ``(out, (k_pages, v_pages))``.  Same params on every path.

        With ``page_table`` AND ``prefix_starts`` ``[B]`` this is the
        *shared-table tail prefill* (the prefix-cache path, ISSUE 14):
        the ``T`` tokens sit at global positions ``prefix_starts[b] + t``
        on top of a cached prefix whose K/V already lives in pool pages
        mapped by the table; this call's K/V is scattered first, then
        attention gathers the WHOLE context (cached prefix + this chunk)
        through the table under a causal-from-start mask — a plain XLA
        gather + :func:`_masked_attention`, no kernel involvement, so
        sharing stays purely a page-table fact.
        """
        B, T, _ = x.shape
        spec = self.spec
        rms = spec.norm == "rmsnorm"
        head_dim = spec.head_dim or self.d_model // self.num_heads
        width = self.num_heads * head_dim
        dt = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        h = _norm(spec, self.dtype, "attn_norm" if rms else None)(x)
        new_cache = None
        if spec.attention == "mla":
            # one latent attention: ``paged_cache`` is its one pool
            out, new_cache = _LatentAttention(
                self.d_model, self.num_heads, spec, self.attn_fn,
                paged_attn_fn=self.paged_attn_fn,
                segment_attn_fn=self.segment_attn_fn, rotary=self.rotary,
                name="attn", **dt,
            )(
                h, attn_mask=attn_mask, pool=paged_cache, page_ids=page_ids,
                page_offsets=page_offsets, page_table=page_table,
                attn_lengths=attn_lengths, prefix_starts=prefix_starts,
                segment_ids=segment_ids,
            )
        else:
            qkv = nn.Dense(3 * width, use_bias=False, name="qkv", **dt)(h)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            if spec.qk_norm == "rmsnorm":
                # over all heads' features at once, before the head split
                q = RMSNorm(spec.norm_eps, dtype=self.dtype, name="q_norm")(q)
                k = RMSNorm(spec.norm_eps, dtype=self.dtype, name="k_norm")(k)
            shape = (B, T, self.num_heads, head_dim)
            q, k, v = q.reshape(shape), k.reshape(shape), v.reshape(shape)
            if self.rotary is not None:
                # before every cache write: K is stored normed and rotated
                q, k = self.rotary(q), self.rotary(k)
            if paged_cache is not None:
                kp, vp = paged_cache
                flat_idx = (page_ids * kp.shape[1] + page_offsets).reshape(B * T)
                kp = _scatter_rows(kp, flat_idx, k)
                vp = _scatter_rows(vp, flat_idx, v)
                if page_table is not None and prefix_starts is not None:
                    # shared-table tail prefill: gather the whole context
                    # (cached prefix pages + the tail just scattered above)
                    # through the table, attend causal-from-start — the
                    # compute twin of the decode seam at T > 1, kernel-free.
                    # The speculative verify pass (genrl/continuous.py) rides
                    # this exact path with T = draft bucket + 1: slot j is
                    # position prefix_starts + j, the pos <= qpos mask keeps
                    # rejected slots' K/V (garbage past the cursor) out of
                    # every query, so draft rollback never touches the device.
                    # The heads are split out of the gathered rows: reshaping
                    # the pool itself would bring its relayout copy back
                    kg = gather_pages(kp, page_table, self.num_heads)
                    vg = gather_pages(vp, page_table, self.num_heads)
                    pos = jnp.arange(kg.shape[1])[None, None, :]
                    qpos = (
                        prefix_starts[:, None] + jnp.arange(T)[None, :]
                    )[:, :, None]
                    out = _masked_attention(
                        q, kg, vg, pos <= qpos, self.dtype
                    )
                elif page_table is not None:
                    paged_attn = self.paged_attn_fn or paged_attention_reference
                    out = paged_attn(q, kp, vp, page_table, attn_lengths)
                    out = out.astype(self.dtype)
                else:
                    out = _masked_attention(q, k, v, attn_mask, self.dtype)
                new_cache = (kp, vp)
            elif segment_ids is not None and self.segment_attn_fn is not None:
                # packed-row training attention through the flash seam: the
                # kernel enforces the segment-blocked causal rule and skips
                # fully-masked (cross-segment / pad) blocks entirely
                out = self.segment_attn_fn(q, k, v, segment_ids)
                out = out.astype(self.dtype)
            elif attn_mask is not None:
                out = _masked_attention(q, k, v, attn_mask, self.dtype)
            else:
                out = self.attn_fn(q, k, v)
            out = nn.Dense(self.d_model, use_bias=False, name="proj", **dt)(
                out.reshape(B, T, width)
            )
        x = x + out
        h = _norm(spec, self.dtype, "ffn_norm" if rms else None)(x)
        if spec.ffn == "experts":
            y = _routed_experts(spec, dt)(h)
            if spec.shared_experts:
                # always on, computed where the token lives
                y = y + _GatedMLP(
                    self.d_model, spec.shared_experts * spec.expert_width,
                    name="shared", **dt,
                )(h)
            h = y
        elif spec.ffn == "swiglu":
            h = _GatedMLP(self.d_model, spec.ffn_hidden, name="ffn", **dt)(h)
        else:
            h = nn.Dense(self.mlp_ratio * self.d_model, name="mlp_in", **dt)(h)
            h = nn.gelu(h)
            h = nn.Dense(self.d_model, name="mlp_out", **dt)(h)
        x = x + h
        if new_cache is not None:
            return x, new_cache
        return x


class _LatentAttention(nn.Module):
    """Multi-head latent attention (MLA) on a normed input ``h [B, T, d]``.

    ``c_q = RMSNorm(h W_qa)``; ``q = s_q (c_q W_qb)``, a head ``[q_nope |
    q_pe]``; ``[c | k_pe] = h W_kva``; ``c = s_kv RMSNorm(c)``; ``[k_nope |
    v]`` a head ``= c W_kvb``; rotary on ``q_pe`` and on the one ``k_pe``
    all heads share; scores ``(q_nope . k_nope + q_pe . k_pe) /
    sqrt(nope + rope)``, softmax in float32; ``o = concat(p v) W_o``.
    Under ``spec.mla_scale`` ``s_q = sqrt(d / q_lora_rank)`` and ``s_kv =
    sqrt(d / kv_lora_rank)``; else both are 1.

    One set of parameters, two forms of the same product:

    - **un-absorbed** wherever the keys are this call's own (the full
      and masked forwards, packed rows, the local prefill): ``k_nope`` and
      ``v`` are made from ``c`` and the call sites of :class:`_Block`
      attend (``v`` is padded to the q/k head size for the kernels that
      take one head size, and the pad sliced off).
    - **absorbed** wherever the keys come through a page table (decode,
      the tail prefill over a cached prefix, the speculative verify): the
      cache holds ``[c | rotated k_pe]`` a token and never a head's K or
      V, so ``W_kvb`` moves to the query side, ``q_abs = [W_uk^T q_nope |
      q_pe]``, scores are ``q_abs . row``, and a head's output is
      ``W_uv (sum p row[:kv_lora_rank])``.  Decode goes through
      ``paged_attn_fn`` (``ops.pallas_paged_attention.paged_decode_latent``
      or its XLA twin), the other two gather rows and run
      :func:`latent_attention`.

    Returns ``(out [B, T, d], pool)``; ``pool`` is None without a cache.
    """

    d_model: int
    num_heads: int
    spec: BlockSpec
    attn_fn: AttentionFn
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    paged_attn_fn: Optional[Callable] = None
    segment_attn_fn: Optional[Callable] = None
    rotary: Optional[Callable] = None

    @nn.compact
    def __call__(
        self, h, attn_mask=None, pool=None, page_ids=None, page_offsets=None,
        page_table=None, attn_lengths=None, prefix_starts=None,
        segment_ids=None,
    ):
        B, T, _ = h.shape
        s, H = self.spec, self.num_heads
        nope, rope, vd = s.qk_nope_head_dim, s.qk_rope_head_dim, s.v_head_dim
        r_kv = s.kv_lora_rank
        f32 = jnp.float32
        dt = dict(dtype=self.dtype, param_dtype=self.param_dtype)

        def dense(width, name, **kw):
            return nn.Dense(width, use_bias=False, name=name, **dt, **kw)

        # under ``mla_scale`` the two projections OUT of a rank start at
        # the variance a ``d_model``-wide input would give them (std
        # ``d_model ** -0.5``), which is what the two scales below bring
        # back to one: q, k and v of unit variance and attention scores of
        # order one at the start.  Plain fan-in over the rank would leave
        # the scores ``s_q x s_kv`` (7 at LongCat's sizes) too large and
        # the softmax near one-hot.  Without the factors plain fan-in over
        # the rank IS unit variance: the normed ranks go in at variance one
        if s.mla_scale:
            up_init = nn.initializers.normal(self.d_model ** -0.5)
            s_q = (self.d_model / s.q_lora_rank) ** 0.5
            s_kv = (self.d_model / r_kv) ** 0.5
        else:
            up_init, s_q, s_kv = nn.initializers.lecun_normal(), None, None
        c_q = RMSNorm(s.norm_eps, dtype=self.dtype, name="q_a_norm")(
            dense(s.q_lora_rank, "q_a")(h)
        )
        q = dense(H * (nope + rope), "q_b", kernel_init=up_init)(c_q)
        if s_q is not None:
            q = q * s_q
        q = q.reshape(B, T, H, nope + rope)
        q_nope, q_pe = q[..., :nope], q[..., nope:]
        kv = dense(r_kv + rope, "kv_a")(h)
        # the scale is applied in the norm's float32, rounded once
        c = RMSNorm(s.norm_eps, dtype=f32, name="kv_a_norm")(kv[..., :r_kv])
        if s_kv is not None:
            c = c * s_kv
        c = c.astype(self.dtype)
        k_pe = kv[..., None, r_kv:]  # [B, T, 1, rope]: one for all heads
        q_pe, k_pe = self.rotary(q_pe), self.rotary(k_pe)
        w_kvb = self.param(
            "kv_b", up_init, (r_kv, H * (nope + vd)), self.param_dtype
        ).astype(self.dtype)
        scale = 1.0 / (nope + rope) ** 0.5
        if pool is not None:
            # the cached row, normed, scaled and rotated, zero to the
            # pool's whole tiles
            row = jnp.concatenate([c, k_pe[:, :, 0]], axis=-1)
            row = jnp.pad(row, ((0, 0), (0, 0), (0, pool.shape[2] - row.shape[-1])))
            flat_idx = (page_ids * pool.shape[1] + page_offsets).reshape(B * T)
            pool = _scatter_rows(pool, flat_idx, row)
        if page_table is not None:
            w = w_kvb.reshape(r_kv, H, nope + vd)
            # as wide as the pool's row, zeros against its pad columns: the
            # kernel then takes the query as it is
            q_abs = jnp.concatenate(
                [
                    jnp.einsum(
                        "bthn,chn->bthc", q_nope, w[..., :nope],
                        preferred_element_type=f32,
                    ),
                    q_pe.astype(f32),
                    jnp.zeros((B, T, H, pool.shape[2] - r_kv - rope), f32),
                ],
                axis=-1,
            )
            if prefix_starts is not None:
                rows = gather_pages(pool, page_table, 1)[:, :, 0]
                pos = jnp.arange(rows.shape[1])[None, None, :]
                qpos = (
                    prefix_starts[:, None] + jnp.arange(T)[None, :]
                )[:, :, None]
                lat = latent_attention(q_abs, rows, pos <= qpos, r_kv, scale)
            else:
                paged = self.paged_attn_fn or paged_latent_attention_reference
                lat = paged(q_abs, pool, page_table, attn_lengths, r_kv, scale)
            out = jnp.einsum(
                "bthc,chv->bthv", lat.astype(self.dtype), w[..., nope:],
                preferred_element_type=f32,
            ).astype(self.dtype)
        else:
            kvb = jnp.dot(c, w_kvb).reshape(B, T, H, nope + vd)
            k = jnp.concatenate(
                [kvb[..., :nope], jnp.broadcast_to(k_pe, (B, T, H, rope))],
                axis=-1,
            )
            qf = jnp.concatenate([q_nope, q_pe], axis=-1)
            v = kvb[..., nope:]
            packed = segment_ids is not None and self.segment_attn_fn is not None
            if pool is not None or (attn_mask is not None and not packed):
                out = _masked_attention(qf, k, v, attn_mask, self.dtype)
            elif packed:
                # the segment kernels take a v narrower than q and k
                out = self.segment_attn_fn(qf, k, v, segment_ids).astype(self.dtype)
            else:
                # a causal ``attn_fn`` takes one head size: v padded to q
                # and k's, the pad sliced off
                v = jnp.pad(v, ((0, 0),) * 3 + ((0, nope + rope - vd),))
                out = self.attn_fn(qf, k, v)[..., :vd].astype(self.dtype)
        out = dense(self.d_model, "proj")(out.reshape(B, T, H * vd))
        return out, pool


class _GatedMLP(nn.Module):
    """Dense SwiGLU FFN, no bias: ``(silu(h Wg) * (h Wu)) Wd``."""

    d_model: int
    hidden: int
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h):
        dt = dict(use_bias=False, dtype=self.dtype, param_dtype=self.param_dtype)
        a = nn.silu(nn.Dense(self.hidden, name="gate", **dt)(h))
        a = a * nn.Dense(self.hidden, name="up", **dt)(h)
        return nn.Dense(self.d_model, name="down", **dt)(a)


class _ShortcutBlock(nn.Module):
    """The shortcut-connected double layer (``layer="scmoe"``), ``N`` an
    RMSNorm of its own at each use::

        x1 = x + MLA_0(N(x));  h = N(x1);  m = MoE(h)
        x2 = x1 + FFN_0(h)
        x3 = x2 + MLA_1(N(x2))
        out = x3 + FFN_1(N(x3)) + m

    The routed experts read what the first dense FFN reads and their sum
    joins after the second, so the expert branch can run beside the
    layer's second half.  Two attentions: a layer owns two cache pools.
    The call arguments are :class:`_Block`'s, ``paged_cache`` the pair of
    latent pools."""

    d_model: int
    num_heads: int
    attn_fn: AttentionFn
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    paged_attn_fn: Optional[Callable] = None
    segment_attn_fn: Optional[Callable] = None
    spec: BlockSpec = BlockSpec()
    rotary: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, paged_cache=None, **call):
        spec = self.spec
        dt = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        pools = paged_cache if paged_cache is not None else (None, None)

        def attention(i, x):
            out, pool = _LatentAttention(
                self.d_model, self.num_heads, spec, self.attn_fn,
                paged_attn_fn=self.paged_attn_fn,
                segment_attn_fn=self.segment_attn_fn, rotary=self.rotary,
                name=f"attn_{i}", **dt,
            )(_norm(spec, self.dtype, f"attn_norm_{i}")(x), pool=pools[i], **call)
            return x + out, pool

        def ffn(i, h):
            return _GatedMLP(self.d_model, spec.ffn_hidden, name=f"ffn_{i}", **dt)(h)

        x, pool_0 = attention(0, x)
        h = _norm(spec, self.dtype, "ffn_norm_0")(x)
        m = _routed_experts(spec, dt)(h)
        x = x + ffn(0, h)
        x, pool_1 = attention(1, x)
        x = x + ffn(1, _norm(spec, self.dtype, "ffn_norm_1")(x)) + m
        if paged_cache is not None:
            return x, (pool_0, pool_1)
        return x


class _MTPModule(nn.Module):
    """One multi-token-prediction module (the DeepSeek-V3 report's section
    2.2): ``h'_i = W_eh [RMSNorm_h(h_i) ; RMSNorm_e(Emb(t_{i+1}))]``, then
    one layer of the stack's repeating kind with weights of its own, at
    position ``i``'s rotary angle and under the caller's attention rule.
    ``x`` is the trunk's last layer output (before the final norm),
    ``next_emb`` the shared embedding of each position's next token and
    ``has_next [B, T]`` whether that token is in the position's own
    sequence: where it is not, the embedding's half is zero, so nothing of
    a neighbouring segment enters.  The caller norms the result and scores
    it with the shared head; it predicts ``t_{i+2}``."""

    d_model: int
    num_heads: int
    mlp_ratio: int
    attn_fn: AttentionFn
    spec: BlockSpec
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    segment_attn_fn: Optional[Callable] = None
    rotary: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, next_emb, has_next, **call):
        eps = self.spec.norm_eps
        h = RMSNorm(eps, dtype=self.dtype, name="h_norm")(x)
        e = RMSNorm(eps, dtype=self.dtype, name="e_norm")(next_emb)
        e = e * has_next[..., None].astype(self.dtype)
        y = nn.Dense(
            self.d_model, use_bias=False, name="eh_proj", dtype=self.dtype,
            param_dtype=self.param_dtype,
        )(jnp.concatenate([h, e], axis=-1))
        return _Block(
            self.d_model, self.num_heads, self.mlp_ratio, self.attn_fn,
            dtype=self.dtype, param_dtype=self.param_dtype,
            segment_attn_fn=self.segment_attn_fn, spec=self.spec,
            rotary=self.rotary, name="block",
        )(y, **call)


def _latent_pools(spec: BlockSpec) -> int:
    return 2 if spec.layer == "scmoe" else 1


@functools.lru_cache(maxsize=None)
def _note_layers(shape, kinds, attention, held, num_experts, mtp_layers) -> None:
    """A stack always runs the layers it was built from, so its counter is
    its make-up: one zero-length program span a traced shape (the cache is
    the "once"), so that a trace says which stack ran."""
    from scalerl_tpu.runtime import tracing

    with tracing.span(
        "model.layers", kind="model", shape=list(shape), layers=list(kinds),
        attention=attention, held=held, num_experts=num_experts,
        mtp_layers=mtp_layers,
    ):
        pass


class TransformerPolicy(nn.Module):
    """Causal transformer actor-critic over ``[B, T, obs_dim]`` features.

    ``attn_fn``: defaults to single-device causal :func:`full_attention`;
    pass a closed-over :func:`ring_attention` (inside ``shard_map``) for
    sequence-parallel execution.  NOTE: a custom ``attn_fn`` must apply its
    own causal masking — the default here is causal.

    ``use_flash=True`` swaps in the Pallas flash kernel
    (:func:`scalerl_tpu.ops.pallas_attention.flash_attention`): blockwise
    online-softmax attention that never materializes ``[T, T]`` scores —
    the right default on TPU once ``T`` is long (ignored when ``attn_fn``
    is given).
    """

    num_actions: int
    d_model: int = 128
    num_heads: int = 4
    num_layers: int = 2
    mlp_ratio: int = 4
    max_len: int = 4096
    attn_fn: Optional[AttentionFn] = None
    use_flash: bool = False
    # Token mode (the genrl sequence-RL plane): when set, ``obs`` is an
    # int32 ``[B, T]`` token-id array embedded through a learned table
    # instead of the Dense feature embed.  ``num_actions`` is then the
    # vocabulary the policy head scores (typically == vocab_size).
    vocab_size: Optional[int] = None
    # Mixed precision: blocks compute in ``dtype`` with params stored in
    # ``param_dtype`` (bf16/bf16 on the sharded learner plane); the heads
    # always emit float32 so the loss/V-trace math stays full precision.
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    # Sharded-activation seam: when set (``parallel.logical
    # .activation_constraint``), applied to the residual stream after the
    # embedding and after every block — pins inter-layer activations to
    # batch-over-dp / replicated-over-mp so GSPMD derives the per-block
    # head/mlp reshard from the weight shardings alone.
    constrain: Optional[Callable] = None
    # Paged-attention seam (the continuous-batching decode plane): the
    # gather-through-page-table attention used when ``paged_cache`` is
    # passed with a ``page_table`` — ``ops.pallas_paged_attention
    # .make_paged_attn_fn`` resolves Pallas-on-TPU / XLA-gather-elsewhere;
    # None defaults to the XLA reference.
    paged_attn_fn: Optional[Callable] = None
    # Packed-learner seam (the pad-free training plane, ISSUE 15): the
    # segment-blocked causal self-attention used when ``segment_ids`` is
    # passed — ``ops.pallas_attention.make_segment_attn_fn`` resolves
    # Pallas-flash-on-TPU / None-elsewhere; None builds the dense
    # :func:`packed_attention_mask` and rides ``_masked_attention``.
    segment_attn_fn: Optional[Callable] = None
    # The block kind (norm, positions, q/k norm, head size, FFN) as data;
    # the default is the GPT-2 block.  ``block_spec(family, ...)`` names
    # the families the program's arguments can choose.  What the whole
    # model shares is read from here: norm, positions, attention kind.
    block: BlockSpec = BlockSpec()
    # The stack as a per-layer list (``layer_specs``); empty: ``block``,
    # ``num_layers`` times.  The layers share ``block``'s attention kind.
    layers: Tuple[BlockSpec, ...] = ()
    # Multi-token-prediction modules (0 | 1): a layer of ``block``'s kind
    # with weights of its own under ``mtp/``, which a forward called with
    # ``mtp=True`` (the packed learner's) runs and no other does.
    mtp_layers: int = 0

    @property
    def head_dim(self) -> int:
        """The head size of q and k (of an ``mla`` block: both parts)."""
        if self.block.attention == "mla":
            return self.block.qk_nope_head_dim + self.block.qk_rope_head_dim
        return self.block.head_dim or self.d_model // self.num_heads

    @property
    def layer_specs(self) -> Tuple[BlockSpec, ...]:
        specs = self.layers or (self.block,) * self.num_layers
        if len(specs) != self.num_layers or any(
            s.attention != self.block.attention for s in specs
        ):
            raise ValueError(
                f"{len(specs)} layer specs for num_layers={self.num_layers}, "
                "or a layer whose attention kind is not the model's"
            )
        return specs

    @property
    def routed_layers(self) -> int:
        """Layers of the stack with a router (the MTP module's not among
        them: generation never runs it)."""
        return sum(s.ffn == "experts" for s in self.layer_specs)

    def init_paged_cache(self, num_pages: int, page_size: int, dtype=jnp.float32):
        """Zeroed page pools of the kind and number this model's blocks
        cache into (page 0 = the never-read null page): the cache is
        described by the model, and everything that holds it (the engine,
        its fork and its programs) treats it as one pytree of ``[num_pages,
        page_size, width]`` pools."""
        spec = self.block
        if spec.attention == "mla":
            # one latent pool an attention: one a plain layer, two a
            # shortcut-connected double layer
            width = latent_pool_width(spec.kv_lora_rank + spec.qk_rope_head_dim)
            pools = sum(_latent_pools(s) for s in self.layer_specs)
            return LatentKVCache(
                rows=tuple(
                    jnp.zeros((num_pages, page_size, width), dtype)
                    for _ in range(pools)
                )
            )
        return init_paged_kv_cache(
            num_pages, page_size, self.num_layers, self.num_heads,
            self.head_dim, dtype,
        )

    @nn.compact
    def __call__(
        self,
        obs: jnp.ndarray,
        positions: Optional[jnp.ndarray] = None,
        attn_mask: Optional[jnp.ndarray] = None,
        paged_cache: Optional[PagedKVCache] = None,
        page_ids: Optional[jnp.ndarray] = None,
        page_offsets: Optional[jnp.ndarray] = None,
        page_table: Optional[jnp.ndarray] = None,
        attn_lengths: Optional[jnp.ndarray] = None,
        prefix_starts: Optional[jnp.ndarray] = None,
        segment_ids: Optional[jnp.ndarray] = None,
        mtp: bool = False,
    ):
        """Full forward, masked full forward, or paged incremental step.

        - ``attn_mask=None``: the original whole-trajectory forward (causal
          ``attn_fn``) returning :class:`TransformerOutput`.
        - ``attn_mask=[B, T, T]``: full forward under an explicit mask
          (:func:`sequence_attention_mask`) — the learner pass over
          left-padded generated sequences.
        - ``paged_cache=PagedKVCache`` (the continuous-batching plane):
          scatter this call's k/v into pool pages at ``(page_ids[b, t],
          page_offsets[b, t])``.  With ``attn_mask=[B, T, T]`` and no
          ``page_table`` this is paged *prefill* over RIGHT-padded compact
          prompts (:func:`prompt_attention_mask` — attention is local, the
          pool is write-only); with ``page_table=[B, M]`` +
          ``attn_lengths=[B]`` and ``T = 1`` it is paged *decode*
          (attention gathers through the table); with ``page_table`` +
          ``prefix_starts=[B]`` it is the shared-table *tail prefill*
          over a cached prefix (the prefix-cache path — see
          :class:`_Block`).  Returns
          ``(TransformerOutput, new_paged_cache)``.  Same params as every
          other path.
        - ``segment_ids=[B, S]`` (the pad-free packed learner, ISSUE 15):
          full forward over PACKED rows holding several independent
          sequences — tokens attend causally WITHIN their own nonzero
          segment only.  Callers pass per-segment ``positions`` (reset to
          0 at every segment start, ``genrl/rollout.py``).  With
          ``segment_attn_fn`` set the blocks ride the Pallas segment
          flash kernel; otherwise the dense
          :func:`packed_attention_mask` feeds the existing masked path.
          Same params as every other path.
        - ``mtp=True`` (a model with ``mtp_layers``, no cache): also run
          the multi-token-prediction module over the same rows and return
          its logits as ``mtp_logits`` (:class:`_MTPModule`).
        """
        B, T = obs.shape[:2]
        spec = self.block
        specs = self.layer_specs
        if T > self.max_len and spec.positions == "learned":
            # out-of-range gathers clamp silently under jit, which would
            # alias every late position onto one embedding
            raise ValueError(
                f"sequence length {T} exceeds max_len={self.max_len}"
            )
        if not self.is_initializing():  # a program's trace, not the weights' making
            _note_layers(
                tuple(obs.shape), tuple(f"{s.layer}/{s.ffn}" for s in specs),
                spec.attention, spec.experts_held or spec.num_experts,
                spec.num_experts, self.mtp_layers,
            )
        attn = self.attn_fn
        if attn is None:
            base = flash_attention if self.use_flash else full_attention
            attn = lambda q, k, v: base(q, k, v, causal=True)  # noqa: E731
        run_mtp = bool(self.mtp_layers) and (mtp or self.is_initializing())
        if run_mtp:
            # whether position i's next token is of i's own sequence
            has_next = jnp.arange(T)[None, :] < T - 1
            if segment_ids is not None:
                seg = segment_ids.astype(jnp.int32)
                has_next = has_next & (seg > 0) & (jnp.roll(seg, -1, axis=1) == seg)
            has_next = jnp.broadcast_to(has_next, (B, T))
        if segment_ids is not None and self.segment_attn_fn is None:
            # dense packed fallback: ONE [B, S, S] mask shared by every
            # block — the XLA reference path and the off-TPU shape
            attn_mask = packed_attention_mask(segment_ids)
            segment_ids = None
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(T), (B, T))
        c = self.constrain if self.constrain is not None else (lambda x: x)
        if self.vocab_size is not None:
            embed = nn.Embed(
                self.vocab_size, self.d_model, name="token_embed",
                dtype=self.dtype, param_dtype=self.param_dtype,
            )
            x = embed(obs.astype(jnp.int32))
        else:
            x = nn.Dense(
                self.d_model, name="obs_embed",
                dtype=self.dtype, param_dtype=self.param_dtype,
            )(obs.reshape(B, T, -1).astype(self.dtype))
        rotary = None
        if spec.attention == "mla":
            rotary = rotary_fn(
                positions, spec.qk_rope_head_dim, spec.rope_theta,
                spec.rope_pairing,
            )
        elif spec.positions == "rope":
            rotary = rotary_fn(positions, self.head_dim, spec.rope_theta)
        else:
            pos_tab = self.param(
                "pos_embed",
                nn.initializers.normal(0.02),
                (self.max_len, self.d_model),
                self.param_dtype,
            )
            x = x + pos_tab[positions].astype(self.dtype)
        x = c(x)
        latent = spec.attention == "mla"
        pools = []  # what each layer wrote: (k, v), one latent pool, or two
        at = 0  # the layer's first pool among the latent cache's rows
        for i, layer in enumerate(specs):
            common = dict(
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                paged_attn_fn=self.paged_attn_fn,
                segment_attn_fn=self.segment_attn_fn,
                spec=layer,
                rotary=rotary,
                name=f"block_{i}",
            )
            if layer.layer == "scmoe":
                block = _ShortcutBlock(self.d_model, self.num_heads, attn, **common)
            else:
                block = _Block(
                    self.d_model, self.num_heads, self.mlp_ratio, attn, **common
                )
            if paged_cache is not None:
                if not latent:
                    cache = (paged_cache.k[i], paged_cache.v[i])
                elif layer.layer == "scmoe":
                    cache = paged_cache.rows[at : at + 2]
                else:
                    cache = paged_cache.rows[at]
                at += _latent_pools(layer)
                x, written = block(
                    x,
                    attn_mask=attn_mask,
                    paged_cache=cache,
                    page_ids=page_ids,
                    page_offsets=page_offsets,
                    page_table=page_table,
                    attn_lengths=attn_lengths,
                    prefix_starts=prefix_starts,
                )
                pools.append(written)
            elif segment_ids is not None:
                x = block(x, segment_ids=segment_ids)
            else:
                x = block(x, attn_mask=attn_mask)
            x = c(x)
        final_norm = functools.partial(_norm, spec, jnp.float32)
        policy_head = nn.Dense(self.num_actions, name="policy_head")
        mtp_logits = None
        if run_mtp:
            # the module reads the trunk's last layer output and the shared
            # embedding of each position's next token, and is scored by the
            # shared head behind a norm of its own
            call = (
                dict(segment_ids=segment_ids) if segment_ids is not None
                else dict(attn_mask=attn_mask)
            )
            y = _MTPModule(
                self.d_model, self.num_heads, self.mlp_ratio, attn, spec,
                dtype=self.dtype, param_dtype=self.param_dtype,
                segment_attn_fn=self.segment_attn_fn, rotary=rotary, name="mtp",
            )(x, embed(jnp.roll(obs.astype(jnp.int32), -1, axis=1)), has_next, **call)
            mtp_logits = policy_head(
                final_norm("mtp_final_norm")(c(y).astype(jnp.float32))
            )
        x = final_norm("final_norm")(x.astype(jnp.float32))
        policy_logits = policy_head(x)
        baseline = nn.Dense(1, name="value_head")(x).squeeze(-1)
        out = TransformerOutput(policy_logits, baseline, mtp_logits)
        if paged_cache is None:
            return out
        if latent:
            return out, LatentKVCache(
                rows=tuple(
                    p for layer, w in zip(specs, pools)
                    for p in (w if layer.layer == "scmoe" else (w,))
                )
            )
        return out, PagedKVCache(
            k=tuple(k for k, _v in pools), v=tuple(v for _k, v in pools)
        )
