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
from typing import Callable, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from scalerl_tpu.models.routed_ffn import RoutedExperts
from scalerl_tpu.ops.pallas_attention import flash_attention
from scalerl_tpu.ops.pallas_paged_attention import (
    gather_pages,
    paged_attention_reference,
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
    """

    norm: str = "layernorm"  # layernorm | rmsnorm
    norm_eps: float = 1e-6
    positions: str = "learned"  # learned (a table added to the embedding) | rope
    rope_theta: float = 10000.0
    qk_norm: str = "none"  # none | rmsnorm (over the projection's whole width)
    head_dim: Optional[int] = None  # None: d_model // num_heads
    ffn: str = "mlp"  # mlp (GELU, mlp_ratio x d_model) | experts (routed SwiGLU)
    num_experts: int = 0
    experts_per_token: int = 0
    expert_width: int = 0
    norm_topk_prob: bool = False


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
) -> BlockSpec:
    """The block a named family stacks; the sizes only the family reads
    are ignored by the others (``gpt2`` keeps its own epsilon)."""
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
    raise ValueError(f"block family must be gpt2 | olmoe, got {family!r}")


class TransformerOutput(NamedTuple):
    policy_logits: jnp.ndarray  # [B, T, num_actions]
    baseline: jnp.ndarray  # [B, T]


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


def rotary_fn(positions: jnp.ndarray, head_dim: int, theta: float) -> Callable:
    """``x [B, T, H, D] -> x`` rotated to ``positions [B, T]``: the
    rotate-half pairing (feature ``i`` with ``i + D/2``), ``inv_freq_i =
    theta^(-2i/D)``, angle ``position x inv_freq``; computed in float32
    and rounded once.  The angles are made once a forward and shared by
    every block."""
    half = head_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / head_dim)
    angle = positions.astype(jnp.float32)[:, :, None, None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)

    def rotate(x):
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
        return out.astype(x.dtype)

    return rotate


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
        new_cache = None
        if paged_cache is not None:
            kp, vp = paged_cache
            # flat single-axis scatter (page_id * page_size + offset) of
            # H*D rows into the lane-dense pool: the reshape is a bitcast
            # and XLA:CPU lowers 1-level row scatters measurably faster
            # than the 2-level fancy-index form
            N, ps, width = kp.shape
            flat_idx = (page_ids * ps + page_offsets).reshape(B * T)
            kp = (
                kp.reshape(N * ps, width)
                .at[flat_idx]
                .set(k.astype(kp.dtype).reshape(B * T, width))
                .reshape(kp.shape)
            )
            vp = (
                vp.reshape(N * ps, width)
                .at[flat_idx]
                .set(v.astype(vp.dtype).reshape(B * T, width))
                .reshape(vp.shape)
            )
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
            h = RoutedExperts(
                spec.num_experts, spec.experts_per_token, spec.expert_width,
                spec.norm_topk_prob, name="experts", **dt,
            )(h)
        else:
            h = nn.Dense(self.mlp_ratio * self.d_model, name="mlp_in", **dt)(h)
            h = nn.gelu(h)
            h = nn.Dense(self.d_model, name="mlp_out", **dt)(h)
        x = x + h
        if new_cache is not None:
            return x, new_cache
        return x


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
    # the families the program's arguments can choose.
    block: BlockSpec = BlockSpec()

    @property
    def head_dim(self) -> int:
        return self.block.head_dim or self.d_model // self.num_heads

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
        """
        B, T = obs.shape[:2]
        spec = self.block
        if T > self.max_len and spec.positions == "learned":
            # out-of-range gathers clamp silently under jit, which would
            # alias every late position onto one embedding
            raise ValueError(
                f"sequence length {T} exceeds max_len={self.max_len}"
            )
        attn = self.attn_fn
        if attn is None:
            base = flash_attention if self.use_flash else full_attention
            attn = lambda q, k, v: base(q, k, v, causal=True)  # noqa: E731
        if segment_ids is not None and self.segment_attn_fn is None:
            # dense packed fallback: ONE [B, S, S] mask shared by every
            # block — the XLA reference path and the off-TPU shape
            attn_mask = packed_attention_mask(segment_ids)
            segment_ids = None
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(T), (B, T))
        c = self.constrain if self.constrain is not None else (lambda x: x)
        if self.vocab_size is not None:
            x = nn.Embed(
                self.vocab_size, self.d_model, name="token_embed",
                dtype=self.dtype, param_dtype=self.param_dtype,
            )(obs.astype(jnp.int32))
        else:
            x = nn.Dense(
                self.d_model, name="obs_embed",
                dtype=self.dtype, param_dtype=self.param_dtype,
            )(obs.reshape(B, T, -1).astype(self.dtype))
        rotary = None
        if spec.positions == "rope":
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
        new_k = []
        new_v = []
        for i in range(self.num_layers):
            block = _Block(
                self.d_model,
                self.num_heads,
                self.mlp_ratio,
                attn,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                paged_attn_fn=self.paged_attn_fn,
                segment_attn_fn=self.segment_attn_fn,
                spec=spec,
                rotary=rotary,
                name=f"block_{i}",
            )
            if paged_cache is not None:
                x, (bk, bv) = block(
                    x,
                    attn_mask=attn_mask,
                    paged_cache=(paged_cache.k[i], paged_cache.v[i]),
                    page_ids=page_ids,
                    page_offsets=page_offsets,
                    page_table=page_table,
                    attn_lengths=attn_lengths,
                    prefix_starts=prefix_starts,
                )
                new_k.append(bk)
                new_v.append(bv)
            elif segment_ids is not None:
                x = block(x, segment_ids=segment_ids)
            else:
                x = block(x, attn_mask=attn_mask)
            x = c(x)
        x = _norm(spec, jnp.float32, "final_norm")(x.astype(jnp.float32))
        policy_logits = nn.Dense(self.num_actions, name="policy_head")(x)
        baseline = nn.Dense(1, name="value_head")(x).squeeze(-1)
        out = TransformerOutput(policy_logits, baseline)
        if paged_cache is not None:
            return out, PagedKVCache(k=tuple(new_k), v=tuple(new_v))
        return out
