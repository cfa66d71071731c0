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
import math
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from scalerl_tpu.models.routed_ffn import MLPRouter, RoutedExperts
from scalerl_tpu.ops.pallas_attention import flash_attention
from scalerl_tpu.ops.pallas_gdn import gdn_decode_update_pallas, heads_per_block
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


class RopeScaling(NamedTuple):
    """YaRN's numbers, as a ``rope_scaling`` block of the DeepSeek family
    gives them (:func:`yarn_terms` turns them into frequencies and the
    two factors)."""

    factor: float
    original_max: int  # original_max_position_embeddings
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


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

    ``layer="mixer"`` is a layer of ONE mixer (:class:`_MixerBlock`: one
    norm, one residual add), ``mixer`` saying which: ``mamba`` (a Mamba-2
    state-space mixer of the ``ssm_*`` sizes, :class:`_Mamba2Mixer`),
    ``attention`` (this spec's attention alone), ``experts`` (the routed
    experts and their shared expert alone) or ``ffn`` (a dense FFN of
    ``ffn_hidden`` alone); :func:`pattern_specs` lays a stack of them out
    by a pattern string.  ``kv_heads`` key/value heads serve ``num_heads``
    query heads (query head ``i`` reads head ``i // (heads / kv_heads)``;
    0: one each), projected apart from ``q``; ``positions="none"`` gives
    the attention no position signal at all (a stack whose state-space
    layers carry the order).  ``expert_act="relu2"`` makes every expert
    and dense FFN of the spec ``relu(h W_up)^2 W_down``, two matrices and
    no gate; ``shared_width`` is the always-on expert's own width.

    A PLAIN layer's mixer may be recurrent too: ``mixer="gdn"`` puts a
    Gated DeltaNet (:class:`_GatedDeltaMixer`) where the attention sits
    (``"attention"`` or ``""``: attention), and :func:`interval_specs`
    lays such a stack out.  The rule reuses the ``ssm_*`` sizes, whose
    meaning is the same: ``ssm_heads`` value heads of ``ssm_head_dim``,
    ``ssm_groups`` key heads (key head ``j`` serves value heads ``j x
    heads / groups`` on, as a Mamba group does) of ``ssm_state`` features,
    so that a head's state is ``[ssm_state, ssm_head_dim]`` (key x value);
    ``ssm_conv`` taps, ``ssm_chunk`` tokens a chunk.  That family's
    attention: ``qk_norm="head"`` norms q and k over each head's own
    features, ``rotary_dim`` rotates a head's first features alone (0:
    all), ``attn_gate`` has the query projection carry a sigmoid gate of
    the attention's output, ``shared_gate`` puts the shared expert behind
    a sigmoid scalar, and ``norm_zero_centered`` stores every RMSNorm
    scale as ``w`` in ``1 + w``.

    ``attention="cca"`` is compressed convolutional attention
    (:class:`_CompressedConvAttention`, the ``zaya`` family): an attention
    with K and V pages like ``mha``'s AND a lane-indexed window, because
    its queries and keys are convolved over the ``cca_time0 + cca_time1 -
    2`` tokens before them (a depthwise convolution of ``cca_time0`` taps,
    then a grouped one of ``cca_time1`` that mixes a head's channels) and
    half of its values are the previous token's.  Such a layer owns one
    ``k``, one ``v`` and one ``conv`` array and no ``ssm``.
    ``router="mlp"`` puts :class:`~scalerl_tpu.models.routed_ffn.MLPRouter`
    (``router_width`` wide) in front of the routed experts: its state goes
    up the stack from layer to layer beside the residual stream (the layer
    contract's ``r``).  ``residual_scale`` gives both sides of every
    residual add a learned scale and bias: ``(s x + c) + (s' y + c')``.

    ``streams > 1`` (the ``xing4`` family) makes the residual stream a
    STREAM OF ROWS, ``[B, T, streams, d]`` where every other family's is
    ``[B, T, d]``: manifold-constrained hyper-connections.  The model
    copies a token's embedding into every row after the embedding and sums
    the rows before the final norm; in between every sublayer of a plain
    layer has a :class:`_HyperMix` of its own, which READS the sublayer's
    one-row input as a learned, input-dependent mix of the rows and WRITES
    its output back by a ``streams x streams`` matrix on the rows
    (``hc_iters`` Sinkhorn iterations from ``exp`` of a logit clipped to
    ``hc_clamp``, ``hc_eps`` in every denominator: doubly stochastic) plus
    a gated copy of the output into each row.  Mixers, FFNs, caches and
    kernels see ``[B, T, d]`` as ever; with ``streams == 1`` nothing of
    this is built and the tree and the program are the one-row ones.
    ``rope_scaling`` (:class:`RopeScaling`): the rotary frequencies are
    YaRN's blend and the latent attention's softmax scale carries
    ``m^2`` (:func:`yarn_terms`, :func:`rotary_fn`); None: plain rotary.
    """

    norm: str = "layernorm"  # layernorm | rmsnorm
    norm_eps: float = 1e-6
    positions: str = "learned"  # learned (a table added to the embedding) | rope | none
    rope_theta: float = 10000.0
    qk_norm: str = "none"  # none | rmsnorm (over the projection's whole width) | head (each head's)
    head_dim: Optional[int] = None  # None: d_model // num_heads
    ffn: str = "mlp"  # mlp (GELU, mlp_ratio x d_model) | experts (routed SwiGLU) | swiglu
    num_experts: int = 0
    experts_per_token: int = 0
    expert_width: int = 0
    norm_topk_prob: bool = False
    attention: str = "mha"  # mha | mla | cca
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_pairing: str = "half"  # half (i with i + D/2) | interleaved (2i with 2i + 1)
    layer: str = "plain"  # plain (attention, FFN) | scmoe | mixer (one mixer alone)
    ffn_hidden: int = 0
    zero_experts: int = 0
    experts_held: int = 0  # 0: every routed expert
    first_expert: int = 0
    router_bias: bool = False
    routed_scaling: float = 1.0
    mla_scale: bool = False
    scoring: str = "softmax"  # softmax | sigmoid (the router's, over all outputs)
    shared_experts: int = 0
    kv_heads: int = 0  # 0: as many as query heads
    expert_act: str = "swiglu"  # swiglu | relu2 (experts, shared expert, dense FFN)
    shared_width: int = 0  # 0: shared_experts x expert_width
    # of a mixer layer: mamba | attention | experts | ffn; of a plain
    # layer: gdn | attention ("": attention)
    mixer: str = ""
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 128
    rotary_dim: int = 0  # a head's leading features that rotate (0: all)
    attn_gate: bool = False
    shared_gate: bool = False
    norm_zero_centered: bool = False
    cca_time0: int = 0  # taps of a cca attention's depthwise convolution ...
    cca_time1: int = 0  # ... and of its grouped one
    router: str = "linear"  # linear (one matrix) | mlp (MLPRouter, router_width wide)
    router_width: int = 0
    residual_scale: bool = False
    streams: int = 1  # rows of the residual stream (hc_mult); 1: one row, a plain add
    hc_iters: int = 0  # Sinkhorn iterations of a stream's row-mixing matrix
    hc_eps: float = 1e-6
    hc_clamp: Tuple[float, float] = (-30.0, 30.0)  # on the matrix's logit, before exp
    rope_scaling: Optional[RopeScaling] = None

    @property
    def residual(self) -> str:
        """The residual path as ``model.layers`` notes it."""
        if self.streams > 1:
            return f"mhc{self.streams}"
        return "scaled" if self.residual_scale else "add"

    @property
    def recurrent(self) -> bool:
        """Whether the layer's mixer is a recurrence (its state: ``ssm``)."""
        return self.mixer in ("mamba", "gdn")

    @property
    def lane_state(self) -> bool:
        """Whether a lane carries rows of this layer: arrays indexed by
        lane, which no page table describes (a recurrent mixer's state
        and window, a ``cca`` attention's window beside its pages)."""
        return bool(self.owns.keys() & _LANE_FIELDS)

    @property
    def state_shape(self) -> Tuple[int, ...]:
        """A lane's recurrent state in this layer: Mamba-2's ``[heads,
        head_dim, state]``, the delta rule's ``[heads, key, value]``."""
        if self.mixer == "gdn":
            return (self.ssm_heads, self.ssm_state, self.ssm_head_dim)
        return (self.ssm_heads, self.ssm_head_dim, self.ssm_state)

    @property
    def conv_channels(self) -> int:
        """What the layer's causal convolution runs over: Mamba-2's ``[x |
        B | C]``, the delta rule's ``[q | k | v]``."""
        return self.ssm_heads * self.ssm_head_dim + 2 * self.ssm_groups * self.ssm_state

    @property
    def owns(self) -> Dict[str, int]:
        """How many arrays of each of :class:`ModelCache`'s fields the
        layer caches into: a recurrent mixer one state and one window of
        taps, an attention one K and one V pool (or one latent pool, and
        the double layer's two attentions two; a ``cca`` attention its
        two pools AND one window), any other mixer none."""
        if self.recurrent:
            return {"ssm": 1, "conv": 1}
        if self.layer == "mixer" and self.mixer != "attention":
            return {}
        if self.attention == "mla":
            return {"rows": 2 if self.layer == "scmoe" else 1}
        if self.attention == "cca":
            return {"k": 1, "v": 1, "conv": 1}
        return {"k": 1, "v": 1}

    @property
    def kind(self) -> str:
        """The layer as ``model.layers`` notes it: its layer kind and what
        fills it (a plain layer that names its mixer: the mixer, then the
        FFN)."""
        if self.layer == "mixer":
            return f"{self.layer}/{self.mixer}"
        if self.mixer or self.attention == "cca":
            return f"{self.layer}/{self.mixer or self.attention}/{self.ffn}"
        return f"{self.layer}/{self.ffn}"


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
    kv_heads: int = 0,
    expert_act: str = "swiglu",
    shared_width: int = 0,
    ssm_heads: int = 0,
    ssm_head_dim: int = 0,
    ssm_state: int = 0,
    ssm_groups: int = 1,
    ssm_conv: int = 4,
    ssm_chunk: int = 128,
    rotary_dim: int = 0,
    cca_time0: int = 0,
    cca_time1: int = 0,
    router_width: int = 0,
    streams: int = 1,
    hc_iters: int = 0,
    hc_eps: float = 1e-6,
    hc_clamp: Tuple[float, float] = (-30.0, 30.0),
    rope_scaling: Optional[RopeScaling] = None,
) -> BlockSpec:
    """The block a named family stacks; the sizes only the family reads
    are ignored by the others (``gpt2`` keeps its own epsilon).  For a
    family whose stack has more than one kind of layer this is the kind
    that repeats (``joyai``: the routed layer) and :func:`layer_specs`
    gives the stack; for ``nemotron_h`` it is the expert layer, and
    :func:`pattern_specs` gives the stack; for ``qwen3_next`` it is the
    full-attention layer, and :func:`interval_specs` gives the stack;
    ``zaya``'s stack is one kind of layer; ``xing4`` is ``joyai``'s
    routed layer (and :func:`layer_specs` its stack) on a residual stream
    of ``streams`` rows, with YaRN's ``rope_scaling``."""
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
    if family == "xing4":
        if streams < 2 or hc_iters < 1 or hc_eps <= 0 or not hc_clamp[0] < hc_clamp[1]:
            raise ValueError(
                "the xing4 block needs a residual stream of 2 rows or more, 1 "
                "Sinkhorn iteration or more, a positive epsilon and a clamp "
                f"(min < max), got {streams}/{hc_iters}/{hc_eps}/{hc_clamp}"
            )
        if rope_scaling is not None and not (
            rope_scaling.factor >= 1
            and rope_scaling.original_max >= 1
            and rope_scaling.beta_fast > rope_scaling.beta_slow > 0
        ):
            raise ValueError(
                "YaRN needs a factor of 1 or more, the original context "
                f"length and beta_fast > beta_slow > 0, got {rope_scaling}"
            )
        base = block_spec(
            "joyai", norm_eps=norm_eps, rope_theta=rope_theta,
            num_experts=num_experts, experts_per_token=experts_per_token,
            expert_width=expert_width, norm_topk_prob=norm_topk_prob,
            q_lora_rank=q_lora_rank, kv_lora_rank=kv_lora_rank,
            qk_nope_head_dim=qk_nope_head_dim, qk_rope_head_dim=qk_rope_head_dim,
            v_head_dim=v_head_dim, ffn_hidden=ffn_hidden,
            experts_held=experts_held, first_expert=first_expert,
            routed_scaling=routed_scaling, scoring=scoring,
            shared_experts=shared_experts,
        )
        return dataclasses.replace(
            base, streams=streams, hc_iters=hc_iters, hc_eps=hc_eps,
            hc_clamp=(float(hc_clamp[0]), float(hc_clamp[1])),
            rope_scaling=rope_scaling,
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
    if family == "nemotron_h":
        held = experts_held or num_experts
        sizes = (
            head_dim or 0, expert_width, ssm_heads, ssm_head_dim, ssm_state,
            ssm_groups, ssm_chunk,
        )
        if min(sizes) < 1 or ssm_conv < 2 or ssm_heads % ssm_groups:
            raise ValueError(
                "the nemotron_h stack needs a head size, an expert width and "
                "its state-space sizes (heads a multiple of the groups, a "
                f"convolution of 2 taps or more), got {sizes}/{ssm_conv}"
            )
        if not (
            1 <= experts_per_token <= num_experts
            and 0 <= first_expert
            and 1 <= held
            and first_expert + held <= num_experts
            and shared_experts >= 0
            and kv_heads >= 0
            and scoring in ("softmax", "sigmoid")
            and expert_act in ("swiglu", "relu2")
        ):
            raise ValueError(
                "the nemotron_h router picks experts_per_token of num_experts "
                "by a softmax or sigmoid score and holds experts first_expert "
                ".. first_expert + experts_held beside shared_experts "
                f"always-on ones, got {experts_per_token}/{num_experts}/"
                f"{scoring}/{first_expert}/{held}/{shared_experts}/{expert_act}"
            )
        return BlockSpec(
            norm="rmsnorm", norm_eps=norm_eps, positions="none",
            head_dim=head_dim, ffn="experts", num_experts=num_experts,
            experts_per_token=experts_per_token, expert_width=expert_width,
            norm_topk_prob=norm_topk_prob, layer="mixer", mixer="experts",
            ffn_hidden=ffn_hidden, experts_held=held,
            first_expert=first_expert, router_bias=True,
            routed_scaling=routed_scaling, scoring=scoring,
            shared_experts=shared_experts, kv_heads=kv_heads,
            expert_act=expert_act, shared_width=shared_width,
            ssm_heads=ssm_heads, ssm_head_dim=ssm_head_dim,
            ssm_state=ssm_state, ssm_groups=ssm_groups, ssm_conv=ssm_conv,
            ssm_chunk=ssm_chunk,
        )
    if family == "qwen3_next":
        held = experts_held or num_experts
        sizes = (
            head_dim or 0, expert_width, ssm_heads, ssm_head_dim, ssm_state,
            ssm_groups, ssm_chunk,
        )
        if min(sizes) < 1 or ssm_conv < 2 or ssm_heads % ssm_groups:
            raise ValueError(
                "the qwen3_next stack needs a head size, an expert width and "
                "its delta-rule sizes (value heads a multiple of the key "
                f"heads, a convolution of 2 taps or more), got {sizes}/{ssm_conv}"
            )
        if not (
            1 <= experts_per_token <= num_experts
            and 0 <= first_expert
            and 1 <= held
            and first_expert + held <= num_experts
            and shared_experts >= 0
            and kv_heads >= 0
            and rotary_dim >= 0
            and rotary_dim % 2 == 0
            and rotary_dim <= (head_dim or 0)
        ):
            raise ValueError(
                "the qwen3_next router picks experts_per_token of num_experts "
                "and holds experts first_expert .. first_expert + experts_held "
                "beside shared_experts gated always-on ones, and rotates an "
                "even rotary_dim of a head's features, got "
                f"{experts_per_token}/{num_experts}/{first_expert}/{held}/"
                f"{shared_experts}/{rotary_dim}"
            )
        return BlockSpec(
            norm="rmsnorm", norm_eps=norm_eps, positions="rope",
            rope_theta=rope_theta, qk_norm="head", head_dim=head_dim,
            ffn="experts", num_experts=num_experts,
            experts_per_token=experts_per_token, expert_width=expert_width,
            norm_topk_prob=norm_topk_prob, experts_held=held,
            first_expert=first_expert, shared_experts=shared_experts,
            kv_heads=kv_heads, shared_width=shared_width, mixer="attention",
            ssm_heads=ssm_heads, ssm_head_dim=ssm_head_dim,
            ssm_state=ssm_state, ssm_groups=ssm_groups, ssm_conv=ssm_conv,
            ssm_chunk=ssm_chunk, rotary_dim=rotary_dim, attn_gate=True,
            shared_gate=bool(shared_experts), norm_zero_centered=True,
        )
    if family == "zaya":
        sizes = (head_dim or 0, expert_width, router_width)
        if min(sizes) < 1 or min(cca_time0, cca_time1) < 2:
            raise ValueError(
                "the zaya block needs a head size, an expert width, a router "
                "width and two convolutions of 2 taps or more, got "
                f"{sizes}/{cca_time0}/{cca_time1}"
            )
        if not (
            1 <= experts_per_token <= num_experts
            and kv_heads >= 1
            and kv_heads % 2 == 0
            and rotary_dim >= 0
            and rotary_dim % 2 == 0
            and rotary_dim <= (head_dim or 0)
        ):
            raise ValueError(
                "the zaya router picks experts_per_token of num_experts, its "
                "values' two halves (this token's, the previous one's) are cut "
                "into an even number of kv_heads, and an even rotary_dim of a "
                "head's features rotates, got "
                f"{experts_per_token}/{num_experts}/{kv_heads}/{rotary_dim}"
            )
        return BlockSpec(
            norm="rmsnorm", norm_eps=norm_eps, positions="rope",
            rope_theta=rope_theta, head_dim=head_dim, ffn="experts",
            num_experts=num_experts, experts_per_token=experts_per_token,
            expert_width=expert_width, norm_topk_prob=norm_topk_prob,
            attention="cca", router_bias=True, kv_heads=kv_heads,
            rotary_dim=rotary_dim, cca_time0=cca_time0, cca_time1=cca_time1,
            router="mlp", router_width=router_width, residual_scale=True,
        )
    raise ValueError(
        "block family must be gpt2 | olmoe | longcat | joyai | nemotron_h | "
        f"qwen3_next | zaya | xing4, got {family!r}"
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


_PATTERN_MIXERS = {"M": "mamba", "E": "experts", "*": "attention", "-": "ffn"}


def pattern_specs(spec: BlockSpec, pattern: str) -> Tuple[BlockSpec, ...]:
    """A stack of single-mixer layers laid out by the ``nemotron_h``
    family's pattern string, a character a layer: ``M`` a Mamba-2 mixer,
    ``E`` the routed experts, ``*`` attention, ``-`` a dense FFN of
    ``ffn_hidden``.  ``spec`` is the family's expert layer."""
    unknown = sorted(set(pattern) - set(_PATTERN_MIXERS))
    if not pattern or unknown or spec.layer != "mixer":
        raise ValueError(
            "a layer pattern is a string of M | E | * | - over a mixer-layer "
            f"spec, got {pattern!r} (unknown {unknown}) over {spec.layer!r}"
        )
    if "-" in pattern and spec.ffn_hidden < 1:
        raise ValueError("a '-' layer needs ffn_hidden")
    bare = dataclasses.replace(
        spec, ffn="none", num_experts=0, experts_per_token=0, expert_width=0,
        experts_held=0, first_expert=0, router_bias=False, routed_scaling=1.0,
        norm_topk_prob=False, scoring="softmax", shared_experts=0,
        shared_width=0,
    )
    kinds = {
        "E": spec,
        **{
            ch: dataclasses.replace(bare, mixer=_PATTERN_MIXERS[ch])
            for ch in "M*-"
        },
    }
    return tuple(kinds[ch] for ch in pattern)


def interval_specs(
    spec: BlockSpec, num_layers: int, full_attention_interval: int
) -> Tuple[BlockSpec, ...]:
    """A stack of plain layers whose mixer is ``spec``'s attention at
    every ``full_attention_interval``-th layer (layer ``i`` from 0 where
    ``(i + 1) % interval == 0``) and a Gated DeltaNet at the others: the
    ``qwen3_next`` family's ``L L L F`` at an interval of 4."""
    if full_attention_interval < 1 or spec.layer != "plain" or spec.ssm_heads < 1:
        raise ValueError(
            "full_attention_interval must be >= 1 over a plain-layer spec "
            f"with the delta rule's sizes, got {full_attention_interval} over "
            f"{spec.layer!r}/{spec.ssm_heads}"
        )
    linear = dataclasses.replace(spec, mixer="gdn")
    return tuple(
        spec if (i + 1) % full_attention_interval == 0 else linear
        for i in range(num_layers)
    )


class TransformerOutput(NamedTuple):
    policy_logits: jnp.ndarray  # [B, T, num_actions]
    baseline: jnp.ndarray  # [B, T]
    # [B, T, num_actions] of the multi-token-prediction module: position
    # i's distribution over token i + 2; only a forward called with
    # ``mtp=True`` on a model that carries a module has it
    mtp_logits: Optional[jnp.ndarray] = None


class ModelCache(NamedTuple):
    """What a model's layers cache into, one pytree whatever the stack: a
    tuple of arrays a kind, each in layer order and empty where the model
    has none of the kind (an empty tuple has no leaves: a program's
    arguments are the arrays the model has).  ``BlockSpec.owns`` says how
    many of each a layer owns, :meth:`TransformerPolicy.init_paged_cache`
    makes them, and a layer is handed, and hands back, a cache of its own
    arrays alone.

    **Pages** (``k``, ``v``, ``rows``): lane-dense ``[num_pages,
    page_size, width]`` pools shared by every lane.  ``k``/``v``: one of
    each an attention layer, ``width = kv_heads x head_dim``: a token's
    heads lie side by side on the minor axis, so the TPU runtime stores
    the pool row-major and the paged-decode kernel reads it in place
    (``ops/pallas_paged_attention.py``; a ``[.., H, D]`` pool with ``D <
    128`` was stored page-index-minor and copied whole, twice, by every
    decode program).  Consumers split the heads out of the rows they
    gathered, never out of the pool.  ``rows``: ONE pool a latent
    (``mla``) attention, of ``[c | rotated k_pe | zeros]`` rows (``width =
    latent_pool_width(kv_lora_rank + qk_rope_head_dim)``, 640 for the
    published 576), which every head shares and which holds the values
    too; one a plain layer, two a ``scmoe`` layer.  Lanes own *pages*, not
    contiguous rows: a host-side allocator (``genrl/paging.py``) hands
    each lane an ordered page list, and the decode path writes token ``p``
    of a lane into page ``table[p // page_size]`` at slot ``p %
    page_size``, so KV memory scales with LIVE tokens across all lanes
    instead of ``max_bucket x lanes`` (the vLLM shape).  Page 0 is the
    allocator's null page: dead-lane and pad writes are routed there and
    it is never read (every read is masked by a lane's true length).
    Sharing, forks and the prefix cache are page-index facts and do not
    see the kind.

    **Lanes** (``ssm``, ``conv``): what a layer carries from token to
    token beside pages, float32, indexed by LANE and of a size that does
    not depend on a lane's length: ``ssm [lanes, *spec.state_shape]`` (a
    Mamba-2 layer's ``[heads, head_dim, state]``, which
    :func:`ssm_decode_update` updates in place; a Gated DeltaNet layer's
    ``[heads, key, value]``, which :func:`gdn_decode_update` does) and
    ``conv [lanes, rows, channels]``, the last ``rows`` inputs of a
    layer's causal convolution (a recurrent mixer's ``taps - 1``).
    ``conv`` may stand without ``ssm``: a ``cca`` attention layer owns a
    ``k`` and a ``v`` pool AND one ``conv`` array, ``[lanes, rows x
    channels]`` with ``rows = cca_time0 + cca_time1 - 2`` and ``channels =
    (heads + kv_heads) x head_dim + kv_heads x head_dim / 2``, what its two
    convolutions and its value shift read of the tokens before this one:
    a RING of ``rows`` rows side by side on the minor axis, of which row
    ``p mod rows`` holds ``[q~ | k~ | h W_v2]`` of the token at position
    ``p`` (zeros before a sequence's start), so that a decoded token
    overwrites the oldest row where it lies and nothing shifts (a
    recurrent mixer's window is kept oldest first, ``[lanes, taps - 1,
    channels]``, and shifts by a row a token, which costs a copy of the
    window a layer a substep).
    Written by the prefill at the prompt's true length, updated in place
    by every decode substep and copied leader to member by the group fork
    (:func:`fork_cache`); a page table says nothing about it, so a
    prefix-cache hit and a page-cursor rollback cannot serve a model that
    has one.
    """

    k: Tuple[jnp.ndarray, ...] = ()
    v: Tuple[jnp.ndarray, ...] = ()
    rows: Tuple[jnp.ndarray, ...] = ()
    ssm: Tuple[jnp.ndarray, ...] = ()
    conv: Tuple[jnp.ndarray, ...] = ()


_PAGE_FIELDS = ("k", "v", "rows")  # the others are indexed by lane
_LANE_FIELDS = frozenset(ModelCache._fields) - frozenset(_PAGE_FIELDS)


def _layer_entries(cache: ModelCache, specs) -> list:
    """The cache cut into each layer's own arrays (``BlockSpec.owns``), a
    cache of them alone a layer, in layer order."""
    entries = []
    for spec in specs:
        own = [spec.owns.get(name, 0) for name in ModelCache._fields]
        entries.append(ModelCache(*(arrays[:n] for arrays, n in zip(cache, own))))
        cache = ModelCache(*(arrays[n:] for arrays, n in zip(cache, own)))
    return entries


def _join(entries) -> ModelCache:
    """The layers' entries, in layer order, as the model's cache again."""
    return ModelCache(*(sum(parts, ()) for parts in zip(*entries)))


def fork_cache(cache: ModelCache, src_page, dst_page, src_lane, dst_lane) -> ModelCache:
    """A group fork on a model's cache: pool pages ``src_page`` copied to
    ``dst_page`` (partial prompt pages; pad rows copy null to null) and
    lanes ``src_lane``'s rows of the lane-indexed state to ``dst_lane``'s
    (pad rows carry an out-of-range lane and drop)."""
    pages = lambda pool: pool.at[dst_page].set(pool[src_page])  # noqa: E731
    lanes = lambda st: st.at[dst_lane].set(st[src_lane], mode="drop")  # noqa: E731
    return ModelCache(*(
        tuple(map(pages if name in _PAGE_FIELDS else lanes, arrays))
        for name, arrays in zip(ModelCache._fields, cache)
    ))


# the arguments that spell each form, beside ``positions`` (and, for a
# prefill of a model whose lanes carry state, ``state_lanes``)
_FORMS = {
    "causal": (),
    "masked": ("attn_mask",),
    "packed": ("segment_ids",),
    "prefill": ("attn_mask", "page_ids", "page_offsets"),
    "tail": ("page_ids", "page_offsets", "page_table", "prefix_starts"),
    "decode": ("page_ids", "page_offsets", "page_table", "attn_lengths"),
}


@dataclasses.dataclass(frozen=True)
class Call:
    """How the model is being called: decided once, by :meth:`Call.of` at
    the top of :class:`TransformerPolicy`, from the keyword arguments
    that method takes.  ``mode`` is a Python string, fixed when a program
    is traced; every layer and mixer switches on it and reads the arrays
    its mode has.  One set of parameters serves all six:

    - ``causal``: the whole-trajectory forward, no argument but
      ``positions``; attention is the model's causal ``attn_fn``.
    - ``masked`` (``attn_mask [B, T, T]``, True = attend): a full forward
      under an explicit mask: the learner's pass over left-padded
      generated sequences (:func:`sequence_attention_mask`), explicit
      masked attention against the call's own k/v.
    - ``packed`` (``segment_ids [B, S]``, the pad-free learner): a full
      forward over rows holding several independent sequences; a token
      attends causally WITHIN its own nonzero segment, through the
      model's ``segment_attn_fn`` (the Pallas segment flash kernel, which
      skips cross-segment and pad blocks).  Callers pass per-segment
      ``positions`` (reset to 0 at every segment start,
      ``genrl/rollout.py``).  A model without that kernel runs the same
      rows as ``masked`` under the dense :func:`packed_attention_mask`,
      made here once for every layer.
    - the three on a cache (``paged_cache``, the continuous-batching
      plane): each scatters this call's keys and values (or latent rows)
      into pool pages first, token ``t`` of row ``b`` into ``(page_ids[b,
      t], page_offsets[b, t])``, dead-lane and pad writes routed to the
      null page by the caller, and the model returns ``(output, cache)``:

      - ``prefill`` (``attn_mask``, no ``page_table``): fresh RIGHT-padded
        prompts (:func:`prompt_attention_mask`); the whole context is in
        the call, so attention is local and the pool write-only.  On a
        model whose lanes carry state ``state_lanes [B]`` names the lanes
        whose state rows the prompts write, at their true lengths (an id
        out of range drops).
      - ``decode`` (``page_table [B, M]``, ``attn_lengths [B]``, ``T =
        1``, row ``b`` is lane ``b``): attention gathers through the
        table by the model's ``paged_attn_fn``; a recurrent layer updates
        every lane's state in place.
      - ``tail`` (``page_table``, ``prefix_starts [B]``): the
        shared-table tail prefill of the prefix-cache path: the ``T``
        tokens sit at positions ``prefix_starts[b] + t`` on top of a
        cached prefix whose K/V already lives in pages the table maps;
        attention gathers the WHOLE context (prefix and this chunk)
        through the table under a causal-from-start mask
        (:func:`_tail_mask`), plain XLA and no kernel, so sharing stays a
        page-table fact.  The speculative verify pass
        (``genrl/continuous.py``) rides this form with ``T`` = draft
        bucket + 1: the mask keeps rejected slots' K/V (garbage past the
        cursor) out of every query, so a draft rollback never touches the
        device.  A model whose lanes carry state has no such form: the
        state cannot be entered at a page boundary or rewound by a page
        cursor.

    On a model whose lanes carry state (``lane_state``: a recurrent layer,
    or a ``cca`` attention's window) ``real [B, T]`` says which tokens
    are real and ``runs [B, T]`` (:func:`run_ids`) where a recurrence, or
    a window, starts anew: the packed rows' segments, or the diagonal of a mask (a
    token that may attend itself is real); a ``causal`` call's rows are
    one run of real tokens each, and ``decode`` reads neither.
    """

    mode: str  # causal | masked | packed | prefill | tail | decode
    attn_mask: Optional[jnp.ndarray] = None
    segment_ids: Optional[jnp.ndarray] = None
    page_ids: Optional[jnp.ndarray] = None
    page_offsets: Optional[jnp.ndarray] = None
    page_table: Optional[jnp.ndarray] = None
    attn_lengths: Optional[jnp.ndarray] = None
    prefix_starts: Optional[jnp.ndarray] = None
    runs: Optional[jnp.ndarray] = None
    real: Optional[jnp.ndarray] = None
    state_lanes: Optional[jnp.ndarray] = None

    @property
    def paged(self) -> bool:
        """Whether the call runs on a cache (and the model returns one)."""
        return self.mode in ("prefill", "tail", "decode")

    @classmethod
    def of(
        cls, *, lane_state: bool, segment_kernel: bool, mtp: bool, paged_cache, **arrays
    ) -> "Call":
        """The form the arguments spell (``arrays``: the fields above that
        a caller passes, None where it did not), for a model whose lanes
        carry state (``lane_state``) or none and that has a
        ``segment_kernel`` or none; ``ValueError`` for a set that spells
        none of the six."""
        given = {name for name, a in arrays.items() if a is not None}
        if paged_cache is None:
            mode = "packed" if "segment_ids" in given else "masked" if "attn_mask" in given else "causal"
        else:
            mode = "prefill" if "page_table" not in given else "tail" if "prefix_starts" in given else "decode"
        takes = set(_FORMS[mode]) | ({"state_lanes"} if lane_state and mode == "prefill" else set())
        if mtp and paged_cache is not None:
            given.add("mtp")  # the module runs on no cache
        if given != takes:
            raise ValueError(
                f"a {mode} call {'on' if paged_cache is not None else 'without'} a "
                f"paged_cache takes {sorted(takes)} beside positions, got {sorted(given)}"
            )
        if lane_state and mode == "tail":
            raise ValueError(
                "a layer that carries lane state has no tail prefill over a "
                "cached prefix and no speculative verify: its state cannot be "
                "entered at a page boundary or rewound by a page cursor"
            )
        attn_mask, segment_ids = arrays["attn_mask"], arrays["segment_ids"]
        runs = real = None
        if lane_state and mode == "packed":
            real = segment_ids > 0
            runs = run_ids(segment_ids)
        elif lane_state and mode in ("masked", "prefill"):
            real = jnp.diagonal(attn_mask, axis1=1, axis2=2)
            runs = run_ids(real.astype(jnp.int32))
        if mode == "packed" and not segment_kernel:
            # ONE dense [B, S, S] mask shared by every layer: the XLA
            # reference path and the off-TPU shape
            mode = "masked"
            arrays.update(attn_mask=packed_attention_mask(segment_ids), segment_ids=None)
        return cls(mode, runs=runs, real=real, **arrays)


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
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Explicit masked attention: q ``[B, T, H, D]`` against k/v
    ``[B, S, H, D]`` with a ``[B, T, S]`` validity mask (True = attend);
    scores times ``scale`` (None: ``1 / sqrt(D)``).

    Scores/softmax run in float32 regardless of the compute dtype — the
    decode path feeds sampling logits, where bf16 softmax drift would show
    up directly in the behavior logprobs the learner's importance ratios
    divide by.  Fully-masked rows degrade to a uniform distribution (finite
    by construction) instead of NaN.
    """
    head_dim = q.shape[-1]
    if scale is None:
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
    (scale included) and rounded once to ``dtype``.  ``zero_centered``:
    the parameter is ``w`` in ``scale = 1 + w`` and starts at zero."""

    epsilon: float
    dtype: jnp.dtype = jnp.float32
    zero_centered: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        init = nn.initializers.zeros if self.zero_centered else nn.initializers.ones
        scale = self.param("scale", init, (x.shape[-1],), jnp.float32)
        if self.zero_centered:
            scale = 1.0 + scale
        x = x.astype(jnp.float32)
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return (x * lax.rsqrt(ms + self.epsilon) * scale).astype(self.dtype)


def _norm(spec: BlockSpec, dtype, name: Optional[str] = None) -> nn.Module:
    if spec.norm == "rmsnorm":
        if spec.norm_zero_centered:
            return RMSNorm(spec.norm_eps, dtype=dtype, zero_centered=True, name=name)
        return RMSNorm(spec.norm_eps, dtype=dtype, name=name)
    return nn.LayerNorm(use_bias=False, dtype=dtype, name=name)


class YarnTerms(NamedTuple):
    """What YaRN makes of a :class:`RopeScaling` at one rotary size."""

    inv_freq: Tuple[float, ...]  # the blended inverse frequencies, ``dim / 2``
    low: int  # pairs below it keep their frequency ...
    high: int  # ... pairs above it turn ``factor`` times slower
    amplitude: float  # on cos and sin: m(s, mscale) / m(s, mscale_all_dim)
    softmax_factor: float  # on the softmax scale: m(s, mscale_all_dim)^2


@functools.lru_cache(maxsize=None)
def yarn_terms(scaling: RopeScaling, dim: int, theta: float) -> YarnTerms:
    """YaRN as the DeepSeek family computes it, from its numbers alone
    (Python floats: fixed when a program is traced).  With ``f_i =
    theta^(-2i/dim)`` and ``dim(r) = dim ln(L0 / (2 pi r)) / (2 ln
    theta)`` (the pair that turns ``r`` times over the original context
    ``L0``): ``low = max(floor(dim(beta_fast)), 0)``, ``high =
    min(ceil(dim(beta_slow)), dim - 1)``, ``ramp_i = clip((i - low) /
    (high - low), 0, 1)`` and ``inv_freq_i = f_i (1 - ramp_i) + (f_i /
    factor) ramp_i``: fast pairs keep their frequency, slow ones are
    interpolated.  ``m(s, a) = 0.1 a ln s + 1`` (1 at ``s <= 1``)."""
    s, L0 = float(scaling.factor), float(scaling.original_max)

    def pair(rotations: float) -> float:
        return dim * math.log(L0 / (rotations * 2.0 * math.pi)) / (2.0 * math.log(theta))

    def m(a: float) -> float:
        return 0.1 * a * math.log(s) + 1.0 if s > 1.0 else 1.0

    low = max(math.floor(pair(scaling.beta_fast)), 0)
    high = min(math.ceil(pair(scaling.beta_slow)), dim - 1)
    span = max(high - low, 1e-3)  # the family's guard against low == high
    inv_freq = []
    for i in range(dim // 2):
        f = theta ** (-2.0 * i / dim)
        ramp = min(max((i - low) / span, 0.0), 1.0)
        inv_freq.append(f * (1.0 - ramp) + (f / s) * ramp)
    return YarnTerms(
        tuple(inv_freq), low, high, m(scaling.mscale) / m(scaling.mscale_all_dim),
        m(scaling.mscale_all_dim) ** 2,
    )


@functools.lru_cache(maxsize=None)
def _note_rope_form(shape, factor, low, high, softmax_scale) -> None:
    """A scaled rotary's numbers: one zero-length program span a traced
    shape (the cache is the "once")."""
    from scalerl_tpu.runtime import tracing

    with tracing.span(
        "rope.form", kind="model", shape=list(shape), scaling="yarn",
        factor=factor, low=low, high=high, softmax_scale=softmax_scale,
    ):
        pass


def rotary_fn(
    positions: jnp.ndarray,
    head_dim: int,
    theta: float,
    pairing: str = "half",
    scaling: Optional[RopeScaling] = None,
) -> Callable:
    """``x [B, T, H, D] -> x`` rotated to ``positions [B, T]``: the
    rotate-half pairing (feature ``i`` with ``i + D/2``), ``inv_freq_i =
    theta^(-2i/D)``, angle ``position x inv_freq``; computed in float32
    and rounded once.  The angles are made once a forward and shared by
    every block.  Under ``pairing="interleaved"`` pair ``i`` is features
    ``(2i, 2i + 1)``; the result is laid out half-wise (all first members,
    then all second: the DeepSeek family's own arrangement), which q and k
    share, so every score is that of the interleaved rotation.  An ``x``
    wider than ``head_dim`` has its first ``head_dim`` features rotated
    and the rest passed through (a partial rotary).

    ``scaling`` (YaRN, :func:`yarn_terms`) changes two things and nothing
    else: ``inv_freq`` is the blend of ``theta^(-2i/D)`` and that over
    ``factor``, pair by pair, and cos and sin carry the ``amplitude``
    (1 where ``mscale == mscale_all_dim``).  The third YaRN term, ``m^2``
    on the softmax scale, is the attention's (:class:`_LatentAttention`).
    The closure is the same."""
    half = head_dim // 2
    amplitude = 1.0
    if scaling is None:
        inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / head_dim)
    else:
        terms = yarn_terms(scaling, head_dim, float(theta))
        inv_freq = jnp.asarray(terms.inv_freq, jnp.float32)
        amplitude = terms.amplitude
    angle = positions.astype(jnp.float32)[:, :, None, None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if amplitude != 1.0:
        cos, sin = cos * amplitude, sin * amplitude

    def rotate(x):
        if x.shape[-1] > head_dim:
            return jnp.concatenate(
                [rotate(x[..., :head_dim]), x[..., head_dim:]], axis=-1
            )
        if pairing == "interleaved":
            xf = x.astype(jnp.float32)
            x1, x2 = xf[..., 0::2], xf[..., 1::2]
        else:
            x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
        return out.astype(x.dtype)

    return rotate


# what a layer's attention does around its projections, as a device trace's
# ``op_name`` shows it (``benchmark/op_scopes.py``, PERF.md section 3)
_SCOPE_KV_WRITE = "kv_write"
_SCOPE_ATTEND = "attend"


def _write_pages(call: Call, pools, rows):
    """This call's ``rows`` (one ``[B, T, ...]`` array a pool) into the
    pools' pages: a flat single-axis scatter (page id x page size +
    offset) into each lane-dense pool.  The reshape is a bitcast, and
    XLA:CPU lowers 1-level row scatters measurably faster than the
    2-level fancy-index form."""
    N, ps, _ = pools[0].shape
    with jax.named_scope(_SCOPE_KV_WRITE):
        flat_idx = (call.page_ids * ps + call.page_offsets).reshape(-1)
        return tuple(
            pool.reshape(N * ps, -1)
            .at[flat_idx]
            .set(new.astype(pool.dtype).reshape(-1, pool.shape[2]))
            .reshape(pool.shape)
            for pool, new in zip(pools, rows)
        )


def _tail_mask(call: Call, T: int, context: int) -> jnp.ndarray:
    """``[B, T, context]``: a tail prefill's query ``t`` of row ``b``,
    at position ``prefix_starts[b] + t``, attends the gathered context
    causally from its start."""
    pos = jnp.arange(context)[None, None, :]
    qpos = (call.prefix_starts[:, None] + jnp.arange(T)[None, :])[:, :, None]
    return pos <= qpos


def _one_row(kind: str, spec: BlockSpec) -> None:
    """A layer class that knows one residual row refuses a stream of them."""
    if spec.streams > 1:
        raise ValueError(
            f"a {kind} layer takes a one-row residual stream; a stream of "
            f"{spec.streams} rows (hyper-connections) runs through plain layers alone"
        )


def _routed_experts(spec: BlockSpec, dt) -> RoutedExperts:
    """The routed FFN a spec describes, under the name every layer kind
    gives it."""
    return RoutedExperts(
        spec.num_experts, spec.experts_per_token, spec.expert_width,
        spec.norm_topk_prob, zero_experts=spec.zero_experts,
        held=spec.experts_held, first_expert=spec.first_expert,
        choice_bias=spec.router_bias, routed_scaling=spec.routed_scaling,
        scoring=spec.scoring, act=spec.expert_act, name="experts", **dt,
    )


def _repeat_kv(x: jnp.ndarray, num_heads: int) -> jnp.ndarray:
    """``[B, S, KV, D] -> [B, S, H, D]``: each key/value head under the
    ``H / KV`` query heads that read it (the identity at ``KV == H``)."""
    kv = x.shape[2]
    return x if kv == num_heads else jnp.repeat(x, num_heads // kv, axis=2)


def _attend(mod, q, k, v, call: Call, cache: Optional[ModelCache]):
    """The one attention core under every multi-head kind: ``q [B, T, H,
    D]`` against ``k``/``v`` ``[B, T, KV, D]`` (normed and rotated by the
    caller: K enters a cache as it is read), in the call's form.  On a
    cache the call's keys and values are written into the layer's pools
    first.  Returns ``(out [B, T, H, D], the layer's K and V pools
    written, or None)``."""
    T, H = q.shape[1], q.shape[2]
    KV = k.shape[2]
    if call.paged:
        kp, vp = _write_pages(call, (cache.k[0], cache.v[0]), (k, v))
        cache = ModelCache(k=(kp,), v=(vp,))
    with jax.named_scope(_SCOPE_ATTEND):
        if call.mode == "tail":
            # the heads are split out of the gathered rows: reshaping the
            # pool itself would bring its relayout copy back
            kg = _repeat_kv(gather_pages(kp, call.page_table, KV), H)
            vg = _repeat_kv(gather_pages(vp, call.page_table, KV), H)
            out = _masked_attention(q, kg, vg, _tail_mask(call, T, kg.shape[1]), mod.dtype)
        elif call.mode == "decode":
            paged_attn = mod.paged_attn_fn or paged_attention_reference
            out = paged_attn(q, kp, vp, call.page_table, call.attn_lengths)
            out = out.astype(mod.dtype)
        elif call.mode == "packed":
            out = mod.segment_attn_fn(q, _repeat_kv(k, H), _repeat_kv(v, H), call.segment_ids)
            out = out.astype(mod.dtype)
        elif call.mode == "causal":
            out = mod.attn_fn(q, _repeat_kv(k, H), _repeat_kv(v, H))
        else:  # masked, prefill: the call's own keys under its mask
            out = _masked_attention(
                q, _repeat_kv(k, H), _repeat_kv(v, H), call.attn_mask, mod.dtype
            )
    return out, cache


def _mha(mod, h, call: Call, cache: Optional[ModelCache]):
    """Multi-head attention on a normed input ``h [B, T, d]``, inside the
    compact ``__call__`` of ``mod`` (a :class:`_Block` or a
    :class:`_MixerBlock`: the projections are ``mod``'s own children, so a
    plain layer's tree is what it always was).  Returns ``(out [B, T, d],
    the layer's K and V pools written, or None)``.

    With ``spec.kv_heads`` key/value heads under more query heads (or a
    gated query) the projections are ``q`` and ``kv`` apart, the pools hold ``kv_heads x D``
    a token, the paged decode reads them as they are (the kernel's query
    is block-diagonal by group) and every other path repeats each
    key/value head under its query heads after the cache write.

    ``spec.attn_gate``: the query projection is twice as wide, a head
    ``[q | gate]``, and ``sigmoid(gate)`` multiplies the attention's
    output before ``proj``.  ``spec.qk_norm == "head"``: q and k are
    normed over each head's own features, after the head split and
    before the rotation and the cache write."""
    B, T, _ = h.shape
    spec, H = mod.spec, mod.num_heads
    head_dim = spec.head_dim or mod.d_model // H
    width = H * head_dim
    KV = spec.kv_heads or H
    dt = dict(dtype=mod.dtype, param_dtype=mod.param_dtype)
    if KV == H and not spec.attn_gate:
        qkv = nn.Dense(3 * width, use_bias=False, name="qkv", **dt)(h)
        q, k, v = jnp.split(qkv, 3, axis=-1)
    else:
        q = nn.Dense(
            (2 if spec.attn_gate else 1) * width, use_bias=False, name="q", **dt
        )(h)
        kv = nn.Dense(2 * KV * head_dim, use_bias=False, name="kv", **dt)(h)
        k, v = jnp.split(kv, 2, axis=-1)
    gate = None
    if spec.attn_gate:
        q, gate = jnp.split(q.reshape(B, T, H, 2 * head_dim), 2, axis=-1)
    if spec.qk_norm == "rmsnorm":
        # over all heads' features at once, before the head split
        q = RMSNorm(spec.norm_eps, dtype=mod.dtype, name="q_norm")(q)
        k = RMSNorm(spec.norm_eps, dtype=mod.dtype, name="k_norm")(k)
    shape = (B, T, KV, head_dim)
    q = q.reshape(B, T, H, head_dim)
    k, v = k.reshape(shape), v.reshape(shape)
    if spec.qk_norm == "head":
        head_norm = functools.partial(
            RMSNorm, spec.norm_eps, dtype=mod.dtype,
            zero_centered=spec.norm_zero_centered,
        )
        q, k = head_norm(name="q_norm")(q), head_norm(name="k_norm")(k)
    if mod.rotary is not None:
        # before every cache write: K is stored normed and rotated
        q, k = mod.rotary(q), mod.rotary(k)
    out, cache = _attend(mod, q, k, v, call, cache)
    if gate is not None:
        out = out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(out.dtype)
    out = nn.Dense(mod.d_model, use_bias=False, name="proj", **dt)(
        out.reshape(B, T, width)
    )
    return out, cache


class _Layer(nn.Module):
    """What every layer class is built from, and the one contract: ``(x,
    call, cache, r) -> (x, cache, r)``, ``cache`` the layer's own arrays
    (:func:`_layer_entries`) where ``call.paged`` and None elsewhere, in
    and out.  ``x`` is the residual stream: ``[B, T, d]``, one row a
    token, or under ``spec.streams > 1`` a STREAM OF ROWS ``[B, T,
    streams, d]`` (hyper-connections), which the model
    (:class:`TransformerPolicy`) expands from the embedding before the
    first layer and sums into one row after the last; only a plain layer
    (:class:`_Block`, through :class:`_HyperMix`) takes one.  Like ``r``
    it is an activation: no cache field, no lane state, nothing a page
    table or a fork has to know.  ``r`` is the second stream that goes up the
    stack beside ``x``: the router's state a ``router="mlp"`` layer reads
    from the layer before it and hands to the one after (``[B, T,
    router_width]`` float32; None into the first layer, and None all the
    way up a stack without such a router, whose layers pass it by).  It is
    an activation of this forward, not cache: nothing of it outlives the
    call.  A mixer under a layer takes the normed input as ``(h, call,
    cache) -> (out, cache)``."""

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


def _hc_bias_init(n: int):
    """How a :class:`_HyperMix`'s bias is drawn: the read and write logits
    (``2 n``) normal of deviation 1, the row-mixing matrix's ``2 I`` plus
    normal of deviation 0.5 (row-major).  The published initialisation
    (``alpha`` 0.01, an identity-like bias) would leave the
    input-dependent half of every map invisible to a check on seeded
    weights, and a matrix at the identity or at the uniform one would not
    need its iterations; this draw is far from both."""

    def init(key, shape, dtype=jnp.float32):
        k_vec, k_mat = jax.random.split(key)
        vec = jax.random.normal(k_vec, (2 * n,), jnp.float32)
        mat = 2.0 * jnp.eye(n, dtype=jnp.float32) + 0.5 * jax.random.normal(
            k_mat, (n, n), jnp.float32
        )
        return jnp.concatenate([vec, mat.reshape(-1)]).astype(dtype).reshape(shape)

    return init


def _hc_alpha_init(key, shape, dtype=jnp.float32):
    """The three map gains, uniform in 0.5 .. 1.5 (see :func:`_hc_bias_init`)."""
    return jax.random.uniform(key, shape, dtype, 0.5, 1.5)


class _HyperMix(nn.Module):
    """One sublayer's manifold-constrained hyper-connection, on a stream of
    rows ``X [B, T, n, d]`` (``n = spec.streams``).  Per token::

        u      = RMSNorm_{n d}(vec(X))                 a learned scale [n d]; vec row-major
        z      = alpha * (u Phi) + b                   Phi [n d, n + n + n n]; alpha one gain a group
        H_pre  = sigmoid(z[:n])                        the read weights
        H_post = 2 sigmoid(z[n:2n])                    the write gates
        M_0    = exp(clip(mat(z[2n:]), hc_clamp))      [n, n], row-major
        M_t    = T_r(T_c(M_{t-1})),  t = 1..hc_iters   T_c: a column over (its sum + hc_eps); T_r: a row likewise
        read:   h = sum_i H_pre[i] X[i]                the sublayer's one-row input
        write:  X[i] <- sum_j M[i, j] X[j] + H_post[i] y

    :meth:`read` makes the maps and the input, :meth:`write` takes the
    sublayer's output back; a layer calls them where a one-row layer
    calls its norm and :meth:`_Block._merge`.  Everything but the stream
    is float32: the flattened norm, the projection (HIGHEST precision: 24
    columns, no cost), the sigmoids, the iteration; the read and the
    write accumulate in float32 and round once to the stream's dtype.
    The iterations are a Python loop: a decode program holds no ``while``
    of its own for them."""

    spec: BlockSpec
    d_model: int
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        n, width = self.spec.streams, self.spec.streams * self.d_model
        f32 = jnp.float32
        self.scale = self.param("scale", nn.initializers.ones, (width,), f32)
        self.phi = self.param("phi", nn.initializers.lecun_normal(), (width, n * (n + 2)), f32)
        self.b = self.param("b", _hc_bias_init(n), (n * (n + 2),), f32)
        self.alpha = self.param("alpha", _hc_alpha_init, (3,), f32)

    def maps(self, x):
        """``(H_pre [B, T, n], H_post [B, T, n], M [B, T, n, n])`` of a
        stream ``x [B, T, n, d]``, float32."""
        spec, f32 = self.spec, jnp.float32
        B, T, n, d = x.shape
        exact = dict(precision=lax.Precision.HIGHEST)
        with jax.named_scope("mhc_maps"):
            flat = x.astype(f32).reshape(B, T, n * d)
            ms = jnp.mean(jnp.square(flat), axis=-1, keepdims=True)
            u = flat * lax.rsqrt(ms + spec.norm_eps) * self.scale
            gain = self.alpha[np.repeat(np.arange(3), [n, n, n * n])]  # a gain a group
            z = gain * jnp.dot(u, self.phi, **exact) + self.b
            pre = jax.nn.sigmoid(z[..., :n])
            post = 2.0 * jax.nn.sigmoid(z[..., n : 2 * n])
            # the matrix's 16 entries side by side, row-major, and a
            # normalisation's sums as ONE product with a constant 0/1
            # matrix that adds the entries of a column (or a row) and hands
            # each entry its sum back: a ``jnp.sum`` a normalisation stands
            # alone as a ``reduce`` and its division as a second fusion
            # (128 device operations a sublayer at the decode shape, 51 so;
            # PERF.md, PR 51)
            m = jnp.exp(jnp.clip(z[..., 2 * n :], *spec.hc_clamp))
            k = np.arange(n * n)
            same_col = jnp.asarray(k[:, None] % n == k[None, :] % n, f32)
            same_row = jnp.asarray(k[:, None] // n == k[None, :] // n, f32)
            for _ in range(spec.hc_iters):
                m = m / (jnp.dot(m, same_col, **exact) + spec.hc_eps)  # columns
                m = m / (jnp.dot(m, same_row, **exact) + spec.hc_eps)  # rows
        return pre, post, m.reshape(B, T, n, n)

    def read(self, x):
        """``(h [B, T, d], maps)``: the sublayer's input and what
        :meth:`write` needs."""
        pre, post, m = self.maps(x)
        with jax.named_scope("mhc_mix"):
            h = sum(
                pre[..., i, None] * x[:, :, i].astype(jnp.float32)
                for i in range(x.shape[2])
            )
        return h.astype(self.dtype), (post, m)

    def write(self, x, y, maps):
        """The stream after the sublayer's output ``y [B, T, d]``."""
        post, m = maps
        n, f32 = x.shape[2], jnp.float32
        with jax.named_scope("mhc_mix"):
            rows = [x[:, :, j].astype(f32) for j in range(n)]
            yf = y.astype(f32)
            out = [
                sum(m[..., i, j, None] * rows[j] for j in range(n)) + post[..., i, None] * yf
                for i in range(n)
            ]
            return jnp.stack(out, axis=2).astype(x.dtype)


class _Block(_Layer):
    """A plain layer (``layer="plain"``): ``x + Mixer(N(x))``, then ``x +
    FFN(N(x))``; the mixer this spec's attention (:func:`_mha`,
    :class:`_LatentAttention` under ``attention="mla"``,
    :class:`_CompressedConvAttention` under ``"cca"``) or, under
    ``mixer="gdn"``, a Gated DeltaNet (:class:`_GatedDeltaMixer`).  Under
    ``spec.residual_scale`` both adds are ``(s x + c) + (s' y + c')``
    (:meth:`_merge`); under ``spec.router == "mlp"`` the experts' router
    reads and hands on the stack's second stream ``r``.  Under
    ``spec.streams > 1`` ``x`` is a stream of rows and each sublayer is
    ``X <- write(X, F(N(read(X))))`` through a :class:`_HyperMix` of its
    own (``attn_hc``, ``ffn_hc``) in place of the add."""

    def _merge(self, name: str, x, y):
        """A residual add; under ``spec.residual_scale`` with a learned
        scale and bias on both sides (``[2, d]`` each: the stream's, the
        sublayer's), in float32 and rounded once."""
        if not self.spec.residual_scale:
            return x + y
        f32 = jnp.float32
        s = self.param(f"{name}_res_scale", nn.initializers.ones, (2, self.d_model), f32)
        c = self.param(f"{name}_res_bias", nn.initializers.zeros, (2, self.d_model), f32)
        merged = (s[0] * x.astype(f32) + c[0]) + (s[1] * y.astype(f32) + c[1])
        return merged.astype(x.dtype)

    def _sublayer(self, name: str, x):
        """``(the sublayer's one-row input, how to take its output back)``
        on a one-row stream (the row itself, :meth:`_merge`) or on a
        stream of rows (a :class:`_HyperMix`'s read and write)."""
        if self.spec.streams == 1:
            return x, lambda y: self._merge(name, x, y)
        mix = _HyperMix(self.spec, self.d_model, dtype=self.dtype, name=f"{name}_hc")
        h, maps = mix.read(x)
        return h, lambda y: mix.write(x, y, maps)

    @nn.compact
    def __call__(self, x, call: Call, cache: Optional[ModelCache] = None, r=None):
        spec = self.spec
        rms = spec.norm == "rmsnorm"
        dt = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        h, merge = self._sublayer("attn", x)
        h = _norm(spec, self.dtype, "attn_norm" if rms else None)(h)
        if spec.mixer == "gdn":
            out, cache = _GatedDeltaMixer(self.d_model, spec, name="mixer", **dt)(h, call, cache)
        elif spec.attention == "mla":
            out, cache = _latent_attention(self, "attn")(h, call, cache)
        elif spec.attention == "cca":
            out, cache = _sub_attention(_CompressedConvAttention, self, "attn")(h, call, cache)
        else:
            out, cache = _mha(self, h, call, cache)
        x = merge(out)
        h, merge = self._sublayer("ffn", x)
        h = _norm(spec, self.dtype, "ffn_norm" if rms else None)(h)
        if spec.ffn == "experts":
            logits = None
            if spec.router == "mlp":
                logits, r = MLPRouter(
                    spec.router_width, spec.num_experts + spec.zero_experts,
                    spec.experts_per_token, spec.norm_eps,
                    param_dtype=self.param_dtype, name="router",
                )(h, r)
            y = _routed_experts(spec, dt)(h, logits)
            if spec.shared_experts:
                # always on, computed where the token lives
                shared = _GatedMLP(
                    self.d_model,
                    spec.shared_width or spec.shared_experts * spec.expert_width,
                    name="shared", **dt,
                )(h)
                if spec.shared_gate:
                    # behind a sigmoid scalar a token
                    shared = shared * jax.nn.sigmoid(
                        nn.Dense(1, use_bias=False, name="shared_gate", **dt)(h)
                        .astype(jnp.float32)
                    ).astype(shared.dtype)
                y = y + shared
            h = y
        elif spec.ffn == "swiglu":
            h = _GatedMLP(self.d_model, spec.ffn_hidden, name="ffn", **dt)(h)
        else:
            h = nn.Dense(self.mlp_ratio * self.d_model, name="mlp_in", **dt)(h)
            h = nn.gelu(h)
            h = nn.Dense(self.d_model, name="mlp_out", **dt)(h)
        return merge(h), cache, r


class _LatentAttention(nn.Module):
    """Multi-head latent attention (MLA) on a normed input ``h [B, T, d]``.

    ``c_q = RMSNorm(h W_qa)``; ``q = s_q (c_q W_qb)``, a head ``[q_nope |
    q_pe]``; ``[c | k_pe] = h W_kva``; ``c = s_kv RMSNorm(c)``; ``[k_nope |
    v]`` a head ``= c W_kvb``; rotary on ``q_pe`` and on the one ``k_pe``
    all heads share; scores ``(q_nope . k_nope + q_pe . k_pe) /
    sqrt(nope + rope)``, softmax in float32; ``o = concat(p v) W_o``.
    Under ``spec.mla_scale`` ``s_q = sqrt(d / q_lora_rank)`` and ``s_kv =
    sqrt(d / kv_lora_rank)``; else both are 1.  Under ``spec.rope_scaling``
    (YaRN) the softmax scale is ``m^2 / sqrt(nope + rope)``
    (:func:`yarn_terms`): ``scale`` below is its one source, handed to
    every path that takes a scale (masked, prefill, tail, the segment
    kernels, the paged decode) and multiplied into q for the one that
    takes none (the causal ``attn_fn``).

    One set of parameters, two forms of the same product:

    - **un-absorbed** wherever the keys are this call's own (``causal``,
      ``masked``, ``packed``, ``prefill``): ``k_nope`` and ``v`` are made
      from ``c`` and :func:`_mha`'s call sites attend (``v`` is padded to
      the q/k head size for the kernels that take one head size, and the
      pad sliced off).
    - **absorbed** wherever the keys come through a page table (``decode``
      and ``tail``, the speculative verify with it): the
      cache holds ``[c | rotated k_pe]`` a token and never a head's K or
      V, so ``W_kvb`` moves to the query side, ``q_abs = [W_uk^T q_nope |
      q_pe]``, scores are ``q_abs . row``, and a head's output is
      ``W_uv (sum p row[:kv_lora_rank])``.  Decode goes through
      ``paged_attn_fn`` (``ops.pallas_paged_attention.paged_decode_latent``
      or its XLA twin), the tail gathers rows and runs
      :func:`latent_attention`.

    Returns ``(out [B, T, d], its one pool written, or None)``.
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
    def __call__(self, h, call: Call, cache: Optional[ModelCache]):
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
        scaled = {}  # what a call that takes a scale is told (plain rotary: nothing)
        if s.rope_scaling is not None:
            m2 = yarn_terms(s.rope_scaling, rope, float(s.rope_theta)).softmax_factor
            scale = m2 * scale
            scaled = {"scale": scale}
        if call.paged:
            # the cached row, normed, scaled and rotated, zero to the
            # pool's whole tiles
            pool = cache.rows[0]
            row = jnp.concatenate([c, k_pe[:, :, 0]], axis=-1)
            row = jnp.pad(row, ((0, 0), (0, 0), (0, pool.shape[2] - row.shape[-1])))
            (pool,) = _write_pages(call, (pool,), (row,))
            cache = ModelCache(rows=(pool,))
        if call.mode in ("tail", "decode"):
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
            if call.mode == "tail":
                rows = gather_pages(pool, call.page_table, 1)[:, :, 0]
                lat = latent_attention(
                    q_abs, rows, _tail_mask(call, T, rows.shape[1]), r_kv, scale
                )
            else:
                paged = self.paged_attn_fn or paged_latent_attention_reference
                lat = paged(q_abs, pool, call.page_table, call.attn_lengths, r_kv, scale)
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
            if call.mode == "packed":
                # the segment kernels take a v narrower than q and k
                out = self.segment_attn_fn(
                    qf, k, v, call.segment_ids, **scaled
                ).astype(self.dtype)
            elif call.mode == "causal":
                # a causal ``attn_fn`` takes one head size: v padded to q
                # and k's, the pad sliced off; and no scale: YaRN's factor
                # goes into q, in float32 and rounded once
                if scaled:
                    qf = (qf.astype(f32) * m2).astype(qf.dtype)
                v = jnp.pad(v, ((0, 0),) * 3 + ((0, nope + rope - vd),))
                out = self.attn_fn(qf, k, v)[..., :vd].astype(self.dtype)
            else:  # masked, prefill
                out = _masked_attention(qf, k, v, call.attn_mask, self.dtype, **scaled)
        out = dense(self.d_model, "proj")(out.reshape(B, T, H * vd))
        return out, cache


def _sub_attention(kind, mod: _Layer, name: str):
    """A layer's attention of class ``kind`` (one that is a module of its
    own), under ``name`` among the layer's children."""
    return kind(
        mod.d_model, mod.num_heads, mod.spec, mod.attn_fn, dtype=mod.dtype,
        param_dtype=mod.param_dtype, paged_attn_fn=mod.paged_attn_fn,
        segment_attn_fn=mod.segment_attn_fn, rotary=mod.rotary, name=name,
    )


def _latent_attention(mod: _Layer, name: str) -> _LatentAttention:
    """A layer's latent attention, under ``name`` among its children."""
    return _sub_attention(_LatentAttention, mod, name)


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


class _SquaredReluMLP(nn.Module):
    """Dense FFN without a gate, no bias: ``relu(h Wu)^2 Wd``."""

    d_model: int
    hidden: int
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h):
        dt = dict(use_bias=False, dtype=self.dtype, param_dtype=self.param_dtype)
        a = jnp.square(nn.relu(nn.Dense(self.hidden, name="up", **dt)(h)))
        return nn.Dense(self.d_model, name="down", **dt)(a)


def _dense_ffn(spec: BlockSpec, d_model: int, hidden: int, name: str, dt):
    """A dense FFN of the spec's expert form."""
    kind = _SquaredReluMLP if spec.expert_act == "relu2" else _GatedMLP
    return kind(d_model, hidden, name=name, **dt)


def run_ids(segment_ids: jnp.ndarray) -> jnp.ndarray:
    """``[B, T]`` ids of the run each token's recurrence belongs to: a
    real token's own segment id, a pad token's (id 0) the id of the last
    real token before it, 0 before the first.  So a right-padded prompt's
    tail and a packed row's tail ride on the last sequence (with no input
    and no decay: the state passes through them), and a new id is a
    reset."""
    seg = segment_ids.astype(jnp.int32)
    at = lax.cummax(jnp.where(seg > 0, jnp.arange(seg.shape[1])[None, :], 0), axis=1)
    return jnp.take_along_axis(seg, at, axis=1)


def _masked_exp(keep, v):
    """``exp(v)`` where ``keep``, 0 elsewhere; what is masked out never
    reaches the exponential (no overflow there, no NaN in its gradient)."""
    return jnp.where(keep, jnp.exp(jnp.where(keep, v, 0.0)), 0.0)


def ssd_chunked(x, dt, A, B, C, runs, chunk: int):
    """The Mamba-2 recurrence over whole sequences, ``chunk`` tokens at a
    time (the state-space-duality form)::

        S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T ;  y_t = S_t C_t

    ``x [Bt, T, H, P]``, ``dt [Bt, T, H]`` float32 (0 at a pad token, with
    ``x`` 0 there: no decay, no input), ``A [H]`` negative, ``B``/``C``
    ``[Bt, T, G, N]``, ``runs [Bt, T]`` (:func:`run_ids`, never
    decreasing along a row: the state is zero at the start of every run).  Inside a chunk the outputs are one
    masked ``C B^T`` product weighted by the decays between the two
    positions; between chunks the state is carried, ``[H, P, N]`` a row.
    A position of another run is masked out of every product (never a
    ``-inf`` in a running sum of log-decays).  Returns ``(y [Bt, T, H, P]
    float32, S [Bt, H, P, N] float32 after the last token)``.
    """
    f32 = jnp.float32
    Bt, T, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    Q = chunk
    pad = -T % Q
    if pad:
        widen = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))  # noqa: E731
        x, dt, B, C = widen(x), widen(dt), widen(B), widen(C)
        runs = jnp.pad(runs, ((0, 0), (0, pad)), mode="edge")
    nc = (T + pad) // Q
    per = H // G
    x = x.astype(f32).reshape(Bt, nc, Q, H, P)
    dt = dt.reshape(Bt, nc, Q, H)
    B = B.astype(f32).reshape(Bt, nc, Q, G, N)
    C = C.astype(f32).reshape(Bt, nc, Q, G, N)
    runs = runs.reshape(Bt, nc, Q)
    dtx = dt[..., None] * x
    causal = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]
    same = causal[None, None] & (runs[:, :, :, None] == runs[:, :, None, :])  # [Bt, nc, t, s]
    # log-decay from the start of the token's run (or of the chunk, if the
    # run entered it): a masked sum, so that no rounding of another run's
    # decays reaches this one; the mask is exact at any matmul precision
    cum = jnp.einsum(
        "bcts,bcsh->bcth", same.astype(f32), dt * A.astype(f32),
        precision=lax.Precision.HIGHEST,
    )
    # -- inside a chunk: y_t += sum_{s <= t} exp(cum_t - cum_s) (C_t . B_s) dt_s x_s
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # [Bt, nc, t, s, H]
    weight = _masked_exp(same[..., None], diff)
    cb = jnp.einsum("bctgn,bcsgn->bctsg", C, B)
    m = weight * jnp.repeat(cb, per, axis=-1)
    y = jnp.einsum("bctsh,bcshp->bcthp", m, dtx)
    # -- a chunk's own contribution to the state at its end, and the decay
    # of what entered it: both only for what is of the end's run
    last = runs[:, :, -1]
    of_last = runs == last[:, :, None]  # [Bt, nc, Q]
    tail = _masked_exp(of_last[..., None], cum[:, :, -1:] - cum)
    Bh = jnp.repeat(B, per, axis=3)  # [Bt, nc, Q, H, N]
    grown = jnp.einsum("bcsh,bcshp,bcshn->bchpn", tail, dtx, Bh)
    entered = jnp.concatenate([jnp.zeros_like(last[:, :1]), last[:, :-1]], axis=1)  # the carry's run
    through = jnp.where(
        (last == entered)[..., None], jnp.exp(cum[:, :, -1]), 0.0
    )  # [Bt, nc, H]

    def carry(S, c):
        through_c, grown_c = c
        return through_c[:, :, None, None] * S + grown_c, S

    S, entering = lax.scan(
        carry, jnp.zeros((Bt, H, P, N), f32),
        (jnp.moveaxis(through, 1, 0), jnp.moveaxis(grown, 1, 0)),
    )
    entering = jnp.moveaxis(entering, 0, 1)  # [Bt, nc, H, P, N]: the state at each chunk's start
    # -- what entered the chunk, read by the tokens of its run
    of_entered = runs == entered[:, :, None]
    reach = _masked_exp(of_entered[..., None], cum)
    Ch = jnp.repeat(C, per, axis=3)
    y = y + reach[..., None] * jnp.einsum("bcthn,bchpn->bcthp", Ch, entering)
    return y.reshape(Bt, nc * Q, H, P)[:, :T], S


def ssm_decode_update(state, x, dt, A, B, C, D):
    """One token of the same recurrence for every lane, on the carried
    state::

        S <- exp(dt A) S + dt x B^T ;  y = S C + D x

    ``state [L, H, P, N]`` float32 (how :func:`ssd_chunked` leaves it, the
    state axis on the lanes); ``x [L, H, P]``; ``dt [L, H]`` (after the
    softplus); ``A [H]`` negative; ``B``/``C`` ``[L, G, N]`` (head ``h``
    reads group ``h // (H / G)``); ``D [H]``.  Returns ``(y [L, H, P]
    float32, the new state)``.  Plain ``jax.numpy`` on every backend: XLA
    makes one fusion of it that reads the state once and writes it over
    its input inside a donated ``while`` carry, and a Pallas kernel moved
    the same bytes no faster (PERF.md, PR 40).  Grad-free: decode is
    inference-only, the learner differentiates :func:`ssd_chunked`."""
    f32 = jnp.float32
    per = dt.shape[1] // B.shape[1]
    x, dt = x.astype(f32), dt.astype(f32)
    Bh = jnp.repeat(B.astype(f32), per, axis=1)  # [L, H, N]
    Ch = jnp.repeat(C.astype(f32), per, axis=1)
    with jax.named_scope("ssm_decode_update"):
        state = (
            jnp.exp(dt * A.astype(f32))[:, :, None, None] * state
            + (dt[:, :, None] * x)[..., None] * Bh[:, :, None, :]
        )
        y = jnp.sum(state * Ch[:, :, None, :], axis=-1)
    return y + D.astype(f32)[None, :, None] * x, state


def _tap_init(taps: int):
    """A depthwise convolution's taps (and bias): uniform in ``+- taps^-0.5``."""
    return lambda key, shape: jax.random.uniform(
        key, shape, jnp.float32, -(taps ** -0.5), taps ** -0.5
    )


def _dt_bias_init(key, shape):
    """``softplus(dt_bias)`` log-uniform in [0.001, 0.1], floored at 1e-4."""
    step = jnp.exp(
        jax.random.uniform(key, shape, jnp.float32) * (jnp.log(0.1) - jnp.log(0.001))
        + jnp.log(0.001)
    )
    step = jnp.maximum(step, 1e-4)
    return step + jnp.log(-jnp.expm1(-step))


def _last_taps(u, real, taps: int):
    """The last ``taps`` real inputs of each RIGHT-padded row of ``u [Bt,
    T, C]`` (zeros before a row's start): what a prefill hands the
    decode's convolution window."""
    T = u.shape[1]
    length = jnp.sum(real, axis=1)
    at = length[:, None] - taps + jnp.arange(taps)[None, :]
    tail = jnp.take_along_axis(u, jnp.clip(at, 0, T - 1)[..., None], axis=1)
    return jnp.where((at >= 0)[..., None], tail, 0.0)


def _causal_taps(u, conv_w, runs, bias=None):
    """The causal depthwise convolution ``bias + sum_j w_j u_{t-K+1+j}`` of
    ``u [Bt, T, C]`` by taps ``conv_w [K, C]``; an input of another run
    (``runs [Bt, T]``) is not read."""
    K = conv_w.shape[0]
    out = u * conv_w[K - 1]
    if bias is not None:
        out = bias + out
    for back in range(1, K):
        out = out + _earlier(u, runs, back) * conv_w[K - 1 - back]
    return out


def _earlier(a, runs, back: int, fill=0.0):
    """``a [Bt, T, C]`` as it stood ``back`` tokens earlier, where that
    token is of this token's run (``runs [Bt, T]``); ``fill`` elsewhere."""
    T = a.shape[1]
    earlier = jnp.pad(a, ((0, 0), (back, 0), (0, 0)))[:, :T]
    near = jnp.pad(runs, ((0, 0), (back, 0)), constant_values=-1)[:, :T] == runs
    return jnp.where(near[..., None], earlier, fill)


def _runs_and_real(call: Call, rows: int, T: int):
    """A whole-sequence call's ``(runs, real)`` for a recurrent mixer: a
    ``causal`` call's rows are one run of real tokens each."""
    if call.mode == "causal":
        return jnp.ones((rows, T), jnp.int32), jnp.ones((rows, T), bool)
    return call.runs, call.real


def _decode_window(cache: ModelCache, u, conv_w):
    """One token a lane: the carried taps and this token's input ``u
    [lanes, 1, C]`` as the convolution's window ``[lanes, K, C]``, and its
    sum over the taps (no bias)."""
    window = jnp.concatenate([cache.conv[0], u], axis=1)
    return window, jnp.sum(window * conv_w, axis=1)


def _prefill_state(call: Call, cache: Optional[ModelCache], last, u, real):
    """What a whole-sequence call leaves in a recurrent layer's cache: a
    ``prefill`` writes the state after each row's last real token
    (``last``) and its last ``K - 1`` real convolution inputs to rows
    ``call.state_lanes``; the other forms have no cache."""
    if call.mode != "prefill":
        return cache
    ssm, taps = cache.ssm[0], cache.conv[0]
    tail = _last_taps(u, real, taps.shape[1])
    return ModelCache(
        ssm=(ssm.at[call.state_lanes].set(last, mode="drop"),),
        conv=(taps.at[call.state_lanes].set(tail, mode="drop"),),
    )


class _Mamba2Mixer(nn.Module):
    """The Mamba-2 mixer on a normed input ``h [B, T, d]`` (sizes
    ``spec.ssm_*``: ``H`` heads of ``P``, ``G`` groups, state ``N``, a
    causal depthwise convolution of ``K`` taps)::

        [z | xBC | dt] = h W_in                       (d -> H P + (H P + 2 G N) + H)
        xBC_t = silu(b + sum_j w_j xBC_{t-K+1+j})     (float32; [x | B | C])
        dt = softplus(dt + dt_bias);  A = -exp(A_log)
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;   y_t = S_t C_t + D x_t
        out = RMSNorm_groups(y * silu(z)) W_out       (G groups of H P / G)

    ONE set of parameters, two paths:

    - **whole sequences** (every form but ``decode``):
      :func:`ssd_chunked` over ``call.runs``: the state and the
      convolution's taps are cut at every run's start, and a pad token
      (``call.real`` False) has no input and no decay, so what leaves a
      right-padded prompt is the state at its true length, which a
      ``prefill`` writes (:func:`_prefill_state`).
    - **one token a lane** (``decode``): the convolution over the carried
      taps and :func:`ssm_decode_update` on the carried state, in place.

    ``cache`` holds ``ssm [lanes, H, P, N]`` and ``conv [lanes, K - 1, H P
    + 2 G N]``, both float32.  Returns ``(out [B, T, d], cache)``."""

    d_model: int
    spec: BlockSpec
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h, call: Call, cache: Optional[ModelCache]):
        s = self.spec
        f32 = jnp.float32
        Bt, T, _ = h.shape
        H, P, G, N, K = s.ssm_heads, s.ssm_head_dim, s.ssm_groups, s.ssm_state, s.ssm_conv
        inner, channels = H * P, H * P + 2 * G * N
        dt_kw = dict(use_bias=False, dtype=self.dtype, param_dtype=self.param_dtype)
        conv_w = self.param("conv_w", _tap_init(K), (K, channels))
        conv_b = self.param("conv_b", _tap_init(K), (channels,))
        dt_bias = self.param("dt_bias", _dt_bias_init, (H,))
        A_log = self.param(
            "A_log", lambda key, shape: jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0)), (H,)
        )
        D = self.param("D", nn.initializers.ones, (H,), f32)
        norm_scale = self.param("norm_scale", nn.initializers.ones, (inner,), f32)
        A = -jnp.exp(A_log)

        zxbcdt = nn.Dense(2 * inner + 2 * G * N + H, name="in_proj", **dt_kw)(h)
        z = zxbcdt[..., :inner]
        u = zxbcdt[..., inner : inner + channels].astype(f32)  # the convolution's input
        step = jax.nn.softplus(zxbcdt[..., inner + channels :].astype(f32) + dt_bias)

        def split(xbc):
            xbc = jax.nn.silu(xbc).astype(self.dtype)
            lead = xbc.shape[:-1]
            return (
                xbc[..., :inner].reshape(*lead, H, P),
                xbc[..., inner : inner + G * N].reshape(*lead, G, N),
                xbc[..., inner + G * N :].reshape(*lead, G, N),
            )

        if call.mode == "decode":
            window, mixed = _decode_window(cache, u, conv_w)
            x, B, C = split(conv_b + mixed)
            y, ssm = ssm_decode_update(cache.ssm[0], x, step[:, 0], A, B, C, D)
            y = y[:, None]  # [lanes, 1, H, P]
            cache = ModelCache(ssm=(ssm,), conv=(window[:, 1:],))
        else:
            runs, real = _runs_and_real(call, Bt, T)
            u = jnp.where(real[..., None], u, 0.0)
            step = jnp.where(real[..., None], step, 0.0)
            x, B, C = split(_causal_taps(u, conv_w, runs, conv_b))
            x = jnp.where(real[..., None, None], x, 0)
            y, last = ssd_chunked(x, step, A, B, C, runs, s.ssm_chunk)
            y = y + D[:, None] * x.astype(f32)
            cache = _prefill_state(call, cache, last, u, real)
        # the gated norm: within each group's channels, one scale of ``inner``
        gated = (y.reshape(Bt, T, inner) * jax.nn.silu(z.astype(f32))).reshape(Bt, T, G, inner // G)
        ms = jnp.mean(jnp.square(gated), axis=-1, keepdims=True)
        normed = (gated * lax.rsqrt(ms + s.norm_eps)).reshape(Bt, T, inner) * norm_scale
        out = nn.Dense(self.d_model, name="out_proj", **dt_kw)(normed.astype(self.dtype))
        return out, cache


def gated_delta_chunked(q, k, v, g, beta, runs, chunk: int):
    """The gated delta rule over whole sequences, ``chunk`` tokens at a
    time; a head's state ``S`` is ``[N, P]`` (key x value)::

        S <- exp(g_t) S;  d_t = beta_t (v_t - S^T k_t);  S <- S + k_t d_t^T;  o_t = S^T q_t

    ``q``/``k`` ``[Bt, T, G, N]`` (L2-normalised, ``q`` scaled; key head
    ``j`` serves value heads ``j x H / G`` on), ``v [Bt, T, H, P]``, ``g``
    (a log-decay, <= 0) and ``beta`` ``[Bt, T, H]`` float32, both 0 at a
    pad token (the state passes it unchanged), ``runs [Bt, T]``
    (:func:`run_ids`: the state is zero at the start of every run).

    Inside a chunk the writes ``d`` depend on one another through the
    state: with ``c_t`` the log-decay from the run's (or the chunk's)
    start and ``S_0`` what entered the chunk, ``d_t = beta_t (v_t -
    e^{c_t} S_0^T k_t - sum_{s<t} e^{c_t - c_s} (k_s . k_t) d_s)``, a
    unit-lower-triangular system ``(I + A) D = beta (V - e^c K S_0)``
    (the WY form), solved once a chunk for both right-hand sides: ``D =
    U - W S_0``.  Then ``O = e^c Q S_0 + M D`` (``M`` the causal ``q .
    k`` products weighted by the decays between the two positions) and
    the state leaves the chunk as ``e^{c_end} S_0 + (e^{c_end - c} K)^T
    D``; across chunks a ``lax.scan`` carries it.  A position of another
    run is masked out of every product, as :func:`ssd_chunked` does
    (never a ``-inf`` in a running sum of log-decays).  Every product is
    float32 at ``HIGHEST`` precision: the triangular system compounds a
    rounding of its operands.  Differentiable by autodiff.  Returns ``(o
    [Bt, T, H, P] float32, S [Bt, H, N, P] float32 after the last
    token)``.
    """
    f32, hi = jnp.float32, lax.Precision.HIGHEST
    Bt, T, H, P = v.shape
    G, N = k.shape[2], k.shape[3]
    Q = chunk
    pad = -T % Q
    if pad:
        widen = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))  # noqa: E731
        q, k, v, g, beta = widen(q), widen(k), widen(v), widen(g), widen(beta)
        runs = jnp.pad(runs, ((0, 0), (0, pad)), mode="edge")
    nc = (T + pad) // Q
    per = H // G
    q = jnp.repeat(q.astype(f32), per, axis=2).reshape(Bt, nc, Q, H, N)
    k = jnp.repeat(k.astype(f32), per, axis=2).reshape(Bt, nc, Q, H, N)
    v = v.astype(f32).reshape(Bt, nc, Q, H, P)
    g = g.reshape(Bt, nc, Q, H)
    beta = beta.reshape(Bt, nc, Q, H)
    runs = runs.reshape(Bt, nc, Q)
    causal = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]
    same = causal[None, None] & (runs[:, :, :, None] == runs[:, :, None, :])  # [Bt, nc, t, s]
    before = same & ~jnp.eye(Q, dtype=bool)
    # log-decay from the start of the token's run (or of the chunk): a
    # masked sum, exact at any matmul precision
    cum = jnp.einsum("bcts,bcsh->bcth", same.astype(f32), g, precision=hi)
    decay = _masked_exp(same[..., None], cum[:, :, :, None, :] - cum[:, :, None, :, :])  # [Bt, nc, t, s, H]
    kk = jnp.einsum("bcthn,bcshn->bctsh", k, k, precision=hi)
    A = jnp.where(before[..., None], beta[:, :, :, None, :] * decay * kk, 0.0)
    M = jnp.where(same[..., None], decay * jnp.einsum("bcthn,bcshn->bctsh", q, k, precision=hi), 0.0)
    # what enters a chunk is of the run its predecessor ended in; only the
    # tokens of that run read it, and only the end's run leaves the chunk
    last = runs[:, :, -1]
    entered = jnp.concatenate([jnp.zeros_like(last[:, :1]), last[:, :-1]], axis=1)
    reach = _masked_exp((runs == entered[:, :, None])[..., None], cum)  # [Bt, nc, Q, H]
    tail = _masked_exp((runs == last[:, :, None])[..., None], cum[:, :, -1:] - cum)
    through = jnp.where((last == entered)[..., None], jnp.exp(cum[:, :, -1]), 0.0)  # [Bt, nc, H]
    # (I + A) [U | W] = [beta V | beta e^c K], a head at a time
    rhs = jnp.concatenate(
        [beta[..., None] * v, (beta * reach)[..., None] * k], axis=-1
    )  # [Bt, nc, Q, H, P + N]
    solved = jax.scipy.linalg.solve_triangular(
        jnp.eye(Q, dtype=f32) + jnp.moveaxis(A, -1, 2), jnp.moveaxis(rhs, 3, 2),
        lower=True, unit_diagonal=True,
    )  # [Bt, nc, H, Q, P + N]
    chunks = tuple(
        jnp.moveaxis(a, 1, 0)
        for a in (
            solved[..., :P], solved[..., P:], M, reach[..., None] * q,
            tail[..., None] * k, through,
        )
    )

    def carry(S, c):
        U, W, M_c, reach_q, tail_k, through_c = c
        D = U - jnp.einsum("bhqn,bhnp->bhqp", W, S, precision=hi)
        o = jnp.einsum("bqhn,bhnp->bqhp", reach_q, S, precision=hi) + jnp.einsum(
            "btsh,bhsp->bthp", M_c, D, precision=hi
        )
        S = through_c[:, :, None, None] * S + jnp.einsum(
            "bshn,bhsp->bhnp", tail_k, D, precision=hi
        )
        return S, o

    S, o = lax.scan(carry, jnp.zeros((Bt, H, N, P), f32), chunks)
    return jnp.moveaxis(o, 0, 1).reshape(Bt, nc * Q, H, P)[:, :T], S


def gdn_decode_update(state, q, k, v, g, beta):
    """One token of the same rule for every lane, on the carried state,
    which is READ ONCE: with ``S`` the state before the token::

        u = e^g S^T k;  d = beta (v - u);  o = e^g S^T q + (k . q) d;  S' = e^g S + k d^T

    (the literal order, decay, ``S^T k``, rank-one write, ``S^T q``, reads
    the written state again for ``o``; ``S'^T q`` is the line above).
    ``state [L, H, N, P]`` float32 (how :func:`gated_delta_chunked` leaves
    it: key x value); ``q``/``k`` ``[L, G, N]``; ``v [L, H, P]``; ``g``,
    ``beta`` ``[L, H]``.  Returns ``(o [L, H, P] float32, the new
    state)``.  The Pallas kernel of ``ops/pallas_gdn.py`` on every backend
    (interpret mode off the chip): a block of heads of a lane in VMEM, one
    pass in and one out, in place.  The same lines in plain ``jax.numpy``
    compile on a v5e to a reduction fusion and a write fusion a layer,
    which read the state twice, and lost by 18-20% of the cell's rate
    (PERF.md, PR 42; ``benchmark/tools/gdn_decode_probe.py`` keeps that
    form to measure against).  Grad-free: decode is inference-only, the
    learner differentiates the chunked form."""
    with jax.named_scope("gdn_decode_update"):
        return gdn_decode_update_pallas(state, q, k, v, g, beta)


@functools.lru_cache(maxsize=None)
def _note_gdn_form(shape, chunk, heads, groups, decode) -> None:
    """Which form of the delta rule a traced shape took: one zero-length
    program span a shape (the cache is the "once")."""
    from scalerl_tpu.runtime import tracing

    attrs = dict(shape=list(shape), chunk=chunk, heads=heads, state_dtype="float32")
    if decode:
        # what one step of the kernel holds of the state: a block of heads of a lane
        per_block = heads_per_block(heads[0], heads[0] // groups, heads[1], heads[2])
        attrs.update(kernel="pallas", tile=[per_block, heads[1], heads[2]])
    with tracing.span("gdn.form", kind="model", **attrs):
        pass


class _GatedDeltaMixer(nn.Module):
    """The Gated DeltaNet mixer on a normed input ``h [B, T, d]`` (sizes
    ``spec.ssm_*``: ``H`` value heads of ``P``, ``G`` key heads of ``N``, a
    causal depthwise convolution of ``K`` taps, no bias)::

        [q | k | v | z] = h W_in       (d -> G N + G N + H P + H P);   [b | a] = h W_ba  (d -> H + H)
        [q | k | v]_t = silu(sum_j w_j [q | k | v]_{t-K+1+j})       (float32)
        beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)
        q, k L2-normalised a head (eps 1e-6), q times N^-0.5
        S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;  o_t = S^T q_t
        out = (RMSNorm_P(o_t) * w_norm * silu(z_t)) W_out     (the norm a head, THEN the gate)

    ONE set of parameters and :class:`_Mamba2Mixer`'s two paths: whole
    sequences through :func:`gated_delta_chunked` over ``call.runs``, the
    state leaving a prompt at its TRUE length (a pad token has ``beta =
    0``, ``g = 0`` and no convolution input); one token a lane
    (``decode``) through :func:`gdn_decode_update` on the carried state.
    ``cache`` holds ``ssm [lanes, H, N, P]`` and ``conv [lanes, K - 1, 2 G
    N + H P]``, both float32.  Returns ``(out [B, T, d], cache)``."""

    d_model: int
    spec: BlockSpec
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h, call: Call, cache: Optional[ModelCache]):
        s = self.spec
        f32 = jnp.float32
        Bt, T, _ = h.shape
        H, P, G, N, K = s.ssm_heads, s.ssm_head_dim, s.ssm_groups, s.ssm_state, s.ssm_conv
        keys, values, channels = G * N, H * P, s.conv_channels
        dt_kw = dict(use_bias=False, dtype=self.dtype, param_dtype=self.param_dtype)
        conv_w = self.param("conv_w", _tap_init(K), (K, channels))
        dt_bias = self.param("dt_bias", _dt_bias_init, (H,))  # as the Mamba mixer's
        A_log = self.param(
            "A_log",
            lambda key, shape: jnp.log(jnp.maximum(jax.random.uniform(key, shape, f32, 0.0, 16.0), 1e-4)),
            (H,),
        )
        norm_scale = self.param("norm_scale", nn.initializers.ones, (P,), f32)
        if not self.is_initializing():
            _note_gdn_form(tuple(h.shape), s.ssm_chunk, (H, N, P), G, call.mode == "decode")

        qkvz = nn.Dense(channels + values, name="in_proj", **dt_kw)(h)
        ba = nn.Dense(2 * H, name="ba_proj", **dt_kw)(h).astype(f32)
        u = qkvz[..., :channels].astype(f32)  # the convolution's input
        z = qkvz[..., channels:]
        beta = jax.nn.sigmoid(ba[..., :H])
        g = -jnp.exp(A_log) * jax.nn.softplus(ba[..., H:] + dt_bias)

        def split(qkv):
            qkv = jax.nn.silu(qkv)
            lead = qkv.shape[:-1]
            l2 = lambda a: a * lax.rsqrt(jnp.sum(jnp.square(a), axis=-1, keepdims=True) + 1e-6)  # noqa: E731
            return (
                l2(qkv[..., :keys].reshape(*lead, G, N)) * N ** -0.5,
                l2(qkv[..., keys : 2 * keys].reshape(*lead, G, N)),
                qkv[..., 2 * keys :].reshape(*lead, H, P),
            )

        if call.mode == "decode":
            window, mixed = _decode_window(cache, u, conv_w)
            q, k, v = split(mixed)
            o, ssm = gdn_decode_update(cache.ssm[0], q, k, v, g[:, 0], beta[:, 0])
            o = o[:, None]  # [lanes, 1, H, P]
            cache = ModelCache(ssm=(ssm,), conv=(window[:, 1:],))
        else:
            runs, real = _runs_and_real(call, Bt, T)
            u = jnp.where(real[..., None], u, 0.0)
            g = jnp.where(real[..., None], g, 0.0)
            beta = jnp.where(real[..., None], beta, 0.0)
            q, k, v = split(_causal_taps(u, conv_w, runs))
            o, last = gated_delta_chunked(q, k, v, g, beta, runs, s.ssm_chunk)
            cache = _prefill_state(call, cache, last, u, real)
        # the norm a head, then the gate
        ms = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
        gated = (
            o * lax.rsqrt(ms + s.norm_eps) * norm_scale
            * jax.nn.silu(z.astype(f32)).reshape(Bt, T, H, P)
        )
        out = nn.Dense(self.d_model, name="out_proj", **dt_kw)(
            gated.reshape(Bt, T, values).astype(self.dtype)
        )
        return out, cache


def cca_window_shape(spec: BlockSpec, num_heads: int, head_dim: int) -> Tuple[int, int]:
    """``(rows, channels)`` of a ``cca`` attention's window: the
    ``cca_time0 + cca_time1 - 2`` tokens its two convolutions reach back
    over, each ``[q~ | k~ | h W_v2]`` (the query and key latents every
    head, and the half of the values that the NEXT token takes)."""
    kv = spec.kv_heads or num_heads
    return (
        spec.cca_time0 + spec.cca_time1 - 2,
        (num_heads + kv) * head_dim + kv * head_dim // 2,
    )


@functools.lru_cache(maxsize=None)
def _note_cca_form(shape, heads, taps, rotary_dim, window_shape, path) -> None:
    """Which path of the compressed convolutional attention a traced
    shape took: one zero-length program span a shape (the cache is the
    "once")."""
    from scalerl_tpu.runtime import tracing

    with tracing.span(
        "cca.form", kind="model", shape=list(shape), heads=list(heads),
        taps=list(taps), rotary_dim=rotary_dim, window_shape=list(window_shape),
        window_dtype="float32", path=path,
    ):
        pass


class _CompressedConvAttention(nn.Module):
    """Compressed convolutional attention (CCA, the ``zaya`` family) on a
    normed input ``h [B, T, d]``: ``H`` query heads over ``KV`` key/value
    heads of ``D``; anything before a sequence's (a run's) first token is
    zero::

        q~_t = h_t W_q;  k~_t = h_t W_k;  u_t = [q~_t | k~_t]        (H + KV heads of D)
        v_t  = [h_t W_v1 | h_{t-1} W_v2], cut into the KV heads in that order   (the value shift)
        c_t  = b + sum_j w_j u_{t-K0+1+j}                             depthwise, K0 taps
        d_t[g] = b'_g + sum_i c_{t-K1+1+i}[g] M_{i,g}                 grouped, K1 taps; M_{i,g} [D, D]
        q_t[i] = d_t[i] + (q~_t[i] + k~_t[i // (H / KV)]) / 2         the q-k mean, on the latents
        k_t[j] = d_t[H + j] + (mean_{i // (H / KV) = j} q~_t[i] + k~_t[j]) / 2
        q <- sqrt(D) q / |q|;  k <- tau_j sqrt(D) k / |k|             float32; tau a key head
        rotary on a head's first ``rotary_dim`` features of q and k, then :func:`_attend`
        out = o W_o                                                   (H D -> d)

    The input is padded ONCE, by ``K0 + K1 - 2`` zeros: ``c`` before a
    sequence's start is ``b``, not zero.  The grouped convolution mixes a
    head's ``D`` channels and never two heads.  Everything between the
    projections and the rotation is float32 (the grouped products at
    ``HIGHEST`` precision).

    ONE set of parameters, the recurrent mixers' two paths:

    - **whole sequences** (every form but ``decode``): shifted arrays cut
      at every run's start (``call.runs``: :func:`_causal_taps`,
      :func:`_earlier`); a ``prefill`` writes the last ``K0 + K1 - 2``
      REAL tokens' ``[u | h W_v2]`` to the window (:func:`_last_taps`: the
      prompt's true length, whatever its bucket).
    - **one token a lane** (``decode``): the carried window and this
      token's row are the ``K0 + K1 - 1`` tokens both convolutions read.

    ``cache`` holds the layer's ``k`` and ``v`` pools (``KV x D`` a token,
    K normed and rotated) and ``conv [lanes, (K0 + K1 - 2) x ((H + KV) D +
    KV D / 2)]`` float32 (:func:`cca_window_shape`'s rows side by side on
    the minor axis, so that the lanes tile and a row is a whole number of
    128-lane tiles; the older rows' value part is carried and not read),
    a ring: the token at position ``p`` lies in row ``p mod rows``, a
    decoded token is selected over the oldest row (an elementwise pass
    over the carried buffer) and no row moves.  Returns ``(out [B,
    T, d], cache)``."""

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
    def __call__(self, h, call: Call, cache: Optional[ModelCache]):
        B, T, _ = h.shape
        s, H = self.spec, self.num_heads
        KV = s.kv_heads or H
        D = s.head_dim or self.d_model // H
        K0, K1 = s.cca_time0, s.cca_time1
        G, per, half = H + KV, H // KV, KV * D // 2
        R, C = cca_window_shape(s, H, D)[0], G * D  # the window's rows, the convolutions' channels
        f32, hi = jnp.float32, lax.Precision.HIGHEST

        def dense(width, name):
            return nn.Dense(
                width, use_bias=False, name=name, dtype=self.dtype, param_dtype=self.param_dtype
            )

        conv_w = self.param("conv_w", _tap_init(K0), (K0, C))
        conv_b = self.param("conv_b", _tap_init(K0), (C,))
        mix_w = self.param(
            "mix_w",
            nn.initializers.variance_scaling(
                1.0, "fan_in", "truncated_normal", in_axis=(0, 2), out_axis=3, batch_axis=1
            ),
            (K1, G, D, D), f32,
        )
        mix_b = self.param("mix_b", _tap_init(K1 * D), (C,))
        k_temp = self.param("k_temp", nn.initializers.ones, (KV,), f32)
        if not self.is_initializing():
            _note_cca_form(
                tuple(h.shape), (H, KV, D), (K0, K1), s.rotary_dim or D, cca_window_shape(s, H, D),
                call.mode if call.mode in ("decode", "prefill", "packed") else "whole",
            )

        u = jnp.concatenate([dense(H * D, "q")(h), dense(KV * D, "k")(h)], axis=-1).astype(f32)
        v1, v2 = dense(half, "v1")(h), dense(half, "v2")(h).astype(f32)
        with jax.named_scope("cca_window"):
            row = jnp.concatenate([u, v2], axis=-1)  # what the window carries of a token
            if call.mode == "decode":
                ring = jnp.split(cache.conv[0], R, axis=-1)  # rows side by side
                at = call.attn_lengths - 1  # this token's position (a dead lane's: 0)
                # oldest first: the token ``R - i`` back lies in row ``(at +
                # i) mod R``; selects over the rows, not a gather, so that
                # the ring is read and then written where it lies by
                # elementwise passes
                lies_in = ((at[:, None] + jnp.arange(R)) % R)[:, :, None] == jnp.arange(R)
                earlier = [
                    sum(jnp.where(lies_in[:, i, j, None], ring[j], 0.0) for j in range(R))
                    for i in range(R)
                ]
                window = jnp.stack(earlier + [row[:, 0]], axis=1)  # [lanes, K0 + K1 - 1, .]
                taps = window[..., :C]
                # c of the K1 latest tokens, each from its own K0 rows
                cs = jnp.stack(
                    [conv_b + jnp.sum(taps[:, i : i + K0] * conv_w, axis=1) for i in range(K1)],
                    axis=1,
                )[:, None]  # [lanes, 1, K1, C]
                v2_prev = window[:, -2:-1, C:]
            else:
                runs, real = _runs_and_real(call, B, T)
                c = _causal_taps(u, conv_w, runs, conv_b)
                # before a run's start c is the bias alone
                cs = jnp.stack(
                    [_earlier(c, runs, back, conv_b) for back in range(K1 - 1, 0, -1)] + [c],
                    axis=2,
                )  # [B, T, K1, C]
                v2_prev = _earlier(v2, runs, 1)
            d = mix_b + jnp.einsum(
                "btkgd,kgde->btge", cs.reshape(B, T, K1, G, D), mix_w, precision=hi
            ).reshape(B, T, C)
            q_lat = u[..., : H * D].reshape(B, T, KV, per, D)
            k_lat = u[..., H * D :].reshape(B, T, KV, D)
            q = d[..., : H * D].reshape(B, T, KV, per, D) + (q_lat + k_lat[:, :, :, None]) / 2
            k = d[..., H * D :].reshape(B, T, KV, D) + (jnp.mean(q_lat, axis=3) + k_lat) / 2
            unit = lambda a: a * lax.rsqrt(jnp.sum(jnp.square(a), axis=-1, keepdims=True) + 1e-6) * D ** 0.5  # noqa: E731
            q = unit(q).reshape(B, T, H, D)
            k = unit(k) * k_temp[:, None]
            v = jnp.concatenate([v1, v2_prev.astype(self.dtype)], axis=-1).reshape(B, T, KV, D)
        # before every cache write: K is stored normed and rotated
        q, k = self.rotary(q).astype(self.dtype), self.rotary(k).astype(self.dtype)
        out, pools = _attend(self, q, k, v, call, cache)
        if call.mode == "decode":
            # over the oldest row, where it lies: nothing shifts
            oldest = (at % R)[:, None] == jnp.arange(R)
            ring = [jnp.where(oldest[:, i, None], row[:, 0], ring[i]) for i in range(R)]
            pools = pools._replace(conv=(jnp.concatenate(ring, axis=-1),))
        elif call.mode == "prefill":
            tail = _last_taps(row, real, R)  # oldest first, of each row's TRUE length
            order = (jnp.arange(R)[None, :] - jnp.sum(real, axis=1)[:, None]) % R
            tail = jnp.take_along_axis(tail, order[..., None], axis=1).reshape(B, -1)
            pools = pools._replace(
                conv=(cache.conv[0].at[call.state_lanes].set(tail, mode="drop"),)
            )
        out = dense(self.d_model, "proj")(out.reshape(B, T, H * D))
        return out, pools


class _MixerBlock(_Layer):
    """A layer of one mixer (``layer="mixer"``): ``x + Mixer(N(x))``, the
    mixer ``spec.mixer``'s: a Mamba-2 mixer, this spec's attention alone
    (:func:`_mha`), the routed experts beside their shared expert, or a
    dense FFN (the last two cache nothing: their entry is empty)."""

    @nn.compact
    def __call__(self, x, call: Call, cache: Optional[ModelCache] = None, r=None):
        spec = self.spec
        _one_row("mixer", spec)
        dt = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        h = _norm(spec, self.dtype, "norm")(x)
        if spec.mixer == "mamba":
            out, cache = _Mamba2Mixer(self.d_model, spec, name="mixer", **dt)(h, call, cache)
        elif spec.mixer == "attention":
            out, cache = _mha(self, h, call, cache)
        elif spec.mixer == "experts":
            out = _routed_experts(spec, dt)(h)
            if spec.shared_experts:
                # always on, computed where the token lives
                out = out + _dense_ffn(
                    spec, self.d_model,
                    spec.shared_width or spec.shared_experts * spec.expert_width,
                    "shared", dt,
                )(h)
        else:
            out = _dense_ffn(spec, self.d_model, spec.ffn_hidden, "ffn", dt)(h)
        return x + out, cache, r


class _ShortcutBlock(_Layer):
    """The shortcut-connected double layer (``layer="scmoe"``), ``N`` an
    RMSNorm of its own at each use::

        x1 = x + MLA_0(N(x));  h = N(x1);  m = MoE(h)
        x2 = x1 + FFN_0(h)
        x3 = x2 + MLA_1(N(x2))
        out = x3 + FFN_1(N(x3)) + m

    The routed experts read what the first dense FFN reads and their sum
    joins after the second, so the expert branch can run beside the
    layer's second half.  Two attentions: the layer owns two latent
    pools, one each.  (``mlp_ratio`` is not read: both FFNs are
    ``spec.ffn_hidden`` wide.)"""

    @nn.compact
    def __call__(self, x, call: Call, cache: Optional[ModelCache] = None, r=None):
        spec = self.spec
        _one_row("scmoe", spec)
        dt = dict(dtype=self.dtype, param_dtype=self.param_dtype)

        def attention(i, x):
            own = ModelCache(rows=cache.rows[i : i + 1]) if call.paged else None
            out, own = _latent_attention(self, f"attn_{i}")(
                _norm(spec, self.dtype, f"attn_norm_{i}")(x), call, own
            )
            return x + out, own

        def ffn(i, h):
            return _GatedMLP(self.d_model, spec.ffn_hidden, name=f"ffn_{i}", **dt)(h)

        x, first = attention(0, x)
        h = _norm(spec, self.dtype, "ffn_norm_0")(x)
        m = _routed_experts(spec, dt)(h)
        x = x + ffn(0, h)
        x, second = attention(1, x)
        x = x + ffn(1, _norm(spec, self.dtype, "ffn_norm_1")(x)) + m
        return x, _join((first, second)) if call.paged else None, r


# the class of each ``BlockSpec.layer``
_LAYERS = {"plain": _Block, "mixer": _MixerBlock, "scmoe": _ShortcutBlock}


class _MTPModule(_Layer):
    """One multi-token-prediction module (the DeepSeek-V3 report's section
    2.2): ``h'_i = W_eh [RMSNorm_h(h_i) ; RMSNorm_e(Emb(t_{i+1}))]``, then
    one layer of the stack's repeating kind with weights of its own, at
    position ``i``'s rotary angle and under the caller's attention rule.
    ``x`` is the trunk's last layer output (before the final norm),
    ``next_emb`` the shared embedding of each position's next token and
    ``has_next [B, T]`` whether that token is in the position's own
    sequence: where it is not, the embedding's half is zero, so nothing of
    a neighbouring segment enters.  The caller norms the result and scores
    it with the shared head; it predicts ``t_{i+2}``.  It runs in the
    forms without a cache alone."""

    @nn.compact
    def __call__(self, x, next_emb, has_next, call: Call):
        eps = self.spec.norm_eps
        h = RMSNorm(eps, dtype=self.dtype, name="h_norm")(x)
        e = RMSNorm(eps, dtype=self.dtype, name="e_norm")(next_emb)
        e = e * has_next[..., None].astype(self.dtype)
        y = nn.Dense(
            self.d_model, use_bias=False, name="eh_proj", dtype=self.dtype,
            param_dtype=self.param_dtype,
        )(jnp.concatenate([h, e], axis=-1))
        y, _none, _r = _Block(
            self.d_model, self.num_heads, self.mlp_ratio, self.attn_fn,
            dtype=self.dtype, param_dtype=self.param_dtype,
            segment_attn_fn=self.segment_attn_fn, spec=self.spec,
            rotary=self.rotary, name="block",
        )(y, call)
        return y


@functools.lru_cache(maxsize=None)
def _note_layers(shape, kinds, attention, held, num_experts, mtp_layers, residual) -> None:
    """A stack always runs the layers it was built from, so its counter is
    its make-up: one zero-length program span a traced shape (the cache is
    the "once"), so that a trace says which stack ran."""
    from scalerl_tpu.runtime import tracing

    with tracing.span(
        "model.layers", kind="model", shape=list(shape), layers=list(kinds),
        attention=attention, held=held, num_experts=num_experts,
        mtp_layers=mtp_layers, residual=residual,
    ):
        pass


_MHC_PATHS = {"causal": "whole", "masked": "whole"}  # the other forms: their own names


@functools.lru_cache(maxsize=None)
def _note_mhc_form(shape, spec: BlockSpec, stream_dtype, sublayers, path) -> None:
    """A stream of rows and what mixes it: one zero-length program span a
    traced shape (the cache is the "once")."""
    from scalerl_tpu.runtime import tracing

    with tracing.span(
        "mhc.form", kind="model", shape=list(shape), streams=spec.streams,
        iters=spec.hc_iters, eps=spec.hc_eps, clamp=list(spec.hc_clamp),
        map_dtype="float32", stream_dtype=stream_dtype, sublayers=sublayers,
        path=path,
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
    # Under ``block.streams > 1`` the residual stream between the layers is
    # a stream of rows ``[B, T, streams, d]``: this class copies the
    # embedding into every row before the first layer and sums the rows
    # before the final norm (:class:`_HyperMix` mixes them in between).
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

    @property
    def recurrent(self) -> bool:
        """Whether a layer of the stack is a recurrence (a Mamba-2 or a
        Gated DeltaNet mixer)."""
        return any(s.recurrent for s in self.layer_specs)

    @property
    def lane_state(self) -> bool:
        """Whether a lane carries rows of any layer (``BlockSpec.
        lane_state``): what the engine asks before it serves a prefix hit
        or a speculative draft, which a page table alone must describe."""
        return any(s.lane_state for s in self.layer_specs)

    def init_paged_cache(
        self, num_pages: int, page_size: int, dtype=jnp.float32, lanes: int = 0
    ) -> ModelCache:
        """The zeroed cache this model's layers cache into
        (:class:`ModelCache`): the cache is described by the model, layer
        by layer (``BlockSpec.owns``), and everything that holds it (the
        engine and its programs) treats it as one pytree.  Page pools are
        ``[num_pages, page_size, width]`` of ``dtype`` (page 0 = the
        never-read null page); what a lane carries (a recurrent layer's
        state, a window) is float32 and indexed by ``lanes``."""
        if self.lane_state and lanes < 1:
            raise ValueError("a cache that lanes carry rows of is sized by its lanes")

        def shape(s: BlockSpec, name: str):
            if name == "ssm":
                return (lanes,) + s.state_shape
            if name == "conv" and s.attention == "cca":
                rows, channels = cca_window_shape(s, self.num_heads, self.head_dim)
                return (lanes, rows * channels)
            if name == "conv":
                return (lanes, s.ssm_conv - 1, s.conv_channels)
            if name == "rows":
                return (num_pages, page_size, latent_pool_width(s.kv_lora_rank + s.qk_rope_head_dim))
            return (num_pages, page_size, (s.kv_heads or self.num_heads) * self.head_dim)

        return ModelCache(*(
            tuple(
                jnp.zeros(shape(s, name), dtype if name in _PAGE_FIELDS else jnp.float32)
                for s in self.layer_specs
                for _ in range(s.owns.get(name, 0))
            )
            for name in ModelCache._fields
        ))

    @nn.compact
    def __call__(
        self,
        obs: jnp.ndarray,
        positions: Optional[jnp.ndarray] = None,
        attn_mask: Optional[jnp.ndarray] = None,
        paged_cache: Optional[ModelCache] = None,
        page_ids: Optional[jnp.ndarray] = None,
        page_offsets: Optional[jnp.ndarray] = None,
        page_table: Optional[jnp.ndarray] = None,
        attn_lengths: Optional[jnp.ndarray] = None,
        prefix_starts: Optional[jnp.ndarray] = None,
        segment_ids: Optional[jnp.ndarray] = None,
        mtp: bool = False,
        state_lanes: Optional[jnp.ndarray] = None,
    ):
        """One forward in the form the arguments spell (:class:`Call`:
        ``causal``, ``masked``, ``packed``, or ``prefill`` / ``tail`` /
        ``decode`` on a ``paged_cache``); an argument set that spells none
        raises ``ValueError``.  Returns :class:`TransformerOutput`, and
        ``(TransformerOutput, the cache written)`` on a cache.
        ``mtp=True`` (a model with ``mtp_layers``, no cache): also run the
        multi-token-prediction module over the same rows and return its
        logits as ``mtp_logits`` (:class:`_MTPModule`)."""
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
                tuple(obs.shape),
                tuple(s.kind for s in specs),
                spec.attention, spec.experts_held or spec.num_experts,
                spec.num_experts, self.mtp_layers, spec.residual,
            )
        if spec.streams > 1 and self.mtp_layers:
            raise ValueError(
                "mtp_layers > 0 with a residual stream of more than one row: "
                "how a multi-token-prediction module reads a stream of rows is "
                "no key of any configuration here, so none is built (a rollout "
                "worker without speculation drops the module anyway)"
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
        call = Call.of(
            lane_state=self.lane_state, segment_kernel=self.segment_attn_fn is not None,
            mtp=mtp, paged_cache=paged_cache, attn_mask=attn_mask,
            segment_ids=segment_ids, page_ids=page_ids, page_offsets=page_offsets,
            page_table=page_table, attn_lengths=attn_lengths,
            prefix_starts=prefix_starts, state_lanes=state_lanes,
        )
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
                spec.rope_pairing, spec.rope_scaling,
            )
            if spec.rope_scaling is not None and not self.is_initializing():
                terms = yarn_terms(spec.rope_scaling, spec.qk_rope_head_dim, float(spec.rope_theta))
                _note_rope_form(
                    tuple(obs.shape), float(spec.rope_scaling.factor), terms.low, terms.high,
                    terms.softmax_factor / self.head_dim ** 0.5,
                )
        elif spec.positions == "rope":
            rotary = rotary_fn(
                positions, spec.rotary_dim or self.head_dim, spec.rope_theta
            )
        elif spec.positions == "learned":
            pos_tab = self.param(
                "pos_embed",
                nn.initializers.normal(0.02),
                (self.max_len, self.d_model),
                self.param_dtype,
            )
            x = x + pos_tab[positions].astype(self.dtype)
        x = c(x)
        if spec.streams > 1:
            # the expansion: a token's embedding in every row of its stream
            x = c(jnp.broadcast_to(x[:, :, None, :], (B, T, spec.streams, self.d_model)))
            if not self.is_initializing():
                _note_mhc_form(
                    tuple(x.shape), spec, jnp.dtype(self.dtype).name,
                    2 * len(specs),
                    "packed" if segment_ids is not None else _MHC_PATHS.get(call.mode, call.mode),
                )
        entries = _layer_entries(paged_cache, specs) if call.paged else [None] * len(specs)
        r = None  # the stack's second stream (:class:`_Layer`)
        for i, layer in enumerate(specs):
            block = _LAYERS[layer.layer](
                self.d_model, self.num_heads, self.mlp_ratio, attn,
                dtype=self.dtype, param_dtype=self.param_dtype,
                paged_attn_fn=self.paged_attn_fn,
                segment_attn_fn=self.segment_attn_fn, spec=layer, rotary=rotary,
                name=f"block_{i}",
            )
            x, entries[i], r = block(x, call, entries[i], r)
            x = c(x)
        if spec.streams > 1:
            # the read-out: the rows' sum, in the final norm's float32
            x = jnp.sum(x.astype(jnp.float32), axis=2)
        final_norm = functools.partial(_norm, spec, jnp.float32)
        policy_head = nn.Dense(self.num_actions, name="policy_head")
        mtp_logits = None
        if run_mtp:
            # the module reads the trunk's last layer output and the shared
            # embedding of each position's next token, and is scored by the
            # shared head behind a norm of its own
            y = _MTPModule(
                self.d_model, self.num_heads, self.mlp_ratio, attn,
                dtype=self.dtype, param_dtype=self.param_dtype,
                segment_attn_fn=self.segment_attn_fn, spec=spec, rotary=rotary,
                name="mtp",
            )(x, embed(jnp.roll(obs.astype(jnp.int32), -1, axis=1)), has_next, call)
            mtp_logits = policy_head(
                final_norm("mtp_final_norm")(c(y).astype(jnp.float32))
            )
        x = final_norm("final_norm")(x.astype(jnp.float32))
        policy_logits = policy_head(x)
        baseline = nn.Dense(1, name="value_head")(x).squeeze(-1)
        out = TransformerOutput(policy_logits, baseline, mtp_logits)
        return (out, _join(entries)) if call.paged else out
