"""Bytes and operations the Qwen3-Next stack has to move, from shapes.

Model numbers, like ``work.py``'s, ``moe_work.py``'s, ``longcat_work.py``'s
and ``nemotron_work.py``'s (which stay as they are): what a decode substep
has to read and write, not what a compiler chose to.  The configuration
dict is ``configs/qwen3-next-80b-a3b.json``; nothing here reads the
program.  A decode substep is bound by bytes: every matrix outside the
routed experts is read once a substep whatever the number of lanes, a
routed expert's three matrices are read if it is HELD here (every held
one in the streamed form that few tokens take), every live lane reads and
writes the matrix state of every Gated DeltaNet layer once (its size does
not depend on the lane's length), and reads the keys and values of its
own context in the few attention layers.
"""

from __future__ import annotations

from typing import Mapping


def layer_counts(cfg: Mapping) -> Mapping[str, int]:
    """Layers of each mixer: layer ``i`` (from 0) is full attention where
    ``(i + 1) % full_attention_interval == 0``; every layer has experts."""
    layers = int(cfg["num_hidden_layers"])
    attention = layers // int(cfg["full_attention_interval"])
    return {"gdn": layers - attention, "attention": attention, "experts": layers}


def gdn_key_dim(cfg: Mapping) -> int:
    return cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]


def gdn_value_dim(cfg: Mapping) -> int:
    return cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]


def gdn_channels(cfg: Mapping) -> int:
    """What the convolution runs over: ``[q | k | v]``."""
    return 2 * gdn_key_dim(cfg) + gdn_value_dim(cfg)


def gdn_params(cfg: Mapping) -> int:
    """One Gated DeltaNet mixer: W_in (``[q | k | v | z]``), W_ba, the
    convolution's taps, W_out, ``A_log`` and ``dt_bias`` a value head, the
    gated norm's scale."""
    d, heads = cfg["hidden_size"], cfg["linear_num_value_heads"]
    return (
        d * (gdn_channels(cfg) + gdn_value_dim(cfg)) + d * 2 * heads
        + cfg["linear_conv_kernel_dim"] * gdn_channels(cfg) + gdn_value_dim(cfg) * d
        + 2 * heads + cfg["linear_value_head_dim"]
    )


def attention_params(cfg: Mapping) -> int:
    """One gated attention: W_q (a head ``[q | gate]``), W_kv, W_o, the two
    per-head norm scales."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    q = cfg["num_attention_heads"] * dh
    return 2 * d * q + 2 * d * cfg["num_key_value_heads"] * dh + q * d + 2 * dh


def expert_params(cfg: Mapping) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_expert_params(cfg: Mapping) -> int:
    """The shared SwiGLU and its scalar gate's vector."""
    return 3 * cfg["hidden_size"] * cfg["shared_expert_intermediate_size"] + cfg["hidden_size"]


def router_params(cfg: Mapping) -> int:
    """The router scores the PUBLISHED number of experts."""
    return cfg["hidden_size"] * cfg["num_experts_published"]


def decode_dense_bytes(cfg: Mapping, block_bytes: int, head_bytes: int) -> int:
    """Bytes one decode substep reads whatever was routed where: every
    Gated DeltaNet and attention mixer's matrices, every layer's router
    and shared expert (``block_bytes`` a value) and the policy head
    (``head_bytes`` a value).  Norm scales are counted with their mixers
    at ``block_bytes`` (they are float32 and under 0.01%), and the
    embedding is a gather of one row a lane."""
    n = layer_counts(cfg)
    blocks = (
        n["gdn"] * gdn_params(cfg) + n["attention"] * attention_params(cfg)
        + n["experts"] * (router_params(cfg) + shared_expert_params(cfg))
    )
    return blocks * block_bytes + cfg["hidden_size"] * cfg["vocab_size"] * head_bytes


def expert_bytes(cfg: Mapping, block_bytes: int) -> int:
    """Bytes of one held expert's three matrices."""
    return expert_params(cfg) * block_bytes


def decode_expert_bytes(cfg: Mapping, substeps: float, block_bytes: int) -> float:
    """Bytes of routed experts' matrices that ``substeps`` decode substeps
    had to read: every held bank of every layer once a substep, whoever
    was picked.  A substep's tokens (one a lane, far under
    ``models/routed_ffn.py``'s ``STREAMED_MAX_TOKENS`` 512) take the
    streamed form, which multiplies every held bank by every token and
    masks: the bytes follow the algorithm the shapes choose, not the
    router's luck."""
    banks = layer_counts(cfg)["experts"] * cfg["num_experts"]
    return substeps * banks * expert_bytes(cfg, block_bytes)


def kv_bytes_per_token(cfg: Mapping, bytes_per_value: int) -> int:
    """Bytes of K and V one cached token holds over the ATTENTION layers:
    ``kv_heads x head_dim`` each, the pool's row as the program stores it."""
    return (
        2 * layer_counts(cfg)["attention"] * cfg["num_key_value_heads"] * cfg["head_dim"]
        * bytes_per_value
    )


def gdn_state_bytes(cfg: Mapping) -> int:
    """Bytes of one lane's matrix state in ONE Gated DeltaNet layer:
    ``value heads x key x value`` float32, what ``gdn_decode_update`` has
    to read once and write once a substep."""
    return 4 * cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"]


def conv_state_bytes(cfg: Mapping) -> int:
    """Bytes of one lane's convolution window in one such layer."""
    return 4 * (cfg["linear_conv_kernel_dim"] - 1) * gdn_channels(cfg)


def state_bytes_per_lane(cfg: Mapping) -> int:
    """Bytes of recurrent state a lane carries, all Gated DeltaNet layers:
    what the engine's ``stats()['state_bytes_per_lane']`` has to agree
    with."""
    return layer_counts(cfg)["gdn"] * (gdn_state_bytes(cfg) + conv_state_bytes(cfg))


def gdn_decode_bytes_per_token(cfg: Mapping) -> int:
    """Bytes ``gdn_decode_update`` has to move for one decoded token (one
    live lane, one substep), all Gated DeltaNet layers: the state in and
    out, ONCE each (an update that reads it twice moves more than it has
    to and reads a lower share).  Its small operands (``q``, ``k``, ``v``,
    ``g``, ``beta``, ``o``) are under 1% and left out, which makes the
    roofline share a slight underestimate."""
    return 2 * layer_counts(cfg)["gdn"] * gdn_state_bytes(cfg)


def gdn_decode_flops_per_token(cfg: Mapping) -> int:
    """Operations of the same: per state element two multiply-adds into
    the two reductions (``S^T k``, ``S^T q``), a decay multiply and a
    rank-one multiply-add: 7.  At 8 bytes moved an element the update is
    bound by bytes (0.9 FLOP/B against the chip's 240)."""
    return 7 * layer_counts(cfg)["gdn"] * gdn_state_bytes(cfg) // 4


def recurrent_decode_bytes_per_token(cfg: Mapping) -> int:
    """Everything recurrent one decoded token moves: the state and the
    convolution window, in and out, all Gated DeltaNet layers."""
    return 2 * state_bytes_per_lane(cfg)
