"""Bytes and operations the Nemotron-H stack has to move, from shapes.

Model numbers, like ``work.py``'s, ``moe_work.py``'s and
``longcat_work.py``'s (which stay as they are): what a decode substep has
to read and write, not what a compiler chose to.  The configuration dict
is ``configs/nemotron-3-nano-30b-a3b.json``; nothing here reads the
program.  A decode substep is bound by bytes: every matrix outside the
routed experts is read once a substep whatever the number of lanes, a
routed expert's two matrices are read if it is HELD here (every held one
in the streamed form that few tokens take, else those a lane's token
picked), every live lane reads and writes the recurrent state of
every Mamba layer once (its size does not depend on the lane's length),
and reads the keys and values of its own context in the few attention
layers.
"""

from __future__ import annotations

from typing import Mapping


def layer_counts(cfg: Mapping) -> Mapping[str, int]:
    """Layers of each kind in ``hybrid_override_pattern``."""
    pattern = cfg["hybrid_override_pattern"]
    return {
        "mamba": pattern.count("M"), "experts": pattern.count("E"),
        "attention": pattern.count("*"), "ffn": pattern.count("-"),
    }


def ssm_inner(cfg: Mapping) -> int:
    """``d_inner``: heads x head size (not ``expand`` x hidden)."""
    return cfg["mamba_num_heads"] * cfg["mamba_head_dim"]


def ssm_channels(cfg: Mapping) -> int:
    """What the convolution runs over: ``[x | B | C]``."""
    return ssm_inner(cfg) + 2 * cfg["n_groups"] * cfg["ssm_state_size"]


def mamba_params(cfg: Mapping) -> int:
    """One Mamba-2 mixer: W_in, W_out, the convolution's taps and bias,
    ``A_log``, ``D``, ``dt_bias`` a head, the gated norm's scale."""
    d, inner, heads = cfg["hidden_size"], ssm_inner(cfg), cfg["mamba_num_heads"]
    return (
        d * (inner + ssm_channels(cfg) + heads) + inner * d
        + (cfg["conv_kernel"] + 1) * ssm_channels(cfg) + 3 * heads + inner
    )


def attention_params(cfg: Mapping) -> int:
    """One grouped-head attention: W_q, W_kv (K and V of the few
    key/value heads), W_o."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    q = cfg["num_attention_heads"] * dh
    return 2 * d * q + 2 * d * cfg["num_key_value_heads"] * dh


def expert_params(cfg: Mapping) -> int:
    """One routed expert: up and down, no gate."""
    return 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_expert_params(cfg: Mapping) -> int:
    return 2 * cfg["hidden_size"] * cfg["moe_shared_expert_intermediate_size"] * cfg["n_shared_experts"]


def router_params(cfg: Mapping) -> int:
    """The router scores the PUBLISHED number of experts."""
    return cfg["hidden_size"] * cfg["n_routed_experts_published"]


def dense_ffn_params(cfg: Mapping) -> int:
    return 2 * cfg["hidden_size"] * cfg["intermediate_size"]


def decode_dense_bytes(cfg: Mapping, block_bytes: int, head_bytes: int) -> int:
    """Bytes one decode substep reads whatever was routed where: every
    Mamba, attention and dense-FFN layer's matrices, every expert layer's
    router and shared expert (``block_bytes`` a value) and the policy head
    (``head_bytes`` a value).  Norm scales are not counted, and the
    embedding is a gather of one row a lane."""
    n = layer_counts(cfg)
    blocks = (
        n["mamba"] * mamba_params(cfg) + n["attention"] * attention_params(cfg)
        + n["experts"] * (router_params(cfg) + shared_expert_params(cfg))
        + n["ffn"] * dense_ffn_params(cfg)
    )
    return blocks * block_bytes + cfg["hidden_size"] * cfg["vocab_size"] * head_bytes


def expert_bytes(cfg: Mapping, block_bytes: int) -> int:
    """Bytes of one held expert's two matrices."""
    return expert_params(cfg) * block_bytes


def decode_expert_bytes(cfg: Mapping, substeps: float, block_bytes: int) -> float:
    """Bytes of routed experts' matrices that ``substeps`` decode substeps
    had to read: every held bank of every expert layer once a substep,
    whoever was picked.  A substep's tokens (one a lane, far under
    ``models/routed_ffn.py``'s ``STREAMED_MAX_TOKENS`` 512) take the
    streamed form, which multiplies every held bank by every token and
    masks: the bytes follow the algorithm the shapes choose, not the
    router's luck."""
    banks = layer_counts(cfg)["experts"] * cfg["n_routed_experts"]
    return substeps * banks * expert_bytes(cfg, block_bytes)


def kv_bytes_per_token(cfg: Mapping, bytes_per_value: int) -> int:
    """Bytes of K and V one cached token holds over the ATTENTION layers:
    ``kv_heads x head_dim`` each, the pool's row as the program stores it."""
    return (
        2 * layer_counts(cfg)["attention"] * cfg["num_key_value_heads"] * cfg["head_dim"]
        * bytes_per_value
    )


def ssm_state_bytes(cfg: Mapping) -> int:
    """Bytes of one lane's recurrent state in ONE Mamba layer: ``heads x
    head_dim x state`` float32, what ``ssm_decode_update`` reads once and
    writes once a substep."""
    return 4 * ssm_inner(cfg) * cfg["ssm_state_size"]


def conv_state_bytes(cfg: Mapping) -> int:
    """Bytes of one lane's convolution window in one Mamba layer."""
    return 4 * (cfg["conv_kernel"] - 1) * ssm_channels(cfg)


def state_bytes_per_lane(cfg: Mapping) -> int:
    """Bytes of recurrent state a lane carries, all Mamba layers: what the
    engine's ``stats()['state_bytes_per_lane']`` has to agree with."""
    return layer_counts(cfg)["mamba"] * (ssm_state_bytes(cfg) + conv_state_bytes(cfg))


def ssm_decode_bytes_per_token(cfg: Mapping) -> int:
    """Bytes ``ssm_decode_update`` has to move for one decoded token (one
    live lane, one substep), all Mamba layers: the state in and out.  Its
    small operands (``x``, ``B``, ``C``, ``dt``, ``y``) are under 1% and
    left out, which makes the roofline share a slight underestimate."""
    return 2 * layer_counts(cfg)["mamba"] * ssm_state_bytes(cfg)


def ssm_decode_flops_per_token(cfg: Mapping) -> int:
    """Operations of the same: per state element a decay multiply, an
    outer-product multiply-add and a multiply-add into ``y``: 5.  At 8
    bytes moved an element the kernel is bound by bytes (0.6 FLOP/B
    against the chip's 240)."""
    return 5 * layer_counts(cfg)["mamba"] * ssm_inner(cfg) * cfg["ssm_state_size"]


def recurrent_decode_bytes_per_token(cfg: Mapping) -> int:
    """Everything recurrent one decoded token moves: the state and the
    convolution window, in and out, all Mamba layers."""
    return 2 * state_bytes_per_lane(cfg)
