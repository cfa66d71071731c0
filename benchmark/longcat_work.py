"""Bytes the LongCat-Flash block has to read, computed from shapes.

Model numbers, like ``work.py``'s and ``moe_work.py``'s (which stay as
they are): what a decode substep has to read, not what a compiler chose to
read.  The configuration dict is ``configs/longcat-flash-chat.json``;
nothing here reads the program.  A decode substep is bound by bytes: every
weight matrix outside the experts is read once a substep whatever the
number of lanes, an expert's three matrices are read if any lane's token
picked it, a zero-compute expert and an expert another chip holds are
read by nobody, and each lane reads the latent rows of its own context.
"""

from __future__ import annotations

from typing import Mapping


def latent_row_width(cfg: Mapping) -> int:
    """Values one cached token holds in one attention's pool: the
    compressed KV and the rotated key part every head shares."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def latent_bytes_per_token(cfg: Mapping, bytes_per_value: int) -> int:
    """Bytes one cached token holds over all layers: two attentions a
    layer, one row each, no V.  (The pool stores a row in whole 128-lane
    tiles, 640 for 576: the pad is not something a kernel has to read.)"""
    return 2 * cfg["num_layers"] * latent_row_width(cfg) * bytes_per_value


def mla_params(cfg: Mapping) -> int:
    """One latent attention: W_qa, W_qb, W_kva, W_kvb, W_o."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (
        d * cfg["q_lora_rank"]
        + cfg["q_lora_rank"] * heads * qk
        + d * latent_row_width(cfg)
        + cfg["kv_lora_rank"] * heads * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
        + heads * cfg["v_head_dim"] * d
    )


def dense_ffn_params(cfg: Mapping) -> int:
    """One dense SwiGLU FFN: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["ffn_hidden_size"]


def router_outputs(cfg: Mapping) -> int:
    """The router's width: the published computed experts and the
    zero-compute ones."""
    return cfg["n_routed_experts_published"] + cfg["zero_expert_num"]


def layer_dense_params(cfg: Mapping) -> int:
    """A double layer's matrices outside the experts: two attentions, two
    dense FFNs, the router."""
    return (
        2 * mla_params(cfg) + 2 * dense_ffn_params(cfg)
        + cfg["hidden_size"] * router_outputs(cfg)
    )


def expert_params(cfg: Mapping) -> int:
    """One expert's gate, up and down matrices."""
    return 3 * cfg["hidden_size"] * cfg["expert_ffn_hidden_size"]


def decode_dense_bytes(cfg: Mapping, block_bytes: int, head_bytes: int) -> int:
    """Bytes one decode substep reads whatever was routed where: every
    layer's matrices outside the experts (``block_bytes`` a value) and the
    policy head (``head_bytes`` a value).  The embedding is a gather of
    one row a lane and is not counted."""
    return (
        cfg["num_layers"] * layer_dense_params(cfg) * block_bytes
        + cfg["hidden_size"] * cfg["vocab_size"] * head_bytes
    )


def expert_bytes(cfg: Mapping, block_bytes: int) -> int:
    """Bytes of one held expert that received a token in a substep."""
    return expert_params(cfg) * block_bytes
