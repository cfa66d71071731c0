"""Bytes and operations the Xing4.0 stack has to move, from shapes.

Model numbers, like ``work.py``'s, ``moe_work.py``'s, ``longcat_work.py``'s,
``nemotron_work.py``'s, ``qwen3next_work.py``'s and ``zaya_work.py``'s (which
stay as they are): what a decode substep has to read and write, not what a
compiler chose to.  The configuration dict is
``configs/xing4.0-29b-a4b.json``; nothing here reads the program.  A decode
substep is bound by bytes: every matrix outside the experts (the
attention's five projections, the shared expert, the router, the dense
layer's FFN, every hyper-connection's ``Phi``, the policy head) is read
once a substep whatever the number of lanes, every expert's three matrices
are read once a substep whoever was picked (a substep's few tokens take the
streamed form, which multiplies every bank by every token and masks), every
live lane reads the latent rows of its own context in every layer, and
every live lane's stream of rows is read once and written once by every
sublayer's hyper-connection.
"""

from __future__ import annotations

from typing import Mapping


def latent_row_width(cfg: Mapping) -> int:
    """Values one cached token holds in a layer's pool: the compressed KV
    and the rotated key part every head shares."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def latent_bytes_per_token(cfg: Mapping, bytes_per_value: int) -> int:
    """Bytes one cached token holds over all layers: one row a layer, no
    V.  (The pool stores a row in whole 128-lane tiles, 640 for 576: the
    pad is not something a kernel has to read.)"""
    return cfg["num_hidden_layers"] * latent_row_width(cfg) * bytes_per_value


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


def expert_params(cfg: Mapping) -> int:
    """One expert (routed or shared): gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_ffn_params(cfg: Mapping) -> int:
    """A leading dense layer's SwiGLU: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def router_params(cfg: Mapping) -> int:
    return cfg["hidden_size"] * cfg["n_routed_experts"]


def hyper_params(cfg: Mapping) -> int:
    """One hyper-connection: ``Phi [n d, n (n + 2)]``, its bias, three
    gains and the flattened norm's scale ``[n d]``; all float32."""
    n, d = cfg["hc_mult"], cfg["hidden_size"]
    return n * d * n * (n + 2) + n * (n + 2) + 3 + n * d


def sublayers(cfg: Mapping) -> int:
    """Hyper-connections in the stack: two a layer."""
    return 2 * cfg["num_hidden_layers"]


def routed_layers(cfg: Mapping) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def layer_params(cfg: Mapping, routed: bool) -> int:
    """A whole layer: the attention, two hyper-connections, and either
    the dense FFN or the router, the shared expert and every expert."""
    ffn = (
        router_params(cfg) + (cfg["n_shared_experts"] + cfg["n_routed_experts"]) * expert_params(cfg)
        if routed else dense_ffn_params(cfg)
    )
    return mla_params(cfg) + 2 * hyper_params(cfg) + ffn


def hyper_weight_bytes(cfg: Mapping) -> int:
    """Every hyper-connection's parameters once (float32)."""
    return 4 * sublayers(cfg) * hyper_params(cfg)


def decode_dense_bytes(cfg: Mapping, block_bytes: int, head_bytes: int) -> int:
    """Bytes one decode substep reads whatever was routed where: every
    layer's attention, the routed layers' router and shared expert and the
    dense layers' FFN (``block_bytes`` a value), every hyper-connection's
    float32 parameters, and the policy head (``head_bytes`` a value).  The
    embedding is a gather of one row a lane."""
    layers, routed = cfg["num_hidden_layers"], routed_layers(cfg)
    blocks = (
        layers * mla_params(cfg)
        + routed * (router_params(cfg) + cfg["n_shared_experts"] * expert_params(cfg))
        + (layers - routed) * dense_ffn_params(cfg)
    )
    return (
        blocks * block_bytes + hyper_weight_bytes(cfg)
        + cfg["hidden_size"] * cfg["vocab_size"] * head_bytes
    )


def decode_expert_bytes(cfg: Mapping, substeps: float, block_bytes: int) -> float:
    """Bytes of experts' matrices that ``substeps`` decode substeps had to
    read: every bank of every routed layer once a substep, whoever was
    picked (the streamed form; the bytes follow the algorithm the shapes
    choose, not the router's luck)."""
    banks = routed_layers(cfg) * cfg["n_routed_experts"]
    return substeps * banks * expert_params(cfg) * block_bytes


def stream_shape(cfg: Mapping, lanes: int):
    """The decode stream between sublayers, ``[lanes, 1, n, d]``."""
    return [lanes, 1, cfg["hc_mult"], cfg["hidden_size"]]


def stream_bytes_per_token(cfg: Mapping, stream_bytes: int) -> int:
    """What one decoded token moves of its stream of rows: every
    sublayer's hyper-connection reads ``[n, d]`` once (the flattened norm,
    the read mix and the write's ``H_res X`` all take that one pass) and
    writes it once, in the stream's dtype."""
    return sublayers(cfg) * 2 * cfg["hc_mult"] * cfg["hidden_size"] * stream_bytes


def mhc_bytes(cfg: Mapping, tokens: float, substeps: float, stream_bytes: int) -> float:
    """The hyper-connections' own bytes over ``tokens`` decoded tokens in
    ``substeps`` substeps: each token's stream in and out a sublayer, and
    every ``Phi`` (with its vectors) once a substep."""
    return tokens * stream_bytes_per_token(cfg, stream_bytes) + substeps * hyper_weight_bytes(cfg)


def decode_flops_per_token(cfg: Mapping) -> int:
    """Multiply-adds x 2 one decoded token needs by the model: the
    attention's projections, the router, the picked and the shared
    experts, the dense FFN, the hyper-connections' projection and their
    read and write, the head (attention over the context is left out: it
    is bound by bytes; the 20 iterations are 16 numbers a sublayer)."""
    n, d = cfg["hc_mult"], cfg["hidden_size"]
    routed = routed_layers(cfg)
    per_token = (
        cfg["num_hidden_layers"] * mla_params(cfg)
        + routed * (
            router_params(cfg)
            + (cfg["num_experts_per_tok"] + cfg["n_shared_experts"]) * expert_params(cfg)
        )
        + (cfg["num_hidden_layers"] - routed) * dense_ffn_params(cfg)
        + sublayers(cfg) * (n * d * n * (n + 2) + n * d + n * n * d + n * d)
        + d * cfg["vocab_size"]
    )
    return 2 * per_token
