"""Bytes and operations the ZAYA1 stack has to move, from shapes.

Model numbers, like ``work.py``'s, ``moe_work.py``'s, ``longcat_work.py``'s,
``nemotron_work.py``'s and ``qwen3next_work.py``'s (which stay as they
are): what a decode substep has to read and write, not what a compiler
chose to.  The configuration dict is ``configs/zaya1-8b.json``; nothing
here reads the program.  A decode substep is bound by bytes: every matrix
outside the experts (the attention's five projections, its two
convolutions, the router's four matrices, the policy head) is read once a
substep whatever the number of lanes, every expert's three matrices are
read once a substep whoever was picked (a substep's few tokens take the
streamed form, which multiplies every bank by every token and masks), every
live lane reads and writes the two-token window of every layer, and reads
the keys and values of its own context in every layer.
"""

from __future__ import annotations

from typing import Mapping


def conv_channels(cfg: Mapping) -> int:
    """What both convolutions run over: the query and key latents,
    ``(heads + kv_heads) x head_dim``."""
    return (cfg["num_attention_heads"] + cfg["num_key_value_heads"]) * cfg["head_dim"]


def window_channels(cfg: Mapping) -> int:
    """A window row: ``[u | h W_v2]``, the latents and the half of the
    values that the next token takes."""
    return conv_channels(cfg) + cfg["num_key_value_heads"] * cfg["head_dim"] // 2


def window_rows(cfg: Mapping) -> int:
    return cfg["cca_time0"] + cfg["cca_time1"] - 2


def attention_params(cfg: Mapping) -> int:
    """One CCA: W_q, W_k, W_v1, W_v2, W_o; the depthwise taps and bias,
    the grouped taps (a ``head_dim x head_dim`` matrix a tap a head) and
    bias, a temperature a key head."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * dh, cfg["num_key_value_heads"] * dh
    channels = conv_channels(cfg)
    groups = cfg["num_attention_heads"] + cfg["num_key_value_heads"]
    return (
        d * q + d * kv + 2 * d * (kv // 2) + q * d
        + cfg["cca_time0"] * channels + channels
        + cfg["cca_time1"] * groups * dh * dh + channels
        + cfg["num_key_value_heads"]
    )


def router_params(cfg: Mapping) -> int:
    """One ZAYA router: the down-projection and its bias, the carried
    state's scale, the norm's, two square matrices with biases, the
    output matrix, the choice bias."""
    d, w, e = cfg["hidden_size"], cfg["router_hidden_size"], cfg["num_experts"]
    return d * w + w + w + w + 2 * (w * w + w) + w * e + e


def expert_params(cfg: Mapping) -> int:
    """One expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_params(cfg: Mapping) -> int:
    """A whole layer: the attention, the router, every expert, two norms
    and the two residual merges' four vectors each."""
    return (
        attention_params(cfg) + router_params(cfg)
        + cfg["num_experts"] * expert_params(cfg) + 10 * cfg["hidden_size"]
    )


def decode_dense_bytes(cfg: Mapping, block_bytes: int, head_bytes: int) -> int:
    """Bytes one decode substep reads whatever was routed where: every
    layer's attention and router (``block_bytes`` a value: the float32
    convolution taps and vectors are 0.3 M of a layer's 6.2 M and are
    counted at ``block_bytes``, a slight underestimate) and the policy
    head (``head_bytes`` a value).  The embedding is a gather of one row a
    lane."""
    blocks = cfg["num_hidden_layers"] * (
        attention_params(cfg) + router_params(cfg) + 10 * cfg["hidden_size"]
    )
    return blocks * block_bytes + cfg["hidden_size"] * cfg["vocab_size"] * head_bytes


def expert_bytes(cfg: Mapping, block_bytes: int) -> int:
    return expert_params(cfg) * block_bytes


def decode_expert_bytes(cfg: Mapping, substeps: float, block_bytes: int) -> float:
    """Bytes of experts' matrices that ``substeps`` decode substeps had to
    read: every bank of every layer once a substep, whoever was picked.
    A substep's tokens (one a lane, far under ``models/routed_ffn.py``'s
    ``STREAMED_MAX_TOKENS``) take the streamed form: the bytes follow the
    algorithm the shapes choose, not the router's luck."""
    banks = cfg["num_hidden_layers"] * cfg["num_experts"]
    return substeps * banks * expert_bytes(cfg, block_bytes)


def kv_bytes_per_token(cfg: Mapping, bytes_per_value: int) -> int:
    """Bytes of K and V one cached token holds over all layers: ``kv_heads
    x head_dim`` each, the pool's row as the program stores it."""
    return (
        2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] * cfg["head_dim"]
        * bytes_per_value
    )


def state_bytes_per_lane(cfg: Mapping) -> int:
    """Bytes of window a lane carries, all layers, float32: what the
    engine's ``stats()['state_bytes_per_lane']`` has to agree with."""
    return 4 * cfg["num_hidden_layers"] * window_rows(cfg) * window_channels(cfg)


def window_decode_bytes_per_token(cfg: Mapping) -> int:
    """What one decoded token moves of the windows: every layer's, in and
    out."""
    return 2 * state_bytes_per_lane(cfg)


def wide_head_bytes_per_substep(cfg: Mapping, lanes: int, head_bytes: int) -> int:
    """The head's matrix once and the ``[lanes, vocabulary]`` float32
    logits written once and read once by the sampler: the least the head
    and the sampler together move a substep."""
    return (
        cfg["hidden_size"] * cfg["vocab_size"] * head_bytes
        + 2 * lanes * cfg["vocab_size"] * 4
    )


def decode_flops_per_token(cfg: Mapping) -> int:
    """Multiply-adds x 2 one decoded token needs by the model: the
    attention's projections and grouped taps, the router, ONE expert, the
    head (attention over the context is left out: it is bound by bytes).
    The streamed form spends ``num_experts`` times the expert term."""
    d = cfg["hidden_size"]
    per_layer = (
        attention_params(cfg) + router_params(cfg)
        + cfg["num_experts_per_tok"] * expert_params(cfg)
    )
    return 2 * (cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"])
