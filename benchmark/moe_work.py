"""Bytes the OLMoE block has to read, computed from shapes.

Model numbers, like ``work.py``'s (which stays as it is): what a decode
substep has to read, not what a compiler chose to read.  The
configuration dict is ``configs/olmoe-1b-7b.json``; nothing here reads the
program.  A decode substep is bound by bytes: every weight matrix outside
the experts is read once a substep whatever the number of lanes, an
expert's three matrices are read if any lane's token picked it, and the
arithmetic of 32 lanes is far below what those bytes cost to stream.
"""

from __future__ import annotations

from typing import Mapping


def olmoe_head_width(cfg: Mapping) -> int:
    """All heads' features side by side (the q, k, v and cache row width)."""
    return cfg["num_attention_heads"] * (cfg["hidden_size"] // cfg["num_attention_heads"])


def olmoe_expert_params(cfg: Mapping) -> int:
    """One expert's gate, up and down matrices."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def olmoe_layer_dense_params(cfg: Mapping) -> int:
    """A layer's matrices outside the experts: q, k, v, o and the router."""
    d = cfg["hidden_size"]
    return 4 * d * olmoe_head_width(cfg) + d * cfg["num_experts"]


def olmoe_kv_bytes_per_token(cfg: Mapping, bytes_per_value: int) -> int:
    """Bytes of K and V one cached token holds over all layers."""
    return 2 * cfg["num_hidden_layers"] * olmoe_head_width(cfg) * bytes_per_value


def olmoe_decode_dense_bytes(cfg: Mapping, block_bytes: int, head_bytes: int) -> int:
    """Bytes one decode substep reads whatever was routed where: every
    layer's attention and router matrices (``block_bytes`` a value) and
    the policy head (``head_bytes`` a value).  The embedding is a gather
    of one row a lane and is not counted."""
    return (
        cfg["num_hidden_layers"] * olmoe_layer_dense_params(cfg) * block_bytes
        + cfg["hidden_size"] * cfg["vocab_size"] * head_bytes
    )


def olmoe_expert_bytes(cfg: Mapping, block_bytes: int) -> int:
    """Bytes of one expert that received a token in a substep."""
    return olmoe_expert_params(cfg) * block_bytes
