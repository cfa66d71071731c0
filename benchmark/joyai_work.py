"""Operations the JoyAI-LLM-Flash stack needs in a learn step, from shapes.

Model numbers, like ``work.py``'s (which stays as it is): what the forward
and backward passes require of the matrices a token really runs, not what
a compiler chose to execute.  A multiply-add is two operations; the
backward pass is twice the forward; recomputed or padded work does not
count.  The configuration dict is ``configs/joyai-llm-flash.json``; nothing
here reads the program.

What a token runs: one latent attention a layer (five projections, then
scores at q/k 192 and values at 128 against the keys of its own segment
before it); the dense SwiGLU in the leading layers; in a routed layer the
router, the shared expert, and ONE expert's three matrices for each of its
picks that fell on an expert HELD here (a pick of an absent expert runs
nothing on this chip, so the count takes the measured held picks a token
a layer, not ``num_experts_per_tok``); the multi-token-prediction module's
``2d -> d`` projection and its routed layer; the policy head twice (the
trunk's pass and the module's).  Embedding lookups, norms, rotary and the
value head's one column are not counted.
"""

from __future__ import annotations

from typing import Mapping


def mla_params(cfg: Mapping) -> int:
    """One latent attention: W_qa, W_qb, W_kva, W_kvb, W_o."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (
        d * cfg["q_lora_rank"]
        + cfg["q_lora_rank"] * heads * qk
        + d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
        + cfg["kv_lora_rank"] * heads * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
        + heads * cfg["v_head_dim"] * d
    )


def expert_params(cfg: Mapping) -> int:
    """One expert's gate, up and down matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def attentions(cfg: Mapping) -> int:
    """Attentions a learn step's token passes: one a layer of the trunk
    and one in each multi-token-prediction module."""
    return cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]


def routed_layers(cfg: Mapping) -> int:
    """Layers with a router that a learn step runs, the module's among
    them."""
    return (
        cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
        + cfg["num_nextn_predict_layers"]
    )


def matmul_params_per_token(cfg: Mapping, held_picks_per_token_layer: float) -> float:
    """Parameters in matrices one token is multiplied by in one forward
    of the learn step (see the module docstring)."""
    d = cfg["hidden_size"]
    dense = cfg["first_k_dense_replace"] * 3 * d * cfg["intermediate_size"]
    routed = routed_layers(cfg) * (
        d * cfg["n_routed_experts_published"]
        + cfg["n_shared_experts"] * expert_params(cfg)
        + held_picks_per_token_layer * expert_params(cfg)
    )
    mtp = cfg["num_nextn_predict_layers"] * 2 * d * d
    head = (1 + cfg["num_nextn_predict_layers"]) * d * cfg["vocab_size"]
    return attentions(cfg) * mla_params(cfg) + dense + routed + mtp + head


def attention_flops_per_key(cfg: Mapping) -> float:
    """Forward and backward operations of ONE attention for one (query,
    key) pair, all heads: ``q . k`` over 192 and ``p v`` over 128 forward,
    and the four backward products (dv and dp over 128, dq and dk over
    192).  Unpadded widths; the scores the backward kernels recompute are
    not counted."""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    forward = 2.0 * qk + 2.0 * cfg["v_head_dim"]
    return cfg["num_attention_heads"] * 3.0 * forward


def attention_flops_per_token(cfg: Mapping, attended_keys: float) -> float:
    """Attention operations of a learn step for one token that attends
    ``attended_keys`` keys on average, all its attentions."""
    return attentions(cfg) * attention_flops_per_key(cfg) * attended_keys


def train_flops_per_token(
    cfg: Mapping, attended_keys: float, held_picks_per_token_layer: float
) -> float:
    """Forward plus backward for one real token of a learn step."""
    return (
        6.0 * matmul_params_per_token(cfg, held_picks_per_token_layer)
        + attention_flops_per_token(cfg, attended_keys)
    )
