"""Operations and bytes the algorithm needs, computed from shapes.

These are model numbers: what the forward and backward passes require,
not what a compiler chose to execute.  Recomputed or padded work does not
count.  A multiply-add is two operations.  The configuration dicts are the
files under ``configs/``; nothing here reads the program.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Mapping, Sequence

_PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    table = json.loads(_PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r} in {_PEAKS.name}: "
            "add the published numbers with their source"
        )
    return table[device_kind]


# ---------------------------------------------------------------------------
# GPT-2 block (configs/gpt2-*.json)


def gpt2_matmul_params(cfg: Mapping) -> int:
    """Parameters that sit in a matrix multiplied per token: the blocks'
    four projections and the (untied) policy head.  Embedding lookups,
    norms and biases are not matmuls."""
    d = cfg["n_embd"]
    inner = cfg.get("n_inner") or 4 * d
    per_block = 3 * d * d + d * d + 2 * d * inner
    return cfg["n_layer"] * per_block + d * cfg["vocab_size"]


def gpt2_params(cfg: Mapping) -> int:
    """All parameters of the program's GPT-2 block, as it builds it."""
    d = cfg["n_embd"]
    inner = cfg.get("n_inner") or 4 * d
    per_block = 4 * d * d + 2 * d * inner + inner + d + 2 * d  # + biases, 2 norms
    heads = d * cfg["vocab_size"] + cfg["vocab_size"] + d + 1
    embeds = cfg["vocab_size"] * d + cfg["n_positions"] * d
    return cfg["n_layer"] * per_block + heads + embeds + d


def gpt2_forward_flops_per_token(cfg: Mapping, attended_keys: float) -> float:
    """Forward operations for one token that attends ``attended_keys``
    keys on average (QK^T and PV: 4 * d per key per layer)."""
    d = cfg["n_embd"]
    return 2.0 * gpt2_matmul_params(cfg) + 4.0 * d * attended_keys * cfg["n_layer"]


def gpt2_train_flops_per_token(cfg: Mapping, attended_keys: float) -> float:
    """Forward plus backward (twice the forward) for one token."""
    return 3.0 * gpt2_forward_flops_per_token(cfg, attended_keys)


def mean_attended_keys(segment_lengths: Sequence[int]) -> float:
    """Causal attention inside segments: token ``t`` of a segment attends
    ``t + 1`` keys; the mean over all tokens of all segments."""
    total = sum(int(n) for n in segment_lengths)
    if total == 0:
        return 0.0
    return sum(int(n) * (int(n) + 1) / 2.0 for n in segment_lengths) / total


def gpt2_kv_bytes_per_token(cfg: Mapping, bytes_per_value: int) -> int:
    """Bytes of K and V one cached token holds over all layers."""
    return 2 * cfg["n_layer"] * cfg["n_embd"] * bytes_per_value


def decode_kv_tokens_read(prompt_len: int, response_len: int) -> float:
    """Cached tokens a sequence's decode steps read in all: the step that
    consumes response token ``t`` (0-based) attends ``prompt + t + 1``."""
    r = int(response_len)
    return r * int(prompt_len) + r * (r + 1) / 2.0


# ---------------------------------------------------------------------------
# AtariNet (configs/impala-atarinet.json), SAME padding as the program


def atarinet_forward_flops_per_frame(cfg: Mapping) -> float:
    h, w, c = cfg["observation_shape"]
    flops = 0.0
    for feat, kern, stride in zip(
        cfg["conv_features"], cfg["conv_kernels"], cfg["conv_strides"]
    ):
        h, w = -(-h // stride), -(-w // stride)  # SAME padding
        flops += 2.0 * h * w * feat * kern * kern * c
        c = feat
    flat = h * w * c
    core = cfg["hidden_size"] + cfg["num_actions"] + 1
    flops += 2.0 * flat * cfg["hidden_size"]
    flops += 2.0 * core * (cfg["num_actions"] + 1)
    return flops


def impala_flops_per_frame(cfg: Mapping) -> float:
    """One env frame costs one actor forward and, in the learner, one
    forward and one backward (twice a forward): four forwards in all.  The
    learner's bootstrap row (T+1 of T) is not counted."""
    return 4.0 * atarinet_forward_flops_per_frame(cfg)
