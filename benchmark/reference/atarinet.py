"""Plain reference of the AtariNet torso and heads as the program builds
them (feed-forward, SAME-padded convolutions, NHWC), in float32 at
``highest`` precision.  Reads the program's parameter tree by its names.
"""

from __future__ import annotations

from typing import List, Mapping, Tuple

import jax
import jax.numpy as jnp


def program_argv(cfg: Mapping) -> List[str]:
    """The configuration's sizes as the program's own arguments."""
    return [
        "--hidden-size", str(cfg["hidden_size"]),
        "--use-lstm", "true" if cfg["use_lstm"] else "false",
        "--rollout-length", str(cfg["unroll_length"]),
    ]


def forward(
    params: Mapping,
    frames: jnp.ndarray,  # [N, H, W, C] uint8
    last_action: jnp.ndarray,  # [N] int32
    reward: jnp.ndarray,  # [N] float32
    cfg: Mapping,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns ``(policy_logits [N, A], baseline [N])``."""
    p = params["params"]
    with jax.default_matmul_precision("highest"):
        x = frames.astype(jnp.float32) / 255.0
        for i, stride in enumerate(cfg["conv_strides"]):
            conv = p[f"Conv_{i}"]
            x = jax.lax.conv_general_dilated(
                x, conv["kernel"].astype(jnp.float32), (stride, stride), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision=jax.lax.Precision.HIGHEST,
            ) + conv["bias"]
            x = jax.nn.relu(x)
        x = x.reshape(x.shape[0], -1)
        x = jax.nn.relu(x @ p["Dense_0"]["kernel"] + p["Dense_0"]["bias"])
        one_hot = jax.nn.one_hot(last_action, cfg["num_actions"], dtype=jnp.float32)
        core = jnp.concatenate(
            [x, one_hot, jnp.clip(reward, -1.0, 1.0)[:, None]], axis=-1
        )
        logits = core @ p["policy"]["kernel"] + p["policy"]["bias"]
        baseline = (core @ p["baseline"]["kernel"] + p["baseline"]["bias"])[:, 0]
    return logits, baseline
