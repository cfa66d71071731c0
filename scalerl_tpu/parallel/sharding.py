"""Sharding rules: how trajectories, batches, and params lay out on a mesh.

The reference's data-parallel contract is "each DDP rank samples its own
minibatch; NCCL all-reduces gradients" (``scalerl/data/replay_data.py:8-26``
+ ``accelerator.backward``, ``dqn_agent.py:173``).  Here the same contract is
*declarative*: trajectories are sharded on their batch dim over ``dp`` (and
``fsdp``), params are replicated over ``dp`` and optionally sharded over
``fsdp``/``tp``, and GSPMD inserts the gradient ``psum`` over ICI.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def holds_axis(sharding: Any, axis: str) -> bool:
    """Does a ``NamedSharding``'s spec put mesh axis ``axis`` on any
    dimension, alone or in a tuple of axes?"""
    return axis in jax.tree_util.tree_leaves(tuple(getattr(sharding, "spec", ())))


def batch_sharding(mesh: Mesh, batch_dim: int = 0) -> NamedSharding:
    """Shard dim ``batch_dim`` over the data-parallel axes ``(dp, fsdp)``.

    fsdp participates in batch sharding (standard ZeRO-style layout): the
    global batch splits over dp×fsdp, while *params* shard only over fsdp.
    """
    spec = [None] * batch_dim + [("dp", "fsdp")]
    return NamedSharding(mesh, P(*spec))


def trajectory_sharding(mesh: Mesh) -> NamedSharding:
    """Time-major ``[T+1, B, ...]`` chunks shard on the batch dim (dim 1)."""
    return batch_sharding(mesh, batch_dim=1)


def _path_names(path: Tuple[Any, ...]) -> Tuple[str, ...]:
    return tuple(
        str(getattr(p, "name", getattr(p, "key", getattr(p, "idx", p))))
        for p in path
    )


def batch_sharding_tree(batch_example: Any, mesh: Mesh, time_major: bool = True) -> Any:
    """Per-leaf NamedSharding pytree for a batch.

    Trajectory pytrees mix layouts: rollout tensors are time-major
    ``[T+1, B, ...]`` (batch dim 1) while recurrent ``core_state`` leaves
    are ``[B, ...]`` (batch dim 0) — see ``data/trajectory.py``.  Leaves
    whose path passes through ``core_state`` (or any rank-1+ leaf when
    ``time_major=False``) shard dim 0; the rest shard dim 1.
    """

    def spec_for(path, x):
        if not hasattr(x, "ndim") or x.ndim == 0:
            return NamedSharding(mesh, P())
        dim = 0 if (not time_major or "core_state" in _path_names(path)) else 1
        if x.ndim <= dim:
            return NamedSharding(mesh, P())
        return batch_sharding(mesh, batch_dim=dim)

    return jax.tree_util.tree_map_with_path(spec_for, batch_example)


def infer_param_spec(
    path: Tuple[Any, ...],
    x: Any,
    mesh: Mesh,
    axes: Tuple[str, ...] = ("fsdp", "tp"),
    min_shard: int = 8,
) -> P:
    """Pick a PartitionSpec for one param leaf.

    Rule (applies to any Flax/Haiku pytree without model surgery): for
    arrays of rank >= 2, shard the largest divisible dim over ``axes[0]``
    and, if a second divisible dim exists, over ``axes[1]``.  Rank-0/1 and
    non-divisible leaves replicate.  This yields real fsdp/tp layouts for
    the conv/fc stacks of AtariNet-class models; bespoke models can pass
    explicit specs instead.

    ``min_shard``: a dim is only sharded if every shard keeps at least
    this many elements.  Tiny dims (e.g. a ``[hidden, num_actions]`` policy
    head's action dim) otherwise get 2-3-element shards, and the *gradient*
    of the head's activation then carries conflicting shardings from its
    two uses — GSPMD resolves that with an involuntary full
    rematerialization (replicate-then-repartition) of the whole ``[T, B,
    A]`` logits gradient, a multi-chip perf cliff on real models.
    """
    if not hasattr(x, "ndim") or x.ndim < 2:
        return P()
    sizes = {a: mesh.shape[a] for a in axes if mesh.shape.get(a, 1) > 1}
    if not sizes:
        return P()
    spec: list = [None] * x.ndim
    # largest dims first so the big matmul dims absorb the sharding
    order = sorted(range(x.ndim), key=lambda d: -x.shape[d])
    for axis_name in axes:
        n = mesh.shape.get(axis_name, 1)
        if n <= 1:
            continue
        for d in order:
            if (
                spec[d] is None
                and x.shape[d] % n == 0
                and x.shape[d] >= max(2, min_shard) * n
            ):
                spec[d] = axis_name
                break
    return P(*spec)


def has_scanned_params(tree: Any) -> bool:
    """True when the pytree carries ``nn.scan`` core parameters (flax
    prefixes the scanned module's name with ``Scan``, e.g.
    ``Scan_LSTMCore_0``)."""
    for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]:
        if any(str(n).startswith("Scan") for n in _path_names(path)):
            return True
    return False


def param_sharding(
    params: Any, mesh: Mesh, axes: Tuple[str, ...] = ("fsdp", "tp")
) -> Any:
    """NamedSharding pytree for a param/optimizer pytree (fsdp/tp rule).

    Recurrent exception (the ``test_r2d2_enable_mesh_matches_unsharded``
    root cause): when the tree carries ``nn.scan`` core params, EVERY leaf
    replicates — batch-parallel only.  The scan's transpose (backward)
    pass stacks per-step residuals ``[T, B, feat]`` as while-loop carries;
    with any fsdp/tp-sharded param feeding the scan, GSPMD must reshard
    those carries from batch-sharded to feature-sharded layouts, which it
    can only do via an *involuntary full rematerialization* of the loop
    carry (spmd_partitioner "You probably want to enrich the sharding
    annotations"), and with a non-divisible feature dim the padded remat
    produces gradients that are numerically WRONG (~8% loss drift at
    hidden=16, not reduction-reorder noise).  Replicated params make the
    meshed step bitwise-identical to single-device at the same global
    batch; the memory win of fsdp never mattered for LSTM-sized cores.
    """
    if axes and has_scanned_params(params):
        axes = ()
    return jax.tree_util.tree_map_with_path(
        lambda path, x: NamedSharding(mesh, infer_param_spec(path, x, mesh, axes=axes)),
        params,
    )


def shard_params(params: Any, mesh: Mesh) -> Any:
    """Device-put a param pytree with the inferred fsdp/tp layout."""
    return jax.device_put(params, param_sharding(params, mesh))


def shard_batch(batch: Any, mesh: Mesh, batch_dim: int = 0) -> Any:
    """Device-put a host batch pytree sharded on its batch dimension."""
    sh = batch_sharding(mesh, batch_dim)
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sh), batch)


def pad_to_multiple(x: np.ndarray, multiple: int, axis: int) -> np.ndarray:
    """Host-side pad so a dim divides the mesh (static shapes for XLA)."""
    size = x.shape[axis]
    rem = size % multiple
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, multiple - rem)
    return np.pad(x, pad)
