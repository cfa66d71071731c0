"""Pipeline parallelism: GPipe microbatch schedule over the ``pp`` axis.

No counterpart in the reference (SURVEY.md §2.4 lists PP as absent); this
completes the mesh's parallelism families.  Block stages are stacked on a
leading ``[S, ...]`` param axis sharded over ``pp``; inside ``shard_map``
each device runs its stage and hands activations to its right neighbor via
a non-cyclic ``ppermute`` shift.  The classic GPipe bubble applies:
``S + M - 1`` steps for ``M`` microbatches.  Heterogeneous models
(``embed -> S distinct blocks -> head``) are first-class via
:func:`make_hetero_pipeline_apply`; the homogeneous form is the same
schedule with identity boundary stages.

This is the correctness-first formulation (activations are dense every
step; idle stages run their *block* on zeros, but the boundary stages are
``lax.cond``-gated: embed runs only on stage 0 and the head only on the
last stage's active steps — ~M head applications instead of S*(M+S-1)).
It exists so ``pp`` is a real, executable axis — RL-parity models are far
too small to need it, which is why the flagship trainers default to
dp/fsdp.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

# stage_fn(stage_params, x[mb, ...]) -> y[mb, ...] (same shape)
StageFn = Callable[[Any, jnp.ndarray], jnp.ndarray]


def _identity_stage(params: Any, x: jnp.ndarray) -> jnp.ndarray:
    del params
    return x


def make_pipeline_apply(
    stage_fn: StageFn,
    mesh: Mesh,
    num_microbatches: int,
    axis_name: str = "pp",
):
    """Build ``apply(stacked_params, x) -> y`` running stages in pipeline.

    ``stacked_params``: pytree whose leaves lead with the stage axis
    ``[S, ...]`` (sharded over ``axis_name``).  ``x``: ``[B, ...]`` with
    ``B`` divisible by ``num_microbatches``; output has the same shape.

    The homogeneous case IS the heterogeneous pipeline with identity
    boundary stages (one schedule implementation — a fix to the GPipe
    machinery cannot drift between the two forms).
    """
    hetero = make_hetero_pipeline_apply(
        _identity_stage, stage_fn, _identity_stage, mesh,
        num_microbatches, axis_name,
    )

    def apply(stacked_params, x):
        return hetero({"embed": (), "block": stacked_params, "head": ()}, x)

    return apply


def sequential_apply(stage_fn: StageFn, stacked_params: Any, x: jnp.ndarray):
    """Reference semantics: stages applied one after another (no pipeline)."""
    S = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    for s in range(S):
        params_s = jax.tree_util.tree_map(lambda p: p[s], stacked_params)
        x = stage_fn(params_s, x)
    return x


def make_hetero_pipeline_apply(
    embed_fn: StageFn,
    block_fn: StageFn,
    head_fn: StageFn,
    mesh: Mesh,
    num_microbatches: int,
    axis_name: str = "pp",
    _loop_steps: int | None = None,
):
    """Heterogeneous pipeline: ``embed -> S blocks -> head`` over ``pp=S``
    (VERDICT r4 #8 — distinct stage params, not just stacked clones).

    Params are one pytree ``{"embed": E, "block": B, "head": H}`` where
    ``B``'s leaves lead with the stage axis ``[S, ...]`` (sharded over
    ``axis_name`` — the N-block bulk is what pipeline parallelism exists
    to partition) while the boundary trees ``E``/``H`` ride replicated
    (they are small, and only stage 0 / stage S-1 consume them).

    Shapes stay uniform without a stage-indexed ``lax.switch``: the raw
    input only ever feeds ``embed_fn`` (a ``lax.cond`` runs it on stage 0
    only, from that device's local copy of the microbatch), the
    inter-stage carry is always the block width, and ``head_fn``'s output
    (``lax.cond``-gated to the last stage's active steps) goes to a
    separate collection buffer, never onto the pipe.

    Schedule: GPipe, ``M + S - 1`` steps (``M`` microbatches) — the bubble
    fraction is ``(S-1)/(M+S-1)``; ``tests/test_pipeline.py`` asserts the
    schedule is exactly tight (one step fewer drops a microbatch).

    ``apply({"embed","block","head"}, x[B, ...]) -> y[B, ..., out_dim]``.
    """
    M = num_microbatches

    def body(params, x):
        S = jax.lax.psum(1, axis_name)
        stage = jax.lax.axis_index(axis_name)
        block_local = jax.tree_util.tree_map(lambda p: p[0], params["block"])
        B = x.shape[0]
        mb = B // M
        mbs = x.reshape((M, mb) + x.shape[1:])

        # carry width = block output width; shapes only, no runtime flops
        x0_shape = jax.eval_shape(embed_fn, params["embed"], mbs[0])
        out_shape = jax.eval_shape(head_fn, params["head"], x0_shape)
        out0 = jnp.zeros((M,) + out_shape.shape, out_shape.dtype)
        cur0 = jnp.zeros(x0_shape.shape, x0_shape.dtype)

        def step(t, carry):
            outputs, cur = carry
            k = t - stage  # microbatch index flowing through this stage
            active = jnp.logical_and(k >= 0, k < M)
            k_safe = jnp.clip(k, 0, M - 1)
            # boundary stages are lax.cond-gated, not computed-then-masked:
            # a jnp.where would run embed on every stage and head on every
            # (stage, step) pair — S*(M+S-1) head applications where only
            # the last stage's M active steps carry real data.  cond skips
            # the FLOPs entirely on the stages/steps that discard them.
            x_in = jax.lax.cond(
                stage == 0,
                lambda: embed_fn(params["embed"], mbs[k_safe]),
                lambda: cur,
            )
            y = block_fn(block_local, x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            outputs = jax.lax.cond(
                jnp.logical_and(active, stage == S - 1),
                lambda o: o.at[k_safe].set(head_fn(params["head"], y)),
                lambda o: o,
                outputs,
            )
            # non-cyclic right shift: stage i -> i+1 (stage 0 receives zeros)
            nxt = jax.lax.ppermute(
                y, axis_name, [(i, i + 1) for i in range(S - 1)]
            )
            return outputs, nxt

        n_steps = (M + S - 1) if _loop_steps is None else _loop_steps
        outputs, _ = jax.lax.fori_loop(0, n_steps, step, (out0, cur0))
        # only the last stage holds real outputs; psum replicates them
        outputs = jax.lax.psum(
            jnp.where(stage == S - 1, outputs, jnp.zeros_like(outputs)),
            axis_name,
        )
        return outputs.reshape((B,) + outputs.shape[2:])

    sharded = shard_map(
        body,
        mesh=mesh,
        in_specs=({"embed": P(), "block": P(axis_name), "head": P()}, P()),
        out_specs=P(),
        check_vma=False,
    )
    pp = mesh.shape[axis_name]

    def apply(params, x):
        for path, leaf in jax.tree_util.tree_flatten_with_path(params["block"])[0]:
            if leaf.shape[0] != pp:
                raise ValueError(
                    f"stacked block-stage axis {leaf.shape[0]} != pp={pp} at "
                    f"{jax.tree_util.keystr(path)}; one block per pp device"
                )
        if x.shape[0] % M != 0:
            raise ValueError(
                f"batch {x.shape[0]} not divisible by num_microbatches={M}"
            )
        return sharded(params, x)

    return apply


def hetero_sequential_apply(
    embed_fn: StageFn,
    block_fn: StageFn,
    head_fn: StageFn,
    params: Any,
    x: jnp.ndarray,
):
    """Single-device reference for :func:`make_hetero_pipeline_apply`."""
    y = embed_fn(params["embed"], x)
    y = sequential_apply(block_fn, params["block"], y)
    return head_fn(params["head"], y)
