"""Sequence/context parallelism: run a transformer policy with the time
axis sharded over the mesh's ``sp`` axis.

No counterpart in the reference (SURVEY.md §5: long-context machinery is
absent there); this wires :func:`scalerl_tpu.ops.ring_attention.ring_attention`
into :class:`scalerl_tpu.models.transformer.TransformerPolicy` under
``shard_map``: attention communicates k/v blocks neighbor-to-neighbor over
ICI while every position-wise layer runs shard-locally.  Memory per device
is O(T / sp), enabling trajectory contexts far beyond one chip's HBM.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from scalerl_tpu.models.transformer import TransformerPolicy, TransformerOutput
from scalerl_tpu.ops.ring_attention import ring_attention


def make_sequence_parallel_apply(
    model: TransformerPolicy, mesh: Mesh, axis_name: str = "sp"
):
    """Build ``apply(params, obs) -> TransformerOutput`` with ``obs``
    ``[B, T, F]`` sequence-sharded on ``axis_name`` and params replicated.

    Positional embeddings stay globally correct: each shard computes its
    global step offset from its ring index inside the shard_map body.
    """
    ring = functools.partial(ring_attention, axis_name=axis_name, causal=True)
    sp_model = model.clone(attn_fn=ring)

    def shard_body(params, obs):
        import jax

        B, T_local = obs.shape[:2]
        offset = jax.lax.axis_index(axis_name) * T_local
        positions = jnp.broadcast_to(
            offset + jnp.arange(T_local), (B, T_local)
        )
        return sp_model.apply(params, obs, positions=positions)

    seq = P(None, axis_name)
    sharded = shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(P(), P(None, axis_name, None)),
        out_specs=TransformerOutput(P(None, axis_name, None), seq),
        check_vma=False,
    )
    sp = mesh.shape[axis_name]

    def apply(params, obs):
        # Validate against the *global* sequence length here, outside the
        # shard_map body: inside, the model only sees T/sp local steps, so
        # its own max_len guard cannot catch a too-long global sequence —
        # out-of-range positions would silently clamp onto the last
        # positional-embedding row.
        T = obs.shape[1]
        if T > model.max_len:
            raise ValueError(
                f"global sequence length {T} exceeds max_len={model.max_len}"
            )
        if T % sp != 0:
            raise ValueError(
                f"global sequence length {T} not divisible by sp={sp}"
            )
        return sharded(params, obs)

    return apply
