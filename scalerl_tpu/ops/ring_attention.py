"""Ring attention: sequence-parallel exact attention over an ICI ring.

No counterpart exists in the reference (SURVEY.md §2.4: sequence/context
parallelism is **absent** — its longest temporal machinery is an LSTM unroll).
This op makes long-context first-class for the TPU build: sequences are
sharded over the mesh's ``sp`` axis, each device holds a ``[B, T/n, H, D]``
block of q/k/v, and k/v blocks rotate around the ring via
``jax.lax.ppermute`` while a streaming (flash-style) online softmax
accumulates exact attention — memory per device stays O(T/n), communication
rides neighbor-to-neighbor ICI hops, and the result is bitwise-equal math to
full attention (up to float reassociation).

Designed after the blockwise/ring formulation of Liu et al. (Ring Attention
with Blockwise Transformers, 2023); implementation is original and
shard_map-native.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _online_block_update(o, l, m, s, v):
    """Streaming softmax accumulation for one kv block.

    o: [B, Tq, H, D] weighted-value accumulator
    l: [B, H, Tq]    softmax normalizer accumulator
    m: [B, H, Tq]    running row max
    s: [B, H, Tq, Tk] scaled (masked) scores for this block
    v: [B, Tk, H, D]
    """
    m_new = jnp.maximum(m, s.max(axis=-1))
    # fully-masked-so-far rows keep m=-inf; subtract 0 there so exp(-inf)=0
    # instead of exp(nan)
    safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(s - safe_m[..., None])                    # [B,H,Tq,Tk]
    corr = jnp.exp(jnp.where(jnp.isneginf(m), -jnp.inf, m) - safe_m)
    l_new = l * corr + p.sum(axis=-1)
    o_new = o * corr.transpose(0, 2, 1)[..., None] + jnp.einsum(
        "bhqk,bkhd->bqhd", p, v
    )
    return o_new, l_new, m_new


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str = "sp",
    causal: bool = False,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Exact attention over sequence blocks sharded on ``axis_name``.

    Must run inside ``shard_map`` (or ``pjit``-manual) over a mesh with the
    ``axis_name`` axis.  Shapes are per-device blocks ``[B, T_local, H, D]``;
    ``causal`` masks by *global* position (block offset from the device's
    ring index).
    """
    B, T, H, D = q.shape
    n = jax.lax.psum(1, axis_name)          # static ring size
    idx = jax.lax.axis_index(axis_name)
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    q_pos = idx * T + jnp.arange(T)          # global positions of this block

    # accumulate in f32 regardless of input dtype (bf16 inputs stay bf16 on
    # the matmuls; the final division casts back)
    o0 = jnp.zeros((B, T, H, D), jnp.float32)
    l0 = jnp.zeros((B, H, T), jnp.float32)
    m0 = jnp.full((B, H, T), -jnp.inf, jnp.float32)

    def attend(o, l, m, k_blk, v_blk, src):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk).astype(jnp.float32) * scale
        if causal:
            k_pos = src * T + jnp.arange(T)
            visible = k_pos[None, :] <= q_pos[:, None]      # [Tq, Tk]
            s = jnp.where(visible[None, None], s, -jnp.inf)
        return _online_block_update(o, l, m, s, v_blk.astype(jnp.float32))

    # own block first (no communication) ...
    o, l, m = attend(o0, l0, m0, k, v, src=idx)

    def body(carry, r):
        o, l, m, k_blk, v_blk = carry
        # ... then rotate kv one hop (device i -> i+1) and consume: n-1
        # rotations total, so no dead transfer after the last block
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        o, l, m = attend(o, l, m, k_blk, v_blk, src=(idx - r) % n)
        return (o, l, m, k_blk, v_blk), None

    (o, l, _m, _k, _v), _ = jax.lax.scan(
        body, (o, l, m, k, v), jnp.arange(1, n)
    )
    l = jnp.where(l == 0.0, 1.0, l)          # fully-masked rows -> zeros
    return (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)


def full_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Single-device reference attention, same [B, T, H, D] layout."""
    D = q.shape[-1]
    T = q.shape[1]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        visible = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(visible[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v).astype(q.dtype)


def make_ring_attention_fn(mesh: Mesh, causal: bool = False, axis_name: str = "sp"):
    """shard_map ``ring_attention`` over global ``[B, T, H, D]`` arrays
    sequence-sharded on ``axis_name``."""
    from jax import shard_map

    spec = P(None, axis_name, None, None)
    fn = functools.partial(ring_attention, axis_name=axis_name, causal=causal)
    return shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
