"""Pallas TPU kernel for the Gated DeltaNet decode update: one token a
lane on the carried matrix state, the state read ONCE and written once.

The rule, a value head (``S [N, P]``, key x value, float32)::

    u = e^g S^T k;  d = beta (v - u);  o = e^g S^T q + (k . q) d;  S' = e^g S + k d^T

The same lines in plain ``jax.numpy`` compile on a v5e to TWO fusions a layer:
a reduction that reads the whole state for ``S^T k`` and ``S^T q``, then
an elementwise pass that reads it again to write ``S'`` (the write needs
``d``, which needs the finished reduction): three passes over 201 MB a
layer at 96 lanes where two are needed (AOT and chip numbers: PERF.md, PR
42: 872 us a call against this kernel's 625 and a bare copy of the same
blocks' 635; ``benchmark/tools/gdn_decode_probe.py`` reproduces them).  This kernel holds a block of heads of one lane in VMEM, makes both
reductions and the rank-one write from that one copy, and writes the
block back over its input (``input_output_aliases``): one pass in, one
out.

Layout.  The state block is ``[heads, N, P]``: key features on sublanes,
value features on lanes, so both reductions run over sublanes (vector
adds, one 8-row fold a head) and their results, ``v``, ``d`` and ``o``
are rows of ``P`` lanes.  ``k`` and ``q`` multiply along lanes and are
needed as COLUMNS: they arrive as one ``[N, 2 G]`` tile a lane (key
features on sublanes, a key head a lane: ``k`` heads then ``q`` heads),
made by a small transpose outside the kernel, and a head's column is a
one-lane slice broadcast across the lanes.  The per-head scalars
(``e^g``, ``beta``, ``k . q``) ride in SMEM as scalar-prefetch operands.

Grad-free: decode is inference-only.  Interpret mode off-TPU.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# bytes of state one grid step holds in VMEM (its in and out blocks are
# double-buffered: four times this is resident): 1 MiB is 16 heads of
# 128 x 128 float32
_BLOCK_BYTES = 2**20


def heads_per_block(heads: int, per: int, key_dim: int, value_dim: int) -> int:
    """Value heads a grid step updates, from the state's shape alone: as
    many as fit ``_BLOCK_BYTES``, a divisor of ``heads`` and a multiple of
    ``per`` (the value heads a key head serves), so that a block reads
    whole key heads."""
    fit = max(per, _BLOCK_BYTES // (4 * key_dim * value_dim))
    best = per
    for hb in range(per, heads + 1, per):
        if heads % hb == 0 and hb <= fit:
            best = hb
    return best


def _block_kernel(decay_ref, beta_ref, kq_dot_ref, cols_ref, v_ref, s_ref, o_ref, s_out_ref, *, per, gb):
    """One lane, one block of ``hb`` value heads: ``cols_ref [N, 2 gb]``
    holds the block's own key heads' ``k`` columns, then their ``q``
    columns; ``v_ref`` / ``o_ref`` ``[hb, P]``; ``s_ref`` / ``s_out_ref``
    ``[hb, N, P]`` (one HBM block, read once and written once)."""
    lane, block = pl.program_id(0), pl.program_id(1)
    hb = s_ref.shape[0]
    cols = cols_ref[...]
    rows = []
    for i in range(hb):
        head = block * hb + i
        decay, beta, kq = decay_ref[lane, head], beta_ref[lane, head], kq_dot_ref[lane, head]
        j = i // per  # the key head that serves it, within the block
        k_col, q_col = cols[:, j : j + 1], cols[:, gb + j : gb + j + 1]  # [N, 1]
        S = s_ref[i]  # [N, P]
        u = decay * jnp.sum(S * k_col, axis=0, keepdims=True)  # [1, P]
        read = jnp.sum(S * q_col, axis=0, keepdims=True)
        d = beta * (v_ref[i : i + 1, :] - u)
        rows.append(decay * read + kq * d)
        s_out_ref[i] = decay * S + k_col * d
    o_ref[...] = jnp.concatenate(rows, axis=0)


def gdn_decode_update_pallas(
    state: jnp.ndarray,
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    g: jnp.ndarray,
    beta: jnp.ndarray,
    interpret: Optional[bool] = None,
):
    """``(o [L, H, P] float32, new state)``; the contract of
    :func:`scalerl_tpu.models.transformer.gdn_decode_update`: ``state [L,
    H, N, P]`` float32, ``q``/``k`` ``[L, G, N]``, ``v [L, H, P]``, ``g``,
    ``beta`` ``[L, H]``.  The state's buffer is reused for the result."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    f32 = jnp.float32
    L, H, N, P = state.shape
    G = k.shape[1]
    per = H // G
    q, k = q.astype(f32), k.astype(f32)
    decay = jnp.exp(g.astype(f32))
    kq_dot = jnp.repeat(jnp.sum(k * q, axis=-1), per, axis=1)  # [L, H]
    hb = heads_per_block(H, per, N, P)
    gb = hb // per  # key heads a block reads
    # [L, H // hb, N, 2 gb]: a block's own k columns, then its q columns
    cols = jnp.concatenate(
        [
            jnp.swapaxes(k.reshape(L, H // hb, gb, N), 2, 3),
            jnp.swapaxes(q.reshape(L, H // hb, gb, N), 2, 3),
        ],
        axis=-1,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(L, H // hb),
        in_specs=[
            pl.BlockSpec((None, None, N, 2 * gb), lambda lane, b, *_: (lane, b, 0, 0)),
            pl.BlockSpec((None, hb, P), lambda lane, b, *_: (lane, b, 0)),
            pl.BlockSpec((None, hb, N, P), lambda lane, b, *_: (lane, b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, hb, P), lambda lane, b, *_: (lane, b, 0)),
            pl.BlockSpec((None, hb, N, P), lambda lane, b, *_: (lane, b, 0, 0)),
        ],
    )
    o, new = pl.pallas_call(
        functools.partial(_block_kernel, per=per, gb=gb),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((L, H, P), f32),
            jax.ShapeDtypeStruct(state.shape, f32),
        ],
        # operands count the three scalar-prefetch arrays: the state is the sixth
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name="gdn_decode_update",
    )(decay, beta.astype(f32), kq_dot, cols, v.astype(f32), state)
    return o, new
