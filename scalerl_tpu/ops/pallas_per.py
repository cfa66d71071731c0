"""Hierarchical prioritized-replay sampling: XLA two-level + Pallas kernel.

SURVEY.md §7 called cumsum-over-capacity "plan A" and a Pallas path "plan B
if this ever dominates the profile".  Both live here:

- :func:`hierarchical_sample` (XLA, any backend): split the priority plane
  into blocks; a tiny block-sum cumsum picks each sample's block, then only
  the selected blocks (``[S, block]``) are scanned — O(N + S·block) instead
  of a full O(N) cumsum materialized per sample batch, and the big array is
  read once, streaming.
- :func:`pallas_sample` (TPU): the within-block phase as a Pallas kernel
  with **scalar-prefetched block indices** — each grid step DMAs exactly one
  priority block HBM→VMEM via the prefetched index map (no ``[S, block]``
  gather materialization in HBM at all) and runs the cumsum+count search on
  the VPU.

Both produce the same sample for the same uniform targets (same float
summation order within blocks).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp


def _split_targets(
    flat_p: jnp.ndarray, targets: jnp.ndarray, block_size: int
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Phase 1 (shared): per-block sums -> block choice + residual target.

    Returns (blocks [nb, bs], block_idx [S], within_target [S]).
    """
    n = flat_p.shape[0]
    pad = (-n) % block_size
    if pad:
        flat_p = jnp.pad(flat_p, (0, pad))
    blocks = flat_p.reshape(-1, block_size)
    block_cum = jnp.cumsum(blocks.sum(axis=1))
    b_idx = jnp.clip(
        jnp.searchsorted(block_cum, targets, side="left"),
        0,
        blocks.shape[0] - 1,
    )
    prev = jnp.where(b_idx > 0, block_cum[b_idx - 1], 0.0)
    return blocks, b_idx.astype(jnp.int32), targets - prev


def hierarchical_sample(
    flat_p: jnp.ndarray, targets: jnp.ndarray, block_size: int = 1024
) -> jnp.ndarray:
    """Two-level proportional search; returns flat indices, one per target."""
    blocks, b_idx, within_t = _split_targets(flat_p, targets, block_size)
    rows = blocks[b_idx]                      # [S, bs]
    row_cum = jnp.cumsum(rows, axis=1)
    w_idx = jnp.sum(row_cum < within_t[:, None], axis=1)
    w_idx = jnp.clip(w_idx, 0, block_size - 1)
    return jnp.clip(
        b_idx * block_size + w_idx, 0, flat_p.shape[0] - 1
    ).astype(jnp.int32)


_LANES = 128


def _block_tiles(block_size: int) -> Tuple[int, int]:
    """A priority block as ``[rows, lanes]`` (row-major).  Mosaic tiles the
    last two block dims, so a block rides as whole 128-lane rows; a block
    size that is not a lane multiple (interpret-mode tests) stays one row.
    """
    if block_size % _LANES == 0:
        return block_size // _LANES, _LANES
    return 1, block_size


def _lane_cumsum(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum along the last axis by log-step shifts (Mosaic
    has no cumsum lowering; ``pltpu.roll`` runs on the XLU)."""
    from jax.experimental.pallas import tpu as pltpu

    lanes = x.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    shift = 1
    while shift < lanes:
        x = x + jnp.where(lane >= shift, pltpu.roll(x, shift, x.ndim - 1), 0.0)
        shift *= 2
    return x


def _within_block_kernel(b_idx_ref, t_ref, p_ref, out_ref):
    """One sample per grid step: search the prefetch-selected block.

    ``p_ref`` is the block as ``[rows, lanes]``; the count of prefix sums
    below the target is taken row by row, each row's running offset being
    the total of the rows before it."""
    import jax.experimental.pallas as pl

    i = pl.program_id(0)
    t = t_ref[i]
    rows, lanes = p_ref.shape
    bs = rows * lanes
    cum = _lane_cumsum(p_ref[...])
    offset = jnp.zeros((1, 1), jnp.float32)
    w = jnp.int32(0)
    for r in range(rows):
        row = cum[r:r + 1, :] + offset
        w = w + jnp.sum((row < t).astype(jnp.int32))
        offset = row[:, lanes - 1:]
    out_ref[i] = b_idx_ref[i] * bs + jnp.minimum(w, bs - 1)


def pallas_sample(
    flat_p: jnp.ndarray,
    targets: jnp.ndarray,
    block_size: int = 1024,
    interpret: bool = None,
) -> jnp.ndarray:
    """Pallas within-block search; distribution-identical to
    :func:`hierarchical_sample`.

    ``interpret=None`` auto-resolves: compiled Mosaic on TPU, the Pallas
    interpreter elsewhere — so an explicitly pinned ``method="pallas"``
    (e.g. ``RLArguments.use_pallas`` on a CPU test run) works on every
    backend instead of failing to compile off-TPU."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    blocks, b_idx, within_t = _split_targets(flat_p, targets, block_size)
    S = targets.shape[0]
    rows, lanes = _block_tiles(block_size)
    # block ids AND residual targets are scalar-prefetched: the ids steer
    # the DMA index map, the targets are read as SMEM scalars; the result
    # is one SMEM word per sample (no scalar stores into VMEM on Mosaic)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((None, rows, lanes), lambda i, b, t: (b[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
    )
    out = pl.pallas_call(
        _within_block_kernel,
        name="per_sample",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S,), jnp.int32),
        interpret=interpret,
    )(b_idx, within_t.astype(jnp.float32), blocks.reshape(-1, rows, lanes))
    return jnp.clip(out, 0, flat_p.shape[0] - 1)


_SAMPLE_METHODS = ("cumsum", "hierarchical", "pallas")


def resolve_sample_method(method: str = "auto") -> str:
    """Resolve ``"auto"`` to the best concrete method for this backend.

    TPU -> ``pallas`` (the scalar-prefetch kernel; top-level and
    shard_map'd legality covered by ``tests_tpu/test_compiled_kernels.py``),
    anything else -> ``hierarchical`` (pure XLA, runs everywhere).
    The env var ``SCALERL_PER_METHOD`` overrides what ``auto`` resolves to
    (e.g. ``hierarchical`` to back out the kernel on TPU without touching
    call sites); an explicitly pinned method always wins, so tests that
    compare methods stay meaningful under the override.

    Buffers resolve ``"auto"`` ONCE at construction time (the
    ``PrioritizedReplayBuffer`` / sharded-replay constructors and the R2D2
    trainers all call this in ``__init__``) rather than inside their traced
    sample programs: trace-time resolution would silently pin whatever the
    env var / backend happened to be at FIRST trace, and later changes to
    ``SCALERL_PER_METHOD`` would be ignored without any signal.  A bare
    ``proportional_sample(..., method="auto")`` still resolves at call
    time for one-off use.
    """
    import os

    if method != "auto":
        if method not in _SAMPLE_METHODS:
            raise ValueError(
                f"unknown sampling method {method!r}; use one of "
                f"{('auto',) + _SAMPLE_METHODS}"
            )
        return method
    forced = os.environ.get("SCALERL_PER_METHOD")
    if forced:
        if forced not in _SAMPLE_METHODS:
            raise ValueError(
                f"SCALERL_PER_METHOD={forced!r} is not one of {_SAMPLE_METHODS}"
            )
        return forced
    return "pallas" if jax.default_backend() == "tpu" else "hierarchical"


def proportional_sample(
    flat_p: jnp.ndarray,
    targets: jnp.ndarray,
    method: str = "auto",
    block_size: int = 1024,
) -> jnp.ndarray:
    """Dispatch: ``auto`` (backend-resolved), ``cumsum`` (flat plan A),
    ``hierarchical``, or ``pallas``."""
    method = resolve_sample_method(method)
    if method == "cumsum":
        cum = jnp.cumsum(flat_p)
        idx = jnp.searchsorted(cum, targets, side="left")
        return jnp.clip(idx, 0, flat_p.shape[0] - 1).astype(jnp.int32)
    if method == "hierarchical":
        return hierarchical_sample(flat_p, targets, block_size)
    # resolve_sample_method validated; only "pallas" remains
    return pallas_sample(flat_p, targets, block_size)


@functools.partial(jax.jit, static_argnames=("method", "block_size"))
def _jitted_proportional_sample(flat_p, targets, method, block_size):
    return proportional_sample(flat_p, targets, method, block_size)


# ---------------------------------------------------------------------------
# fused priority / sum-tree update (the write half of the PER feedback loop)

_UPDATE_METHODS = ("xla", "pallas")


def resolve_update_method(method: str = "auto") -> str:
    """Resolve the priority-update implementation for this backend.

    Mirrors :func:`resolve_sample_method`: ``auto`` -> ``pallas`` on TPU
    (the aliased in-place scatter kernel), ``xla`` elsewhere (interpreter
    mode is correct but slow for a per-learn-step op).  The env var
    ``SCALERL_PER_UPDATE`` overrides what ``auto`` resolves to; an
    explicitly pinned method always wins.
    """
    import os

    if method != "auto":
        if method not in _UPDATE_METHODS:
            raise ValueError(
                f"unknown update method {method!r}; use one of "
                f"{('auto',) + _UPDATE_METHODS}"
            )
        return method
    forced = os.environ.get("SCALERL_PER_UPDATE")
    if forced:
        if forced not in _UPDATE_METHODS:
            raise ValueError(
                f"SCALERL_PER_UPDATE={forced!r} is not one of {_UPDATE_METHODS}"
            )
        return forced
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _pad_to_blocks(flat_p: jnp.ndarray, block_size: int) -> jnp.ndarray:
    pad = (-flat_p.shape[0]) % block_size
    return jnp.pad(flat_p, (0, pad)) if pad else flat_p


def _update_kernel(
    b_idx_ref, w_idx_ref, newp_ref, blocks_ref, out_blocks_ref, out_sums_ref
):
    """Grid step i owns block ``b_idx[i]`` and applies EVERY update whose
    block matches — idempotent per block, so a block revisited by a later
    grid step (whose input DMA races the earlier step's writeback under the
    double-buffered pipeline) recomputes the identical final content
    instead of losing the earlier write.  Updates apply in ascending order,
    so duplicate (block, slot) pairs are deterministic last-wins.  The
    block's refreshed sum leaves as one SMEM word per update."""
    import jax.experimental.pallas as pl

    i = pl.program_id(0)
    my_b = b_idx_ref[i]
    blk = blocks_ref[...]  # [rows, lanes], row-major within the block
    rows, lanes = blk.shape
    slot = (
        jax.lax.broadcasted_iota(jnp.int32, blk.shape, 0) * lanes
        + jax.lax.broadcasted_iota(jnp.int32, blk.shape, 1)
    )

    def body(j, blk):
        sel = (b_idx_ref[j] == my_b) & (slot == w_idx_ref[j])
        return jnp.where(sel, newp_ref[j], blk)

    blk = jax.lax.fori_loop(0, b_idx_ref.shape[0], body, blk)
    out_blocks_ref[...] = blk
    out_sums_ref[i] = jnp.sum(blk)


def _pallas_update(
    blocks: jnp.ndarray,  # [nb, bs]
    b_idx: jnp.ndarray,  # [M]
    w_idx: jnp.ndarray,  # [M]
    new_p: jnp.ndarray,  # [M]
    interpret: bool,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns ``(new blocks [nb, bs], per-update block sums [M])``."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nb, bs = blocks.shape
    M = b_idx.shape[0]
    rows, lanes = _block_tiles(bs)
    block_spec = pl.BlockSpec(
        (None, rows, lanes), lambda i, b, w, p: (b[i], 0, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # block ids steer the DMA; slots and new values are SMEM scalars
        num_scalar_prefetch=3,
        grid=(M,),
        in_specs=[block_spec],
        out_specs=(block_spec, pl.BlockSpec(memory_space=pltpu.SMEM)),
    )
    new_blocks, sums = pl.pallas_call(
        _update_kernel,
        name="per_update",
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((nb, rows, lanes), jnp.float32),
            jax.ShapeDtypeStruct((M,), jnp.float32),
        ),
        # the plane aliases its input (the index counts the scalar-prefetch
        # operands): untouched blocks keep their values with zero copies
        input_output_aliases={3: 0},
        interpret=interpret,
    )(
        b_idx.astype(jnp.int32),
        w_idx.astype(jnp.int32),
        new_p.astype(jnp.float32),
        blocks.astype(jnp.float32).reshape(nb, rows, lanes),
    )
    return new_blocks.reshape(nb, bs), sums


def update_priorities_blocks(
    flat_p: jnp.ndarray,
    idx: jnp.ndarray,
    new_p: jnp.ndarray,
    block_sums=None,
    block_size: int = 1024,
    method: str = "auto",
    interpret=None,
):
    """Fused PER priority + two-level sum-tree update.

    Scatters ``new_p`` into the flat priority plane at ``idx`` and, when
    ``block_sums`` (the maintained per-block partial sums — the two-level
    "sum tree" :func:`hierarchical_sample`'s phase 1 consumes) is given,
    refreshes exactly the affected blocks' sums in the same pass.  Returns
    ``(new_flat_p, new_block_sums)`` (``new_block_sums`` is None when no
    sums were passed).

    Semantics: updates apply in ascending order, so duplicate indices are
    deterministic last-wins in BOTH implementations.  ``method="pallas"``
    runs the aliased in-place kernel — one block DMA per update, no full-
    plane traffic; ``"xla"`` is the reference (an ordered scatter loop +
    affected-block re-sum) the kernel is bit-tolerance-tested against;
    ``"auto"`` resolves per backend (:func:`resolve_update_method`).
    ``interpret=None`` auto-resolves like :func:`pallas_sample`.
    """
    method = resolve_update_method(method)
    n = flat_p.shape[0]
    idx = jnp.clip(idx.astype(jnp.int32), 0, n - 1)
    new_p = new_p.astype(jnp.float32)
    padded = _pad_to_blocks(flat_p.astype(jnp.float32), block_size)
    nb = padded.shape[0] // block_size
    if block_sums is not None and block_sums.shape[0] != nb:
        raise ValueError(
            f"block_sums has {block_sums.shape[0]} entries but the padded "
            f"plane has {nb} blocks of {block_size}"
        )
    b_idx = idx // block_size
    w_idx = idx % block_size

    if method == "xla":
        def body(j, p):
            return p.at[idx[j]].set(new_p[j])

        padded = jax.lax.fori_loop(0, idx.shape[0], body, padded)
        new_sums = None
        if block_sums is not None:
            rows = padded.reshape(nb, block_size)
            new_sums = block_sums.astype(jnp.float32).at[b_idx].set(
                jnp.sum(rows[b_idx], axis=1)
            )
        return padded[:n], new_sums

    new_blocks, touched_sums = _pallas_update(
        padded.reshape(nb, block_size), b_idx, w_idx, new_p,
        interpret=(
            jax.default_backend() != "tpu" if interpret is None else interpret
        ),
    )
    new_sums = None
    if block_sums is not None:
        # duplicates of one block carry the identical (final) sum
        new_sums = block_sums.astype(jnp.float32).at[b_idx].set(touched_sums)
    return new_blocks.reshape(-1)[:n], new_sums
