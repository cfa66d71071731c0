"""Pallas TPU flash attention (forward + flash-style backward).

The hot op of the long-context path (``models/transformer.py`` /
``parallel/sequence.py``).  No counterpart exists in the reference — it has
no attention at all (SURVEY.md §5) — this kernel is part of the TPU build's
beyond-parity long-context stack: blockwise online-softmax attention that
never materializes the ``[T, T]`` score matrix.

Tiling: the kv dimension lives in the *grid* (innermost, sequential on
TPU), with the online-softmax accumulators in VMEM scratch that persists
across kv steps — so VMEM holds one ``[block_q, D]`` query tile, one
``[block_k, D]`` kv tile, and one ``[block_q, block_k]`` score tile at a
time, and HBM traffic stays O(T·D) per (batch, head).  Long contexts never
pull a full ``[T, D]`` K or V into VMEM.

Layout matches :func:`scalerl_tpu.ops.ring_attention.full_attention`:
``q/k/v`` are ``[B, T, H, D]`` and the result is ``[B, Tq, H, D]``, so the
kernel drops into ``TransformerPolicy``'s pluggable ``attn_fn`` seam — and
composes with ring attention's device-level sequence sharding.

Differentiable: a ``jax.custom_vjp`` implements the flash backward — the
probability tiles are recomputed from the saved log-sum-exp rather than
stored, one kernel gridded over q blocks for ``dq`` and one gridded over
k blocks for ``dk``/``dv`` (the FlashAttention-2 split, so neither kernel
needs cross-grid accumulation).

On CPU hosts (tests, this image) the kernels run in Pallas interpret mode;
on TPU they compile to Mosaic.  Scores/accumulators are float32 regardless
of input dtype (bf16 inputs feed the MXU directly).
"""

from __future__ import annotations

import functools
import math
import os
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float("-inf")
_SEG_BIG = 2**30  # sentinel above any real segment id (pad id is 0)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _mask_block(
    i, j, q_len: int, k_len: int, block_q: int, block_k: int, causal: bool
):
    """Validity mask for score tile (q block ``i``, k block ``j``)."""
    q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = (k_pos < k_len) & (q_pos < q_len)
    if causal:
        mask = mask & (k_pos <= q_pos)
    return mask


def _causal_live(i, j, block_q: int, block_k: int):
    """Whether kv tile ``j`` intersects the causal triangle of q tile ``i``."""
    return j * block_k <= i * block_q + block_q - 1


# ----------------------------------------------------------------------
# forward: grid (B, H, nq, nk) — kv innermost, accumulators in scratch
# ----------------------------------------------------------------------
def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_sc, m_sc, l_sc,
    *, scale, causal, q_len, k_len, block_q, block_k, nk,
):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    live = _causal_live(i, j, block_q, block_k) if causal else (j >= 0)

    @pl.when(live)
    def _attend():
        q = q_ref[...].astype(jnp.float32) * scale  # [bq, D]
        k_blk = k_ref[...].astype(jnp.float32)  # [bk, D]
        v_blk = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, bk]
        mask = _mask_block(i, j, q_len, k_len, block_q, block_k, causal)
        s = jnp.where(mask, s, _NEG_INF)
        m = m_sc[:]
        l = l_sc[:]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - safe_m)
        corr = jnp.exp(jnp.where(jnp.isneginf(m), _NEG_INF, m) - safe_m)
        l_sc[:] = l * corr + p.sum(axis=-1, keepdims=True)
        m_sc[:] = m_new
        acc_sc[:] = acc_sc[:] * corr + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(j == nk - 1)
    def _finish():
        l = l_sc[:]
        m = m_sc[:]
        o_ref[...] = (acc_sc[:] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        lse_ref[...] = jnp.where(
            l > 0.0, m + jnp.log(jnp.maximum(l, 1e-30)), _NEG_INF
        )


# Mosaic tiles the LAST TWO dims of every block, and each must be a multiple
# of the (8, 128) vreg tile or span its whole axis.  The public [B, T, H, D]
# layout would leave the head axis second-to-last at block size 1, so the
# wrappers move heads forward ([B, H, T, D], blocks of [rows, D]) and every
# per-row vector (lse, delta, q-side segment ids) rides as a [.., T, 1]
# column, never as a lane vector that the kernel would have to relayout.
def _heads_first(x: jnp.ndarray, t_pad: int) -> jnp.ndarray:
    """``[B, T, H, D]`` -> ``[B, H, t_pad, D]`` (zero tail)."""
    x = jnp.swapaxes(x, 1, 2)
    T = x.shape[2]
    if T == t_pad:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, t_pad - T), (0, 0)))


def _heads_last(x: jnp.ndarray, T: int) -> jnp.ndarray:
    """``[B, H, t_pad, D]`` -> ``[B, T, H, D]``."""
    return jnp.swapaxes(x[:, :, :T], 1, 2)


def _tile(rows: int, width: int, axis: int) -> pl.BlockSpec:
    """``[rows, width]`` block of a head-major ``[B, H, T, width]`` operand
    whose T axis is walked by grid axis ``axis``."""
    return pl.BlockSpec(
        (None, None, rows, width), lambda *g: (g[0], g[1], g[axis], 0)
    )


def _blocks(Tq: int, Tk: int, block_q: int, block_k: int):
    bq = min(block_q, _round_up(Tq, 8))
    bk = min(block_k, _round_up(Tk, 8))
    Tq_p, Tk_p = _round_up(Tq, bq), _round_up(Tk, bk)
    return bq, bk, Tq_p, Tk_p


def _fwd(
    q, k, v, causal, scale, block_q, block_k, interpret
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    bq, bk, Tq_p, Tk_p = _blocks(Tq, Tk, block_q, block_k)
    nq, nk = Tq_p // bq, Tk_p // bk
    qh = _heads_first(q, Tq_p)
    kh, vh = _heads_first(k, Tk_p), _heads_first(v, Tk_p)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, q_len=Tq, k_len=Tk,
        block_q=bq, block_k=bk, nk=nk,
    )
    o, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=(B, H, nq, nk),
        in_specs=[_tile(bq, D, 2), _tile(bk, D, 3), _tile(bk, D, 3)],
        out_specs=[_tile(bq, D, 2), _tile(bq, 1, 2)],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tq_p, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Tq_p, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qh, kh, vh)
    return _heads_last(o, Tq), lse


# ----------------------------------------------------------------------
# backward (FlashAttention-2 split: dq over q blocks, dk/dv over k blocks)
# ----------------------------------------------------------------------
def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_sc,
    *, scale, causal, q_len, k_len, block_q, block_k, nk,
):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    live = _causal_live(i, j, block_q, block_k) if causal else (j >= 0)

    @pl.when(live)
    def _accumulate():
        q = q_ref[...].astype(jnp.float32) * scale
        do = do_ref[...].astype(jnp.float32)
        lse = lse_ref[...]
        delta = delta_ref[...]
        safe_lse = jnp.where(jnp.isneginf(lse), 0.0, lse)
        k_blk = k_ref[...].astype(jnp.float32)
        v_blk = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        mask = _mask_block(i, j, q_len, k_len, block_q, block_k, causal)
        p = jnp.where(mask, jnp.exp(s - safe_lse), 0.0)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta)
        dq_sc[:] = dq_sc[:] + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[...] = (dq_sc[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_sc, dv_sc,
    *, scale, causal, q_len, k_len, block_q, block_k, nq,
):
    j = pl.program_id(2)
    i = pl.program_id(3)

    @pl.when(i == 0)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    live = _causal_live(i, j, block_q, block_k) if causal else (i >= 0)

    @pl.when(live)
    def _accumulate():
        k_blk = k_ref[...].astype(jnp.float32)  # [bk, D]
        v_blk = v_ref[...].astype(jnp.float32)
        q = q_ref[...].astype(jnp.float32) * scale  # [bq, D]
        do = do_ref[...].astype(jnp.float32)
        lse = lse_ref[...]
        delta = delta_ref[...]
        safe_lse = jnp.where(jnp.isneginf(lse), 0.0, lse)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        mask = _mask_block(i, j, q_len, k_len, block_q, block_k, causal)
        p = jnp.where(mask, jnp.exp(s - safe_lse), 0.0)  # [bq, bk]
        dv_sc[:] = dv_sc[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta)
        # q was pre-scaled, so ds@q carries one factor of `scale` already —
        # the remaining factor belongs to dq only
        dk_sc[:] = dk_sc[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(i == nq - 1)
    def _finish():
        dk_ref[...] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[:].astype(dv_ref.dtype)


def _bwd(causal, scale, block_q, block_k, interpret, residuals, g):
    q, k, v, o, lse = residuals  # lse: [B, H, Tq_p, 1] as the forward wrote it
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    bq, bk, Tq_p, Tk_p = _blocks(Tq, Tk, block_q, block_k)
    nq, nk = Tq_p // bq, Tk_p // bk
    qh = _heads_first(q, Tq_p)
    kh, vh = _heads_first(k, Tk_p), _heads_first(v, Tk_p)
    doh, oh = _heads_first(g, Tq_p), _heads_first(o, Tq_p)
    # delta_i = rowsum(dO_i * O_i) — the softmax-jacobian correction term
    delta = jnp.sum(
        doh.astype(jnp.float32) * oh.astype(jnp.float32), axis=-1, keepdims=True
    )

    dq_kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, causal=causal, q_len=Tq, k_len=Tk,
        block_q=bq, block_k=bk, nk=nk,
    )
    dq = pl.pallas_call(
        dq_kernel,
        name="flash_bwd_dq",
        grid=(B, H, nq, nk),
        in_specs=[
            _tile(bq, D, 2), _tile(bk, D, 3), _tile(bk, D, 3),
            _tile(bq, D, 2), _tile(bq, 1, 2), _tile(bq, 1, 2),
        ],
        out_specs=_tile(bq, D, 2),
        out_shape=jax.ShapeDtypeStruct((B, H, Tq_p, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
    )(qh, kh, vh, doh, lse, delta)

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal, q_len=Tq, k_len=Tk,
        block_q=bq, block_k=bk, nq=nq,
    )
    # k blocks outermost here: grid axis 2 walks k, axis 3 walks q
    dk, dv = pl.pallas_call(
        dkv_kernel,
        name="flash_bwd_dkv",
        grid=(B, H, nk, nq),
        in_specs=[
            _tile(bq, D, 3), _tile(bk, D, 2), _tile(bk, D, 2),
            _tile(bq, D, 3), _tile(bq, 1, 3), _tile(bq, 1, 3),
        ],
        out_specs=[_tile(bk, D, 2), _tile(bk, D, 2)],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tk_p, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, Tk_p, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        interpret=interpret,
    )(qh, kh, vh, doh, lse, delta)
    return _heads_last(dq, Tq), _heads_last(dk, Tk), _heads_last(dv, Tk)


# ----------------------------------------------------------------------
# public op
# ----------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Blockwise exact attention; same contract as ``full_attention``.

    ``q/k/v``: ``[B, T, H, D]`` (Tq may differ from Tk).  ``interpret=None``
    auto-selects Pallas interpret mode off-TPU.
    """
    out, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret)
    return out


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if interpret is None:
        interpret = _interpret_default()
    o, lse = _fwd(q, k, v, causal, scale, block_q, block_k, interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, residuals, g):
    if scale is None:
        scale = 1.0 / (residuals[0].shape[-1] ** 0.5)
    if interpret is None:
        interpret = _interpret_default()
    return _bwd(causal, scale, block_q, block_k, interpret, residuals, g)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


# ======================================================================
# segment-packed flash attention (the pad-free packed-learner kernel)
#
# Self-attention over rows that PACK several independent sequences (the
# ``genrl/rollout.py`` bin-packer's layout): ``segment_ids [B, T]`` give
# every token its sequence id within the row (0 = pad), and a token
# attends only causally WITHIN its own segment.  The kernel is the
# training-grade twin of :func:`flash_attention` — same online-softmax
# accumulators, same FlashAttention-2 backward split — with one
# difference in how the work is cut up.  A grid step costs about a third
# of a microsecond whatever it does, so a step here is not one score tile
# but one tile of the stationary operand (q rows in the forward and dq
# kernels, k rows in the dk/dv kernel) against a ``major`` block of the
# streamed operand that usually is the whole row; the score tiles
# ``[block_q, block_k]`` are walked by a loop INSIDE the step (a tile of
# 512 rows; several heads a step measured within 7% either way and were
# left out).  The loop's bounds are the segment-id block skip:
# segments are contiguous and ascending inside a row and pad is a zero
# tail, so the blocks a tile can pair with form one interval, computed
# once a call from the ids (``_live_blocks``) and handed to the kernels as
# prefetched scalars.  Cross-segment, above-diagonal and pad-only blocks
# cost neither a grid step nor a loop iteration, and a major block outside
# the interval is not fetched.  That skip is where the packed learner's
# FLOPs go from O(rows * T^2) to O(sum of per-segment len^2).
# ======================================================================

# what one grid step may hold of v5e's 128 MiB of VMEM by the estimate
# below, and the scoped limit handed to Mosaic (its default is 16 MiB)
_SEG_VMEM_BUDGET = 24 * 2**20
_SEG_VMEM_LIMIT = 32 * 2**20
_SEG_BLOCK = 512  # score tile edge chosen when the caller names none


class SegmentTiling(NamedTuple):
    """How one ``segment_flash_attention`` call is cut up."""

    block_q: int  # rows of a score tile
    block_k: int  # columns of a score tile
    major: int  # rows of the streamed operand a grid step holds
    t_pad: int  # T rounded up to whole major blocks

    def grid(self, B: int, H: int, q_stationary: bool) -> Tuple[int, int, int, int]:
        """``(B, H, tiles, majors)``: q tiles stationary in the forward and
        dq calls, k tiles in the dk/dv call."""
        rows = self.block_q if q_stationary else self.block_k
        return (B, H, self.t_pad // rows, self.t_pad // self.major)

    def grid_steps(self, B: int, H: int) -> Tuple[int, int]:
        """Grid steps of (the forward and dq calls, the dk/dv call)."""
        return math.prod(self.grid(B, H, True)), math.prod(self.grid(B, H, False))


def _seg_vmem_bytes(bq: int, bk: int, major: int, D: int, itemsize: int) -> int:
    """Upper estimate of the widest of the three kernels' VMEM: operands
    double-buffered, a ``[rows, D]`` block padded to 128 lanes, a
    ``[rows, 1]`` column (lse, delta, q-side ids) to a lane tile a row."""
    row = _round_up(D, 128) * itemsize
    col = 128 * 4
    tile = max(bq, bk)
    operands = 2 * (tile * (4 * row + 3 * col) + major * (2 * row + 3 * col))
    scratch = tile * (2 * _round_up(D, 128) * 4 + 2 * col)
    scores = 6 * bq * _round_up(bk, 128) * 4
    return operands + scratch + scores


def segment_flash_tiling(
    T: int,
    D: int,
    dtype,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
) -> SegmentTiling:
    """The tiling ``segment_flash_attention`` runs at these shapes.

    A block the caller names is honoured; one left ``None`` is
    ``_SEG_BLOCK`` rows, or the whole row where that is shorter.  The
    streamed operand's major block is the largest whole fraction of the
    row that the VMEM budget holds: the whole of a 1,024-token row at any
    head size in use, a quarter of an 8k-token one."""

    def edge(block):
        if block is None:  # a lane multiple, so that any T tiles legally
            return min(_SEG_BLOCK, _round_up(T, 128 if T >= 128 else 8))
        return min(block, _round_up(T, 8))

    bq, bk = edge(block_q), edge(block_k)
    unit = math.lcm(bq, bk)
    itemsize = jnp.dtype(dtype).itemsize
    total = -(-T // unit)
    units = max(
        u for u in range(1, total + 1)
        if total % u == 0
        and (u == 1 or _seg_vmem_bytes(bq, bk, unit * u, D, itemsize) <= _SEG_VMEM_BUDGET)
    )
    return SegmentTiling(bq, bk, unit * units, _round_up(T, unit * units))


@functools.lru_cache(maxsize=None)
def _note_tiling(
    shape: Tuple[int, ...], v_head: int, dtype: str, tl: SegmentTiling
) -> None:
    """The mechanism always engages, so its counter is its geometry: one
    zero-length program span a traced shape (the cache is the "once"),
    never one a step, so that a trace says which tiling ran.  ``shape`` is
    q's and k's, ``v_head`` the head size of v and of the output."""
    from scalerl_tpu.runtime import tracing

    steps, steps_dkv = tl.grid_steps(shape[0], shape[2])
    with tracing.span(
        "segment_flash.tiling", kind="kernel", shape=list(shape), v_head=v_head,
        dtype=dtype, grid_steps=steps, grid_steps_dkv=steps_dkv, **tl._asdict(),
    ):
        pass


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _live_blocks(seg: jnp.ndarray, tile: int, block: int, tiles_are_q: bool):
    """``[lo, hi)`` per ``tile``-row tile of one operand: the ``block``-row
    blocks of the other operand that hold a position some row of the tile
    attends to (q tiles) or is attended from (k tiles).  Exact for the
    packer's layout and a superset for any other, so the in-kernel masks
    alone decide values.  Flattened ``[B * T/tile]`` int32, for SMEM.
    Jitted so that a step of 24 layers traces it once, not 72 times."""
    B, T = seg.shape
    tiles = seg.reshape(B, T // tile, tile)
    t_hi = tiles.max(-1)[..., None]
    t_lo = jnp.where(tiles > 0, tiles, _SEG_BIG).min(-1)[..., None]
    other = seg[:, None, :]
    pos = jnp.arange(T, dtype=jnp.int32)
    start = jnp.arange(T // tile, dtype=jnp.int32)[:, None] * tile
    causal = pos < start + tile if tiles_are_q else pos >= start
    live = (other > 0) & (other >= t_lo) & (other <= t_hi) & causal
    first = jnp.min(jnp.where(live, pos, T), axis=-1)
    last = jnp.max(jnp.where(live, pos, -1), axis=-1)
    return (first // block).reshape(-1), ((last + block) // block).reshape(-1)


def _seg_operands(seg: jnp.ndarray, tl: SegmentTiling):
    """Segment ids as a q-side ``[B, T_p, 1]`` column and k-side
    ``[B, T_p/bk, 1, bk]`` rows, so the kernels compare them by
    broadcast and pick a k block by its leading index."""
    seg = seg.astype(jnp.int32)
    B, T = seg.shape
    if T != tl.t_pad:
        # pad tail rides segment id 0 -> masked everywhere by construction
        seg = jnp.pad(seg, ((0, 0), (0, tl.t_pad - T)))
    return seg, seg[:, :, None], seg.reshape(B, -1, 1, tl.block_k)


def _seg_specs(tl: SegmentTiling, q_stationary: bool):
    """BlockSpec makers for a grid ``(B, H, tiles, majors)``: tiles of
    the stationary operand, major blocks of the streamed one; index maps
    also receive the two prefetched ``_live_blocks`` arrays."""
    rows = tl.block_q if q_stationary else tl.block_k
    block = tl.block_k if q_stationary else tl.block_q
    n_tiles, n_major = tl.t_pad // rows, tl.t_pad // tl.major
    per_major = tl.major // block

    def major(b, t, m, lo_ref, hi_ref):
        """Major block ``m``, held inside the tile's live interval so that
        a step with nothing to do re-names the resident block: no copy."""
        if n_major == 1:
            return 0
        first = jnp.minimum(lo_ref[b * n_tiles + t] // per_major, n_major - 1)
        last = jnp.clip((hi_ref[b * n_tiles + t] - 1) // per_major, first, n_major - 1)
        return jnp.clip(m, first, last)

    def tile(width):
        return pl.BlockSpec(
            (None, None, rows, width), lambda b, h, t, m, lo, hi: (b, h, t, 0)
        )

    def stream(width):
        return pl.BlockSpec(
            (None, None, tl.major, width),
            lambda b, h, t, m, lo, hi: (b, h, major(b, t, m, lo, hi), 0),
        )

    if q_stationary:
        qseg = pl.BlockSpec((None, rows, 1), lambda b, h, t, m, lo, hi: (b, t, 0))
        kseg = pl.BlockSpec(
            (None, per_major, 1, block),
            lambda b, h, t, m, lo, hi: (b, major(b, t, m, lo, hi), 0, 0),
        )
    else:
        qseg = pl.BlockSpec(
            (None, tl.major, 1),
            lambda b, h, t, m, lo, hi: (b, major(b, t, m, lo, hi), 0),
        )
        kseg = pl.BlockSpec((None, 1, 1, rows), lambda b, h, t, m, lo, hi: (b, t, 0, 0))
    return tile, stream, qseg, kseg


def _seg_pallas_call(
    kernel, name, grid, in_specs, out_specs, out_shape, scratch_shapes, interpret
):
    return pl.pallas_call(
        kernel,
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # the two _live_blocks arrays
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch_shapes,
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_SEG_VMEM_LIMIT,
        ),
        interpret=interpret,
    )


def _seg_loop_bounds(lo_ref, hi_ref, tile, m, per_major: int):
    """The tile's live blocks that lie in major block ``m``, as indices
    into that major block (an empty range when there are none)."""
    lo = jnp.maximum(lo_ref[tile] - m * per_major, 0)
    hi = jnp.minimum(hi_ref[tile] - m * per_major, per_major)
    return lo, hi


def _seg_mask(rel, gap, q_seg, k_seg):
    """[bq, bk] validity: causal, same nonzero segment.  ``rel`` is column
    minus row inside the tile, ``gap`` the tile's first q position minus
    its first k position; pad (id 0) never passes, so neither does the
    tail that rounds T up."""
    return (rel <= gap) & (q_seg == k_seg) & (q_seg > 0)


def _rel(bq: int, bk: int):
    return jax.lax.broadcasted_iota(
        jnp.int32, (bq, bk), 1
    ) - jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)


def _seg_fwd_kernel(
    lo_ref, hi_ref, q_ref, k_ref, v_ref, qseg_ref, kseg_ref, o_ref, lse_ref,
    acc_sc, m_sc, l_sc,
    *, scale, tl: SegmentTiling,
):
    b, i, km = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    bq, bk = tl.block_q, tl.block_k

    @pl.when(km == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    lo, hi = _seg_loop_bounds(
        lo_ref, hi_ref, b * pl.num_programs(2) + i, km, tl.major // bk
    )
    q = q_ref[...].astype(jnp.float32) * scale
    q_seg = qseg_ref[...]  # [bq, 1]
    rel = _rel(bq, bk)
    gap0 = i * bq - km * tl.major

    def k_block(j, carry):
        cols = pl.ds(pl.multiple_of(j * bk, bk), bk)
        k_blk = k_ref[cols, :].astype(jnp.float32)
        v_blk = v_ref[cols, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        mask = _seg_mask(rel, gap0 - j * bk, q_seg, kseg_ref[j])
        s = jnp.where(mask, s, _NEG_INF)
        m = m_sc[...]
        l = l_sc[...]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - safe_m)
        corr = jnp.exp(jnp.where(jnp.isneginf(m), _NEG_INF, m) - safe_m)
        l_sc[...] = l * corr + p.sum(axis=-1, keepdims=True)
        m_sc[...] = m_new
        acc_sc[...] = acc_sc[...] * corr + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return carry

    jax.lax.fori_loop(lo, hi, k_block, 0)

    @pl.when(km == pl.num_programs(3) - 1)
    def _finish():
        l = l_sc[...]
        m = m_sc[...]
        # fully-masked rows (pad queries) emit exact zeros, matching the
        # reference — their outputs are unused but must stay finite
        o_ref[...] = (acc_sc[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        lse_ref[...] = jnp.where(
            l > 0.0, m + jnp.log(jnp.maximum(l, 1e-30)), _NEG_INF
        )


def _seg_fwd(q, k, v, seg, scale, block_q, block_k, interpret):
    B, T, H, D = q.shape
    Dv = v.shape[-1]  # v, and so the output, may be narrower than q and k
    tl = segment_flash_tiling(T, D, q.dtype, block_q, block_k)
    _note_tiling(q.shape, Dv, jnp.dtype(q.dtype).name, tl)
    qh, kh, vh = (_heads_first(x, tl.t_pad) for x in (q, k, v))
    seg_p, qseg, kseg = _seg_operands(seg, tl)
    tile, stream, qseg_spec, kseg_spec = _seg_specs(tl, q_stationary=True)

    o, lse = _seg_pallas_call(
        functools.partial(_seg_fwd_kernel, scale=scale, tl=tl),
        "segment_flash_fwd", tl.grid(B, H, True),
        in_specs=[tile(D), stream(D), stream(Dv), qseg_spec, kseg_spec],
        out_specs=[tile(Dv), tile(1)],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, tl.t_pad, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, H, tl.t_pad, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tl.block_q, Dv), jnp.float32),
            pltpu.VMEM((tl.block_q, 1), jnp.float32),
            pltpu.VMEM((tl.block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(*_live_blocks(seg_p, tl.block_q, tl.block_k, True), qh, kh, vh, qseg, kseg)
    return _heads_last(o, T), lse


def _seg_bwd_dq_kernel(
    lo_ref, hi_ref, q_ref, k_ref, v_ref, qseg_ref, kseg_ref, do_ref, lse_ref,
    delta_ref, dq_ref, dq_sc,
    *, scale, tl: SegmentTiling,
):
    b, i, km = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    bq, bk = tl.block_q, tl.block_k

    @pl.when(km == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    lo, hi = _seg_loop_bounds(
        lo_ref, hi_ref, b * pl.num_programs(2) + i, km, tl.major // bk
    )
    q = q_ref[...].astype(jnp.float32) * scale
    do = do_ref[...].astype(jnp.float32)
    lse = lse_ref[...]
    delta = delta_ref[...]
    safe_lse = jnp.where(jnp.isneginf(lse), 0.0, lse)
    q_seg = qseg_ref[...]  # [bq, 1]
    rel = _rel(bq, bk)
    gap0 = i * bq - km * tl.major

    def k_block(j, carry):
        cols = pl.ds(pl.multiple_of(j * bk, bk), bk)
        k_blk = k_ref[cols, :].astype(jnp.float32)
        v_blk = v_ref[cols, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        mask = _seg_mask(rel, gap0 - j * bk, q_seg, kseg_ref[j])
        p = jnp.where(mask, jnp.exp(s - safe_lse), 0.0)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta)
        dq_sc[...] = dq_sc[...] + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return carry

    jax.lax.fori_loop(lo, hi, k_block, 0)

    @pl.when(km == pl.num_programs(3) - 1)
    def _finish():
        dq_ref[...] = (dq_sc[...] * scale).astype(dq_ref.dtype)


def _seg_bwd_dkv_kernel(
    lo_ref, hi_ref, q_ref, k_ref, v_ref, qseg_ref, kseg_ref, do_ref, lse_ref,
    delta_ref, dk_ref, dv_ref, dk_sc, dv_sc,
    *, scale, tl: SegmentTiling,
):
    b, j, qm = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    bq, bk = tl.block_q, tl.block_k

    @pl.when(qm == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    lo, hi = _seg_loop_bounds(
        lo_ref, hi_ref, b * pl.num_programs(2) + j, qm, tl.major // bq
    )
    k_blk = k_ref[...].astype(jnp.float32)
    v_blk = v_ref[...].astype(jnp.float32)
    k_seg = kseg_ref[0]  # [1, bk]
    rel = _rel(bq, bk)
    gap0 = qm * tl.major - j * bk

    def q_block(i, carry):
        rows = pl.ds(pl.multiple_of(i * bq, bq), bq)
        q = q_ref[rows, :].astype(jnp.float32) * scale
        do = do_ref[rows, :].astype(jnp.float32)
        lse = lse_ref[rows, :]
        delta = delta_ref[rows, :]
        safe_lse = jnp.where(jnp.isneginf(lse), 0.0, lse)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        mask = _seg_mask(rel, gap0 + i * bq, qseg_ref[rows, :], k_seg)
        p = jnp.where(mask, jnp.exp(s - safe_lse), 0.0)
        dv_sc[...] = dv_sc[...] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta)
        # q carries one factor of `scale` already (same split as the
        # causal kernel): the remaining factor belongs to dq only
        dk_sc[...] = dk_sc[...] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return carry

    jax.lax.fori_loop(lo, hi, q_block, 0)

    @pl.when(qm == pl.num_programs(3) - 1)
    def _finish():
        dk_ref[...] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


def _seg_bwd(scale, block_q, block_k, interpret, residuals, g):
    q, k, v, seg, o, lse = residuals  # lse: [B, H, T_p, 1]
    B, T, H, D = q.shape
    Dv = v.shape[-1]
    tl = segment_flash_tiling(T, D, q.dtype, block_q, block_k)
    qh, kh, vh, doh, oh = (_heads_first(x, tl.t_pad) for x in (q, k, v, g, o))
    seg_p, qseg, kseg = _seg_operands(seg, tl)
    delta = jnp.sum(
        doh.astype(jnp.float32) * oh.astype(jnp.float32), axis=-1, keepdims=True
    )
    operands = (qh, kh, vh, qseg, kseg, doh, lse, delta)

    tile, stream, qseg_spec, kseg_spec = _seg_specs(tl, q_stationary=True)
    dq = _seg_pallas_call(
        functools.partial(_seg_bwd_dq_kernel, scale=scale, tl=tl),
        "segment_flash_bwd_dq", tl.grid(B, H, True),
        in_specs=[
            tile(D), stream(D), stream(Dv), qseg_spec, kseg_spec,
            tile(Dv), tile(1), tile(1),
        ],
        out_specs=tile(D),
        out_shape=jax.ShapeDtypeStruct((B, H, tl.t_pad, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((tl.block_q, D), jnp.float32)],
        interpret=interpret,
    )(*_live_blocks(seg_p, tl.block_q, tl.block_k, True), *operands)

    # k tiles stationary here: grid axis 2 walks k, the loop walks q
    tile, stream, qseg_spec, kseg_spec = _seg_specs(tl, q_stationary=False)
    dk, dv = _seg_pallas_call(
        functools.partial(_seg_bwd_dkv_kernel, scale=scale, tl=tl),
        "segment_flash_bwd_dkv", tl.grid(B, H, False),
        in_specs=[
            stream(D), tile(D), tile(Dv), qseg_spec, kseg_spec,
            stream(Dv), stream(1), stream(1),
        ],
        out_specs=[tile(D), tile(Dv)],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, tl.t_pad, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, tl.t_pad, Dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((tl.block_k, D), jnp.float32),
            pltpu.VMEM((tl.block_k, Dv), jnp.float32),
        ],
        interpret=interpret,
    )(*_live_blocks(seg_p, tl.block_k, tl.block_q, False), *operands)
    return _heads_last(dq, T), _heads_last(dk, T), _heads_last(dv, T)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def segment_flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    segment_ids: jnp.ndarray,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Segment-packed causal self-attention, forward AND backward.

    ``q/k/v``: ``[B, T, H, D]`` with T shared (self-attention over packed
    rows); ``v`` may have a head size of its own (latent attention: q and
    k 192, v 128), which is then the output's.  ``segment_ids``: ``[B, T]`` int32, contiguous ascending ids
    starting at 1 with a zero pad tail (the ``genrl/rollout.py`` packer's
    contract).  Token ``i`` attends to ``j <= i`` iff
    ``segment_ids[i] == segment_ids[j] != 0``.  Fully-masked rows (pad
    queries) emit exact zeros.  ``block_q`` / ``block_k`` name the score
    tile; left ``None`` it is chosen from the shape
    (:func:`segment_flash_tiling`).  ``interpret=None`` auto-selects
    Pallas interpret mode off-TPU.
    """
    out, _ = _segment_flash_fwd(
        q, k, v, segment_ids, scale, block_q, block_k, interpret
    )
    return out


def _segment_flash_fwd(q, k, v, seg, scale, block_q, block_k, interpret):
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if interpret is None:
        interpret = _interpret_default()
    o, lse = _seg_fwd(q, k, v, seg, scale, block_q, block_k, interpret)
    return o, (q, k, v, seg, o, lse)


def _segment_flash_bwd(scale, block_q, block_k, interpret, residuals, g):
    if scale is None:
        scale = 1.0 / (residuals[0].shape[-1] ** 0.5)
    if interpret is None:
        interpret = _interpret_default()
    dq, dk, dv = _seg_bwd(scale, block_q, block_k, interpret, residuals, g)
    # int segment ids are non-differentiable: their cotangent is float0
    dseg = np.zeros(residuals[3].shape, dtype=jax.dtypes.float0)
    return dq, dk, dv, dseg


segment_flash_attention.defvjp(_segment_flash_fwd, _segment_flash_bwd)


def segment_attention_reference(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    segment_ids: jnp.ndarray,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Dense XLA oracle for :func:`segment_flash_attention` — values AND
    gradients, including the exact-zero output at fully-masked (pad)
    rows.  Materializes the ``[T, T]`` scores: the parity reference and
    the off-TPU fallback shape, never the TPU hot path."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    seg = segment_ids.astype(jnp.int32)
    T = q.shape[1]
    causal = jnp.arange(T)[None, :, None] >= jnp.arange(T)[None, None, :]
    mask = (
        causal
        & (seg[:, :, None] == seg[:, None, :])
        & (seg[:, :, None] > 0)
    )  # [B, T, T]
    scores = (
        jnp.einsum(
            "bthd,bshd->bhts",
            q.astype(jnp.float32),
            k.astype(jnp.float32),
        )
        * scale
    )
    scores = jnp.where(mask[:, None, :, :], scores, jnp.float32(-1e30))
    probs = jax.nn.softmax(scores, axis=-1)
    # zero (not uniform) on fully-masked rows, matching the kernel
    probs = jnp.where(
        jnp.any(mask, axis=-1)[:, None, :, None], probs, 0.0
    )
    out = jnp.einsum("bhts,bshd->bthd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def resolve_segment_attn(impl: str = "auto") -> str:
    """``pallas`` on TPU, ``xla`` elsewhere; ``SCALERL_SEGMENT_ATTN``
    overrides what ``auto`` resolves to (the ``SCALERL_PAGED_ATTN`` /
    ``SCALERL_ITER_MODE`` escape-hatch pattern)."""
    impls = ("pallas", "xla")
    if impl == "auto":
        impl = os.environ.get("SCALERL_SEGMENT_ATTN", "") or (
            "pallas" if jax.default_backend() == "tpu" else "xla"
        )
    if impl not in impls:
        raise ValueError(
            f"segment attention impl must be auto | pallas | xla, got "
            f"{impl!r}"
        )
    return impl


def shard_segment_attn(attn_fn: Callable, mesh) -> Callable:
    """``attn_fn(q, k, v, segment_ids)`` run per shard of a dp×mp learner
    mesh: rows over the data axes, heads over the model axis.

    GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map"), so a
    sharded learn step must place the kernel itself.  Attention is
    independent per (row, head) — the layout the logical rules already give
    q/k/v — so the shards need no collective.  The row count must divide
    by the data axes and the head count by ``mp``.
    """
    from jax.sharding import PartitionSpec as P

    qkv = P(("dp", "fsdp"), None, "mp", None)
    return jax.shard_map(
        attn_fn,
        mesh=mesh,
        in_specs=(qkv, qkv, qkv, P(("dp", "fsdp"), None)),
        out_specs=qkv,
        check_vma=False,
    )


def make_segment_attn_fn(impl: str = "auto") -> Optional[Callable]:
    """The ``TransformerPolicy.segment_attn_fn`` seam: resolve once,
    close over the choice.  Returns ``None`` for ``xla`` — the model then
    builds the dense packed mask and rides its existing
    ``_masked_attention`` path, which XLA fuses better than an
    interpret-mode kernel off-TPU."""
    if resolve_segment_attn(impl) == "pallas":
        return segment_flash_attention
    return None
