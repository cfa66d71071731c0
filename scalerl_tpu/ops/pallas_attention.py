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
import os
from typing import Callable, Optional, Tuple

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
# training-grade twin of :func:`flash_attention` — same tiling, same
# online-softmax accumulators, same FlashAttention-2 backward split —
# plus segment-id block masking: each (q block, k block) grid step first
# reduces the two id vectors to their live ranges (segments are
# contiguous and ascending inside a row, pad is a zero tail, so the
# nonzero ids in any block form one integer interval) and SKIPS the
# matmuls entirely when the intervals cannot intersect — cross-segment
# and pad-only blocks cost two [block] reductions, never a [bq, bk]
# score tile.  That block skip is where the packed learner's FLOPs go
# from O(rows * T^2) to O(sum of per-segment len^2).
# ======================================================================


def _seg_ranges(seg_vec):
    """(min nonzero id, max id) of one block's id vector (pad = 0)."""
    hi = jnp.max(seg_vec)
    lo = jnp.min(jnp.where(seg_vec > 0, seg_vec, jnp.int32(_SEG_BIG)))
    return lo, hi


def _seg_block_live(i, j, q_seg, k_seg, block_q: int, block_k: int):
    """Whether any (q, k) pair in tile (i, j) shares a live segment."""
    q_lo, q_hi = _seg_ranges(q_seg)
    k_lo, k_hi = _seg_ranges(k_seg)
    return (
        _causal_live(i, j, block_q, block_k)
        & (q_hi > 0)
        & (k_hi > 0)
        & (q_lo <= k_hi)
        & (k_lo <= q_hi)
    )


def _seg_mask_block(
    i, j, q_seg, k_seg, q_len: int, block_q: int, block_k: int
):
    """[bq, bk] validity: in-bounds, causal, same nonzero segment."""
    mask = _mask_block(i, j, q_len, q_len, block_q, block_k, causal=True)
    return mask & (q_seg == k_seg) & (q_seg > 0)


def _seg_fwd_kernel(
    q_ref, k_ref, v_ref, qseg_ref, kseg_ref, o_ref, lse_ref,
    acc_sc, m_sc, l_sc,
    *, scale, q_len, block_q, block_k, nk,
):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    q_seg = qseg_ref[...]  # [bq, 1]
    k_seg = kseg_ref[...]  # [1, bk]
    live = _seg_block_live(i, j, q_seg, k_seg, block_q, block_k)

    @pl.when(live)
    def _attend():
        q = q_ref[...].astype(jnp.float32) * scale
        k_blk = k_ref[...].astype(jnp.float32)
        v_blk = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        mask = _seg_mask_block(i, j, q_seg, k_seg, q_len, block_q, block_k)
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
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == nk - 1)
    def _finish():
        l = l_sc[:]
        m = m_sc[:]
        # fully-masked rows (pad queries) emit exact zeros, matching the
        # reference — their outputs are unused but must stay finite
        o_ref[...] = (
            acc_sc[:] / jnp.maximum(l, 1e-30)
        ).astype(o_ref.dtype)
        lse_ref[...] = jnp.where(
            l > 0.0, m + jnp.log(jnp.maximum(l, 1e-30)), _NEG_INF
        )


def _seg_operands(seg: jnp.ndarray, t_pad: int):
    """Segment ids as a q-side ``[B, t_pad, 1]`` column and a k-side
    ``[B, 1, t_pad]`` row, so the kernels compare them by broadcast."""
    seg = seg.astype(jnp.int32)
    T = seg.shape[1]
    if T != t_pad:
        # pad tail rides segment id 0 -> masked everywhere by construction
        seg = jnp.pad(seg, ((0, 0), (0, t_pad - T)))
    return seg[:, :, None], seg[:, None, :]


def _seg_specs(bq: int, bk: int, q_axis: int, k_axis: int):
    return [
        pl.BlockSpec((None, bq, 1), lambda *g: (g[0], g[q_axis], 0)),
        pl.BlockSpec((None, 1, bk), lambda *g: (g[0], 0, g[k_axis])),
    ]


def _seg_fwd(q, k, v, seg, scale, block_q, block_k, interpret):
    B, T, H, D = q.shape
    bq, bk, T_p, _ = _blocks(T, T, block_q, block_k)
    nq, nk = T_p // bq, T_p // bk
    qh, kh, vh = (_heads_first(x, T_p) for x in (q, k, v))
    qseg, kseg = _seg_operands(seg, T_p)

    kernel = functools.partial(
        _seg_fwd_kernel, scale=scale, q_len=T,
        block_q=bq, block_k=bk, nk=nk,
    )
    o, lse = pl.pallas_call(
        kernel,
        name="segment_flash_fwd",
        grid=(B, H, nq, nk),
        in_specs=[
            _tile(bq, D, 2), _tile(bk, D, 3), _tile(bk, D, 3),
            *_seg_specs(bq, bk, 2, 3),
        ],
        out_specs=[_tile(bq, D, 2), _tile(bq, 1, 2)],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T_p, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, T_p, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qh, kh, vh, qseg, kseg)
    return _heads_last(o, T), lse


def _seg_bwd_dq_kernel(
    q_ref, k_ref, v_ref, qseg_ref, kseg_ref, do_ref, lse_ref, delta_ref,
    dq_ref, dq_sc,
    *, scale, q_len, block_q, block_k, nk,
):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    q_seg = qseg_ref[...]  # [bq, 1]
    k_seg = kseg_ref[...]  # [1, bk]
    live = _seg_block_live(i, j, q_seg, k_seg, block_q, block_k)

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
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        mask = _seg_mask_block(i, j, q_seg, k_seg, q_len, block_q, block_k)
        p = jnp.where(mask, jnp.exp(s - safe_lse), 0.0)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta)
        dq_sc[:] = dq_sc[:] + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[...] = (dq_sc[:] * scale).astype(dq_ref.dtype)


def _seg_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, qseg_ref, kseg_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref, dk_sc, dv_sc,
    *, scale, q_len, block_q, block_k, nq,
):
    j = pl.program_id(2)
    i = pl.program_id(3)

    @pl.when(i == 0)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    q_seg = qseg_ref[...]  # [bq, 1]
    k_seg = kseg_ref[...]  # [1, bk]
    live = _seg_block_live(i, j, q_seg, k_seg, block_q, block_k)

    @pl.when(live)
    def _accumulate():
        k_blk = k_ref[...].astype(jnp.float32)
        v_blk = v_ref[...].astype(jnp.float32)
        q = q_ref[...].astype(jnp.float32) * scale
        do = do_ref[...].astype(jnp.float32)
        lse = lse_ref[...]
        delta = delta_ref[...]
        safe_lse = jnp.where(jnp.isneginf(lse), 0.0, lse)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        mask = _seg_mask_block(i, j, q_seg, k_seg, q_len, block_q, block_k)
        p = jnp.where(mask, jnp.exp(s - safe_lse), 0.0)
        dv_sc[:] = dv_sc[:] + jax.lax.dot_general(
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
        dk_sc[:] = dk_sc[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(i == nq - 1)
    def _finish():
        dk_ref[...] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[:].astype(dv_ref.dtype)


def _seg_bwd(scale, block_q, block_k, interpret, residuals, g):
    q, k, v, seg, o, lse = residuals  # lse: [B, H, T_p, 1]
    B, T, H, D = q.shape
    bq, bk, T_p, _ = _blocks(T, T, block_q, block_k)
    nq, nk = T_p // bq, T_p // bk
    qh, kh, vh, doh, oh = (_heads_first(x, T_p) for x in (q, k, v, g, o))
    qseg, kseg = _seg_operands(seg, T_p)
    delta = jnp.sum(
        doh.astype(jnp.float32) * oh.astype(jnp.float32), axis=-1, keepdims=True
    )

    dq_kernel = functools.partial(
        _seg_bwd_dq_kernel, scale=scale, q_len=T,
        block_q=bq, block_k=bk, nk=nk,
    )
    dq = pl.pallas_call(
        dq_kernel,
        name="segment_flash_bwd_dq",
        grid=(B, H, nq, nk),
        in_specs=[
            _tile(bq, D, 2), _tile(bk, D, 3), _tile(bk, D, 3),
            *_seg_specs(bq, bk, 2, 3),
            _tile(bq, D, 2), _tile(bq, 1, 2), _tile(bq, 1, 2),
        ],
        out_specs=_tile(bq, D, 2),
        out_shape=jax.ShapeDtypeStruct((B, H, T_p, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
    )(qh, kh, vh, qseg, kseg, doh, lse, delta)

    dkv_kernel = functools.partial(
        _seg_bwd_dkv_kernel, scale=scale, q_len=T,
        block_q=bq, block_k=bk, nq=nq,
    )
    # k blocks outermost here: grid axis 2 walks k, axis 3 walks q
    dk, dv = pl.pallas_call(
        dkv_kernel,
        name="segment_flash_bwd_dkv",
        grid=(B, H, nk, nq),
        in_specs=[
            _tile(bq, D, 3), _tile(bk, D, 2), _tile(bk, D, 2),
            *_seg_specs(bq, bk, 3, 2),
            _tile(bq, D, 3), _tile(bq, 1, 3), _tile(bq, 1, 3),
        ],
        out_specs=[_tile(bk, D, 2), _tile(bk, D, 2)],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T_p, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, T_p, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        interpret=interpret,
    )(qh, kh, vh, qseg, kseg, doh, lse, delta)
    return _heads_last(dq, T), _heads_last(dk, T), _heads_last(dv, T)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def segment_flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    segment_ids: jnp.ndarray,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Segment-packed causal self-attention, forward AND backward.

    ``q/k/v``: ``[B, T, H, D]`` with T shared (self-attention over packed
    rows).  ``segment_ids``: ``[B, T]`` int32, contiguous ascending ids
    starting at 1 with a zero pad tail (the ``genrl/rollout.py`` packer's
    contract).  Token ``i`` attends to ``j <= i`` iff
    ``segment_ids[i] == segment_ids[j] != 0``.  Fully-masked rows (pad
    queries) emit exact zeros.  ``interpret=None`` auto-selects Pallas
    interpret mode off-TPU.
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
