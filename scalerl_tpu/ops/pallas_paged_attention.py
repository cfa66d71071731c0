"""Pallas TPU paged decode attention: one query token against a block-paged
KV cache (the vLLM cache shape on the continuous-batching plane).

The hot op of ``genrl/continuous.py``'s persistent decode loop: every lane
holds ONE new query token and a page table pointing into a shared pool of
``[num_pages, page_size, H, D]`` K/V blocks, so attention must *gather*
each lane's context through its table instead of slicing a dense
``[B, S, H, D]`` cache.  Two implementations behind one contract:

- :func:`paged_attention_reference` — XLA gather: materialize each lane's
  pages (``k_pages[page_table]``), mask positions ``>= lengths``, explicit
  f32 softmax.  The parity oracle and the CPU-backend default (Pallas
  interpret mode would re-interpret the kernel per decode sub-step).
- :func:`paged_decode_attention` — the Pallas kernel: grid
  ``(B, num_pages_per_lane)``, all heads per step, with the page table and
  lengths as *scalar-prefetch* operands, so each kv step's ``BlockSpec``
  index map reads ``page_table[b, j]`` and DMAs exactly that
  ``[page_size, H, D]`` page from the pool into VMEM — HBM traffic is
  O(live tokens), never O(pool).  Online softmax with float32 accumulators
  in VMEM scratch persisting across the (innermost, sequential) page
  dimension; pages past a lane's length are skipped entirely via
  ``pl.when``.  Interpret mode off-TPU; Mosaic on TPU.

Grad-free by construction: decode is inference-only, no ``custom_vjp`` is
defined, and differentiating through ``pallas_call`` raises — the learner
recomputes logits with the dense training forward, never through this op.

Numerics contract (pinned at 1e-5 against the reference across contiguous,
fragmented, and partially-filled-last-page table layouts): masked scores
use -1e30 (not -inf) exactly like ``models/transformer._masked_attention``,
scores/accumulators are float32 regardless of input dtype, and every lane
must have ``lengths >= 1`` (the engine guarantees it: a lane attends at
least to the token it just wrote; dead lanes are masked downstream).
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_BIG = -1e30


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def resolve_paged_attn(impl: str = "auto") -> str:
    """``pallas`` on TPU, ``xla`` elsewhere; ``SCALERL_PAGED_ATTN``
    overrides what ``auto`` resolves to (the ``SCALERL_PER_METHOD`` /
    ``SCALERL_ITER_MODE`` escape-hatch pattern)."""
    impls = ("pallas", "xla")
    if impl == "auto":
        impl = os.environ.get("SCALERL_PAGED_ATTN", "") or (
            "pallas" if jax.default_backend() == "tpu" else "xla"
        )
    if impl not in impls:
        raise ValueError(
            f"paged attention impl must be auto | pallas | xla, got {impl!r}"
        )
    return impl


def paged_attention_reference(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    lengths: jnp.ndarray,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """XLA gather implementation — the oracle the kernel is pinned to.

    ``q``: ``[B, 1, H, D]`` (one query token per lane).  ``k_pages`` /
    ``v_pages``: ``[N, page_size, H, D]`` pools.  ``page_table``:
    ``[B, M]`` int32 page ids (junk entries must still be in ``[0, N)`` —
    the allocator's null page 0 — they are masked by ``lengths``).
    ``lengths``: ``[B]`` int32 valid-token counts (>= 1).  Returns
    ``[B, 1, H, D]``.
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    B = q.shape[0]
    N, ps = k_pages.shape[0], k_pages.shape[1]
    M = page_table.shape[1]
    # flat single-axis gather: XLA:CPU lowers row gathers of a 3-D operand
    # ~3x faster than fancy-indexing the 4-D pool (measured; the reshape
    # itself is a bitcast)
    idx = (
        page_table[:, :, None] * ps + jnp.arange(ps)[None, None, :]
    ).reshape(B, M * ps)
    k = k_pages.reshape(N * ps, *k_pages.shape[2:])[idx]
    v = v_pages.reshape(N * ps, *v_pages.shape[2:])[idx]
    qf = q[:, 0].astype(jnp.float32)  # [B, H, D]
    scores = jnp.einsum("bhd,bshd->bhs", qf, k.astype(jnp.float32)) * scale
    valid = jnp.arange(M * ps)[None, :] < lengths[:, None]  # [B, S]
    scores = jnp.where(valid[:, None, :], scores, jnp.float32(_NEG_BIG))
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhs,bshd->bhd", probs, v.astype(jnp.float32))
    return out[:, None].astype(q.dtype)


def _decode_kernel(
    pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref, acc_sc, m_sc, l_sc,
    *, scale, page_size, num_pages_per_lane,
):
    """One (lane, page) grid step over ALL heads: ``q_ref``/``o_ref`` are
    ``[H, D]``, ``k_ref``/``v_ref`` one ``[ps, H, D]`` page.  A single
    query row cannot feed the MXU, so scores and the weighted sum are VPU
    products reduced over lanes (D) and over the page axis; the softmax
    state is a ``[H, 1]`` column per head, heads staying on sublanes
    throughout."""
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, _NEG_BIG)
        l_sc[:] = jnp.zeros_like(l_sc)

    length = len_ref[b]
    live = j * page_size < length

    @pl.when(live)
    def _attend():
        q = q_ref[...].astype(jnp.float32) * scale  # [H, D]
        k_blk = k_ref[...].astype(jnp.float32)  # [ps, H, D]
        v_blk = v_ref[...].astype(jnp.float32)
        s = jnp.sum(q[None] * k_blk, axis=-1, keepdims=True)  # [ps, H, 1]
        pos = j * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        s = jnp.where(pos < length, s, jnp.float32(_NEG_BIG))
        m = m_sc[:]  # [H, 1]
        m_new = jnp.maximum(m, s.max(axis=0))
        p = jnp.exp(s - m_new[None])  # [ps, H, 1]
        corr = jnp.exp(m - m_new)
        l_sc[:] = l_sc[:] * corr + p.sum(axis=0)
        m_sc[:] = m_new
        acc_sc[:] = acc_sc[:] * corr + jnp.sum(p * v_blk, axis=0)

    @pl.when(j == num_pages_per_lane - 1)
    def _finish():
        o_ref[...] = (
            acc_sc[:] / jnp.maximum(l_sc[:], 1e-30)
        ).astype(o_ref.dtype)


def paged_decode_attention(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    lengths: jnp.ndarray,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Pallas paged decode attention; same contract as the reference.

    The page table and lengths ride as scalar-prefetch operands
    (``pltpu.PrefetchScalarGridSpec``): they land in SMEM before the
    kernel body runs, so the K/V ``BlockSpec`` index maps dereference
    ``page_table[b, j]`` to choose which pool page each grid step DMAs.
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if interpret is None:
        interpret = _interpret_default()
    B, T, H, D = q.shape
    if T != 1:
        raise ValueError(f"decode attention takes one query token, got T={T}")
    N, ps = k_pages.shape[0], k_pages.shape[1]
    M = page_table.shape[1]

    kernel = functools.partial(
        _decode_kernel, scale=scale, page_size=ps, num_pages_per_lane=M,
    )
    # Mosaic tiles the last two block dims, which must be (8, 128)-divisible
    # or span their axis: every block therefore carries the WHOLE [H, D]
    # head plane (a per-head block would leave H second-to-last at size 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, M),
        in_specs=[
            pl.BlockSpec((None, None, H, D), lambda b, j, pt, ln: (b, 0, 0, 0)),
            pl.BlockSpec(
                (None, ps, H, D), lambda b, j, pt, ln: (pt[b, j], 0, 0, 0)
            ),
            pl.BlockSpec(
                (None, ps, H, D), lambda b, j, pt, ln: (pt[b, j], 0, 0, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (None, None, H, D), lambda b, j, pt, ln: (b, 0, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((H, D), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        name="paged_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, H, D), q.dtype),
        interpret=interpret,
    )(
        page_table.astype(jnp.int32),
        lengths.astype(jnp.int32),
        q,
        k_pages,
        v_pages,
    )


def make_paged_attn_fn(impl: str = "auto"):
    """The ``TransformerPolicy.paged_attn_fn`` seam: resolve once, close
    over the choice, keep the jitted decode program shape-stable."""
    resolved = resolve_paged_attn(impl)
    if resolved == "pallas":
        return paged_decode_attention
    return paged_attention_reference
