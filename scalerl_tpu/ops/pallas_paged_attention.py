"""Pallas TPU paged decode attention: one query token against a block-paged
KV cache (the vLLM cache shape on the continuous-batching plane).

The hot op of ``genrl/continuous.py``'s persistent decode loop: every lane
holds ONE new query token and a page table pointing into a shared pool of
K/V pages, so attention must *gather* each lane's context through its
table instead of slicing a dense ``[B, S, H, D]`` cache.

**The pool is lane-dense**: ``[num_pages, page_size, H*D]``, every token's
heads side by side on the minor axis.  With a minor dimension that is whole
128-lane rows the TPU runtime stores the pool row-major, which is the
layout Mosaic asks of its operands, so the decode program reads the pool in
place.  A ``[.., H, D]`` pool with ``D < 128`` is stored page-index-minor
instead, and every program that holds the kernel then transposes each whole
pool into a padded row-major temporary and back (PERF.md, PR 24: 96 copies
and 6.4 GB of temporaries a macro-step at gpt2-medium).  No program may
reshape or transpose a whole pool to a minor dimension under 128; the
consumers reshape what they *gathered*.

Two implementations behind one contract:

- :func:`paged_attention_reference` — XLA gather: materialize each lane's
  pages (rows of ``H*D`` through the table), mask positions ``>= lengths``,
  explicit f32 softmax.  The parity oracle and the CPU-backend default
  (Pallas interpret mode would re-interpret the kernel per decode
  sub-step).
- :func:`paged_decode_attention` — the Pallas kernel: grid
  ``(B, num_pages_per_lane)``, all heads per step, with the page table and
  lengths as *scalar-prefetch* operands, so each kv step's ``BlockSpec``
  index map reads ``page_table[b, j]`` and DMAs exactly that dense
  ``[page_size, H*D]`` page from the pool into VMEM.  The kernel's own HBM
  reads are O(live tokens); whether the *program around it* stays off the
  rest of the pool is a matter of the pool's stored layout (above), which
  ``tests/test_decode_program_layout.py`` guards.  Online softmax with
  float32 accumulators in VMEM scratch persisting across the (innermost,
  sequential) page dimension; pages past a lane's length are skipped
  entirely via ``pl.when``.  Interpret mode off-TPU; Mosaic on TPU.

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
import math
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


def _dense_pool(pages: jnp.ndarray) -> jnp.ndarray:
    """``[N, ps, H, D]`` -> the stored form ``[N, ps, H*D]`` (dense pools
    pass through).  Only callers that hold 4-D pools pay for the reshape:
    tests, and the kernel compile at the benchmark's shapes."""
    if pages.ndim == 4:
        return pages.reshape(*pages.shape[:2], -1)
    return pages


def gather_pages(
    pages: jnp.ndarray, page_table: jnp.ndarray, num_heads: int
) -> jnp.ndarray:
    """Every lane's context through its table, ``[B, M * page_size, H, D]``:
    a flat single-axis gather of ``H*D`` rows (the pool reshape is a bitcast,
    and XLA:CPU lowers row gathers ~3x faster than fancy-indexing the pool).
    The heads are split out of what was gathered, never out of the pool."""
    pages = _dense_pool(pages)
    N, ps, width = pages.shape
    B, M = page_table.shape
    idx = (
        page_table[:, :, None] * ps + jnp.arange(ps)[None, None, :]
    ).reshape(B, M * ps)
    rows = pages.reshape(N * ps, width)[idx]
    return rows.reshape(B, M * ps, num_heads, width // num_heads)


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
    ``v_pages``: ``[N, page_size, H*D]`` dense pools (``[N, page_size, H,
    D]`` is accepted and flattened).  ``page_table``: ``[B, M]`` int32 page
    ids (junk entries must still be in ``[0, N)`` — the allocator's null
    page 0 — they are masked by ``lengths``).  ``lengths``: ``[B]`` int32
    valid-token counts (>= 1).  Returns ``[B, 1, H, D]``.
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    H = q.shape[2]
    k = gather_pages(k_pages, page_table, H)  # [B, S, H, D]
    v = gather_pages(v_pages, page_table, H)
    qf = q[:, 0].astype(jnp.float32)  # [B, H, D]
    scores = jnp.einsum("bhd,bshd->bhs", qf, k.astype(jnp.float32)) * scale
    valid = jnp.arange(k.shape[1])[None, :] < lengths[:, None]  # [B, S]
    scores = jnp.where(valid[:, None, :], scores, jnp.float32(_NEG_BIG))
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhs,bshd->bhd", probs, v.astype(jnp.float32))
    return out[:, None].astype(q.dtype)


def _lane_chunk(num_heads: int, head_dim: int) -> int:
    """Width of the lane slices the kernel works in: the fewest whole
    128-lane rows that hold whole heads, or the whole ``H*D`` axis where
    that does not divide it (the tests' tiny models)."""
    chunk = math.lcm(head_dim, 128)
    return chunk if (num_heads * head_dim) % chunk == 0 else num_heads * head_dim


def _decode_kernel(
    pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref, acc_sc, m_sc, l_sc,
    *, scale, page_size, num_pages_per_lane, head_dim, chunk,
):
    """One (lane, page) grid step over ALL heads: ``q_ref``/``o_ref`` are
    ``[1, H*D]``, ``k_ref``/``v_ref`` one dense ``[ps, H*D]`` page (tokens
    on sublanes, the heads' ``D``-wide segments side by side on lanes).  A
    single query row cannot feed the MXU, so a head's score is a VPU
    product and a masked lane reduction over its segment, broadcast back
    over the segment: scores, softmax state and accumulator all stay in
    the page's own ``[ps, H*D]`` shape.  Each sublane row runs its own
    online softmax over the positions ``r, r + ps, ...`` it sees, so a
    step is elementwise but for that reduction; the ``ps`` rows are merged
    once, at the last page."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    width = q_ref.shape[-1]

    @pl.when(j == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, _NEG_BIG)
        l_sc[:] = jnp.zeros_like(l_sc)

    length = len_ref[b]
    live = j * page_size < length

    @pl.when(live)
    def _attend():
        shape = (page_size, chunk)
        pos = j * page_size + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        valid = pos < length
        lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        heads = [
            (lane >= g * head_dim) & (lane < (g + 1) * head_dim)
            for g in range(chunk // head_dim)
        ]
        for c in range(width // chunk):
            sl = slice(c * chunk, (c + 1) * chunk)
            q = q_ref[:, sl].astype(jnp.float32) * scale  # [1, chunk]
            prod = q * k_ref[:, sl].astype(jnp.float32)  # [ps, chunk]
            if len(heads) == 1:
                s = jnp.broadcast_to(
                    jnp.sum(prod, axis=-1, keepdims=True), shape
                )
            else:
                s = jnp.zeros(shape, jnp.float32)
                for in_head in heads:
                    s_h = jnp.sum(
                        jnp.where(in_head, prod, 0.0), axis=-1, keepdims=True
                    )
                    s = jnp.where(in_head, s_h, s)
            s = jnp.where(valid, s, jnp.float32(_NEG_BIG))
            m = m_sc[:, sl]
            m_new = jnp.maximum(m, s)
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            l_sc[:, sl] = l_sc[:, sl] * corr + p
            m_sc[:, sl] = m_new
            acc_sc[:, sl] = acc_sc[:, sl] * corr + p * v_ref[:, sl].astype(
                jnp.float32
            )

    @pl.when(j == num_pages_per_lane - 1)
    def _finish():
        # merge the rows' softmax streams; a row that saw no valid position
        # still holds -1e30 and weighs exp(-1e30 - m) = 0 (lengths >= 1
        # makes row 0's maximum a real score)
        m = m_sc[:]
        w = jnp.exp(m - jnp.max(m, axis=0, keepdims=True))
        l = jnp.sum(l_sc[:] * w, axis=0, keepdims=True)
        acc = jnp.sum(acc_sc[:] * w, axis=0, keepdims=True)
        o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


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
    k_pages, v_pages = _dense_pool(k_pages), _dense_pool(v_pages)
    ps = k_pages.shape[1]
    M = page_table.shape[1]

    kernel = functools.partial(
        _decode_kernel, scale=scale, page_size=ps, num_pages_per_lane=M,
        head_dim=D, chunk=_lane_chunk(H, D),
    )
    # Mosaic tiles the last two block dims, which must be (8, 128)-divisible
    # or span their axis: a K/V block is one whole dense page, [ps, H*D]
    # (H*D spans its axis whatever the head geometry), q and o one row
    row = pl.BlockSpec((None, 1, H * D), lambda b, j, pt, ln: (b, 0, 0))
    page = pl.BlockSpec(
        (None, ps, H * D), lambda b, j, pt, ln: (pt[b, j], 0, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, M),
        in_specs=[row, page, page],
        out_specs=row,
        scratch_shapes=[pltpu.VMEM((ps, H * D), jnp.float32)] * 3,
    )
    out = pl.pallas_call(
        kernel,
        name="paged_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, H * D), q.dtype),
        interpret=interpret,
    )(
        page_table.astype(jnp.int32),
        lengths.astype(jnp.int32),
        q.reshape(B, 1, H * D),
        k_pages,
        v_pages,
    )
    return out.reshape(B, 1, H, D)


def make_paged_attn_fn(impl: str = "auto"):
    """The ``TransformerPolicy.paged_attn_fn`` seam: resolve once, close
    over the choice, keep the jitted decode program shape-stable."""
    resolved = resolve_paged_attn(impl)
    if resolved == "pallas":
        return paged_decode_attention
    return paged_attention_reference
