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
- :func:`paged_decode_attention` — the Pallas kernel: grid ``(B,)``, one
  lane a step with all its heads, the page table (as the list of the
  copies that fetch it) and lengths as *scalar-prefetch* operands.  The pools stay in HBM; inside a lane the
  kernel walks the table in blocks of ``P`` pages
  (:func:`pages_per_block`: 128 tokens or, for a narrow row, the whole
  multiples of 128 that fill 512 KiB of a pool; from the pool's shape alone) to
  ``cdiv(length, P * page_size)`` and no further, copying the *live* pages
  into a double-buffered VMEM block while the previous block is attended
  to, the next lane's first block included: one async copy a RUN of live
  slots whose entries are adjacent pool pages (a copy's issue, not its
  bytes, is what a page of 8 tokens costs), one page a copy where the
  table holds no run.  Adjacency is read off the table, never assumed
  (:func:`_copy_list`, in the program around the kernel): the kernel
  is right for any table.  So both the HBM
  reads and the steps are O(live tokens): a table slot past a lane's
  length is never read, a dead lane (length 1) costs one page and one
  block.  Whether the *program around it* stays off the rest of the pool
  is a matter of the pool's stored layout (above), which
  ``tests/test_decode_program_layout.py`` guards.  Scores for all heads
  come from one MXU product of the block against the query arranged
  block-diagonally; online softmax with float32 scores, state and
  accumulator in VMEM scratch; float32 operands reach the MXU as exact
  bfloat16 terms, never rounded.  Interpret mode off-TPU; Mosaic on TPU.

A latent-attention (MLA) model caches ONE row a token that every head
shares; its decode kernel, :func:`paged_decode_latent`, and its XLA twin
are the second half of this file, on the same walk.

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
import numpy as np
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
    ``v_pages``: ``[N, page_size, KV*D]`` dense pools (``[N, page_size, KV,
    D]`` is accepted and flattened); ``KV`` key/value heads, read off the
    pool's width, serve the ``H`` query heads, head ``i`` reading
    ``i // (H / KV)`` (grouped heads; ``KV == H`` is one each).  ``page_table``: ``[B, M]`` int32 page
    ids (junk entries must still be in ``[0, N)`` — the allocator's null
    page 0 — they are masked by ``lengths``).  ``lengths``: ``[B]`` int32
    valid-token counts (>= 1).  Returns ``[B, 1, H, D]``.
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    H, D = q.shape[2], q.shape[3]
    kv_heads = _dense_pool(k_pages).shape[2] // D
    k = gather_pages(k_pages, page_table, kv_heads)  # [B, S, KV, D]
    v = gather_pages(v_pages, page_table, kv_heads)
    if kv_heads != H:
        k = jnp.repeat(k, H // kv_heads, axis=2)
        v = jnp.repeat(v, H // kv_heads, axis=2)
    qf = q[:, 0].astype(jnp.float32)  # [B, H, D]
    scores = jnp.einsum("bhd,bshd->bhs", qf, k.astype(jnp.float32)) * scale
    valid = jnp.arange(k.shape[1])[None, :] < lengths[:, None]  # [B, S]
    scores = jnp.where(valid[:, None, :], scores, jnp.float32(_NEG_BIG))
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhs,bshd->bhd", probs, v.astype(jnp.float32))
    return out[:, None].astype(q.dtype)


# a block of the walk is whole steps of 128 tokens (one 128-lane row of
# scores a head), at least one, as many as fill ``_BLOCK_BYTES`` of a pool:
# a block costs about half a microsecond whatever it holds (the counted
# loops that start and wait for its copies, the serial chain product ->
# mask -> max -> exp -> sum -> product), so a narrow row wants more tokens,
# and every position of a block is computed, masked or not, so not too many.
# Measured on the chip, the kernel alone on tables as the engine makes them,
# us a call at 128 | 256 | 512 | 1,024 tokens (PERF.md, PR 53): rows of 1 KiB
# (two key/value heads of 128 float32) 131 | 108 | 105 | 137 at 40 lanes and
# 347 | 247 | 253 | 342 at 96; rows of 2 KiB 327 | 284 | 313 | 445; at rows
# of 4 KiB (gpt2-medium, PERF.md, PR 26) 64 pays the block's cost twice as
# often and 256 computes more masked positions in dead lanes and tails.
# 512 KiB is 512 tokens of 1 KiB, 256 of 2 KiB, and 128 from 2.5 KiB up
_BLOCK_STEP = 128
_BLOCK_BYTES = 512 * 2**10
# ... and no row, however narrow, is given more than the most that paid at
# the narrowest row measured, 1 KiB (1,024 tokens read worse than 128 there).
# A GUARD, not a measured optimum: no cell has a row under 1 KiB, and
# without it a row of 64 B would get a block of 8,192 tokens
_BLOCK_MOST = 512
# bytes the K and V blocks may hold in VMEM, two buffers each: half of the
# 16 MiB a v5e kernel gets by default
_VMEM_BUDGET = 8 * 2**20


def pages_per_block(page_size: int, width: int, itemsize: int) -> int:
    """Pages the kernel fetches and attends to at a step, from the pool's
    row alone (``width * itemsize`` bytes a token): the whole steps of 128
    tokens that fill ``_BLOCK_BYTES`` of one pool, at least one step and at
    most ``_BLOCK_MOST`` tokens; fewer where ``width = H*D`` is so large
    that four such blocks would pass ``_VMEM_BUDGET``, never under one
    page."""
    row = width * itemsize
    tokens = max(_BLOCK_STEP, _BLOCK_BYTES // row // _BLOCK_STEP * _BLOCK_STEP)
    tokens = min(tokens, _BLOCK_MOST, _VMEM_BUDGET // (4 * row))
    return max(1, tokens // page_size)


def table_copies(table, live, page_size: int, width: int, itemsize: int, pool_pages: int) -> int:
    """Copies the decode kernels issue a pool for ``table`` (numpy, ``[lanes,
    slots]``) where lane ``l`` has ``live[l]`` live slots:
    :func:`_copy_list`'s rule reckoned on the host in one vectorised pass, for the engine's
    ``pages_per_copy``.  A run ends where the next entry is not the next
    page, at a block's end and at the largest size; what is left of it
    goes out in the smaller sizes, largest first."""
    P = pages_per_block(page_size, width, itemsize)
    sizes = _run_sizes(P, pool_pages)
    table = np.asarray(table)
    slot = np.arange(table.shape[1])
    is_live = slot[None, :] < np.asarray(live)[:, None]
    # slot s opens a run unless s - 1 is the page before it in the same block
    opens = np.ones(table.shape, bool)
    opens[:, 1:] = (table[:, 1:] != table[:, :-1] + 1) | (slot[1:] % P == 0)
    ids = np.cumsum(opens.reshape(-1))[is_live.reshape(-1)]
    runs = np.bincount(ids)[1:] if ids.size else ids
    copies = runs // sizes[0]
    rest = runs % sizes[0]
    for n in sizes[1:]:
        copies, rest = copies + rest // n, rest % n
    return int(copies.sum())


def _bf16_terms(x):
    """``x`` as bfloat16 terms that sum to it exactly: a float32's 24-bit
    significand in three 8-bit pieces (high, middle, low), or ``x`` itself
    where it is bfloat16 already."""
    if x.dtype == jnp.bfloat16:
        return (x,)
    x = x.astype(jnp.float32)
    high = x.astype(jnp.bfloat16)
    rest = x - high.astype(jnp.float32)
    middle = rest.astype(jnp.bfloat16)
    low = (rest - middle.astype(jnp.float32)).astype(jnp.bfloat16)
    return high, middle, low


def _dot_f32(a_terms, b, contract):
    """``a @ b`` with float32 operands on an MXU that multiplies bfloat16:
    what ``precision=HIGHEST`` computes (the six products of a 3 x 3 split
    that reach float32's last bits, accumulated in float32), arranged so
    that ``b``, the K or V block, passes the MXU three times and not six.
    ``a_terms`` is ``a``'s three terms stacked on rows, ``[3 * rows, K]``:
    all of them meet ``b``'s high term in one product, the first two its
    middle term, the first its low term.  Measured on the chip against
    ``HIGHEST`` (PERF.md, PR 26): the same error, 13% less kernel time at
    gpt2-medium's width."""
    return _dot_terms(a_terms, _bf16_terms(b), contract)


def _dot_terms(a_terms, b_terms, contract):
    """:func:`_dot_f32` on ``b``'s terms as :func:`_bf16_terms` made them
    (a caller that multiplies one block twice makes them once)."""
    rows = a_terms.shape[0] // 3
    total = None
    # from ``b``'s low term up, and within a product from ``a``'s lowest
    # term up: the small products enter the sum before the leading one
    for i, term in reversed(list(enumerate(b_terms))):
        r = jax.lax.dot_general(
            a_terms[: (3 - i) * rows], term, (contract, ((), ())),
            # bfloat16 terms multiply exactly in one pass, whatever
            # ``jax.default_matmul_precision`` the caller runs under
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32,
        )
        for g in reversed(range(3 - i)):
            piece = r[g * rows:(g + 1) * rows]
            total = piece if total is None else total + piece
    return total


# pages one copy may fetch, largest first and ending in one page: a run of
# adjacent pool pages is cut into these, and a block's share of them is
# what divides it.  A size is a loop of the kernel at each of its three
# sites; measured on the chip (PERF.md, PR 50) a block, a quarter of one at
# the cells' page size and a page do what five sizes do, and two do not
_RUN_SIZES = (16, 4, 1)
# an entry of the copy list: the first page's id in the low bits, and above
# them the slot of its block that the copy fills from
_SLOT_SHIFT = 24


def _run_sizes(block_pages: int, pool_pages: int):
    """The sizes a block of ``block_pages`` cuts its runs into: none
    larger than the block, nor than the pool a copy reads from."""
    return tuple(n for n in _RUN_SIZES if n <= min(block_pages, pool_pages))


def largest_copy(page_size: int, width: int, itemsize: int, pool_pages: int) -> int:
    """Pages the walk's largest copy fetches from such a pool: the stretch
    of adjacent free pages that lets a fresh run go out in whole copies
    (a block may hold several)."""
    return _run_sizes(pages_per_block(page_size, width, itemsize), pool_pages)[0]


def _copy_list(page_table, lengths, page_size: int, block_pages: int, pool_pages: int):
    """The copies that fetch ``page_table``'s live pages, a block of
    ``block_pages`` slots at a time, as the kernels walk them: ``(list,
    ends)``.  A RUN is live slots of one block whose entries are adjacent
    pool pages (``at, at + 1, ..``); it goes out in copies of
    :func:`_run_sizes`' sizes, the largest first.  ``list [lanes, blocks *
    block_pages]`` holds each block's copies in its own slots, the largest
    size's first and a size's in slot order, an entry the first page's id
    with the block's slot it fills from above it (``_SLOT_SHIFT``);
    ``ends [lanes, blocks * sizes]`` holds, a block and a size, where that
    size's copies end in the block's list (the block's copies of that size
    or a larger one).  A table with no two entries adjacent lists its
    pages as they stand, a page a copy.

    Adjacency is read off the table, here, in vector operations of the
    program around the kernel (the same for every layer that attends, so
    XLA keeps one), and the kernel's scalar core, which issues the copies
    and is what a small page costs, runs one counted loop a size with no
    test inside it."""
    assert pool_pages <= 1 << _SLOT_SHIFT, "a page id shares its entry with a slot"
    sizes = _run_sizes(block_pages, pool_pages)
    assert sizes[-1] == 1, "what no larger copy takes goes out a page at a time"
    P = block_pages
    lanes, slots = page_table.shape
    blocks = -(-slots // P)
    table = jnp.pad(page_table.astype(jnp.int32), ((0, 0), (0, blocks * P - slots)))
    slot = jnp.arange(blocks * P, dtype=jnp.int32)[None, :]
    live = jnp.clip(-(-lengths.astype(jnp.int32) // page_size), 1, slots)[:, None]
    before = jnp.pad(table[:, :-1], ((0, 0), (1, 0)), constant_values=-2)
    after = jnp.pad(table[:, 1:], ((0, 0), (0, 1)), constant_values=-2)
    # a run's first slot: a block's first, or one that is not the page after
    # its predecessor's; its last: the lane's last live one, a block's last,
    # or one whose successor is not the next page
    opens = (slot % P == 0) | (table != before + 1)
    closes = (slot + 1 >= live) | ((slot + 1) % P == 0) | (after != table + 1)
    first = jax.lax.cummax(jnp.where(opens, slot, 0), axis=1)
    last = jax.lax.cummin(jnp.where(closes, slot, blocks * P), axis=1, reverse=True)
    at, run = slot - first, last - first + 1  # this slot's place in its run of so many
    # which size's copy starts at this slot (``len(sizes)``: none does)
    kind = jnp.full_like(table, len(sizes))
    rest = run  # the run's pages that no larger size took
    for k, n in enumerate(sizes):
        taken = run - rest
        rest = rest % n
        kind = jnp.where((at >= taken) & (at < run - rest) & ((at - taken) % n == 0), k, kind)
    kind = jnp.where(slot < live, kind, len(sizes)).reshape(lanes, blocks, P)
    # a copy's place in its block's list: after the larger sizes' copies and
    # its own size's at earlier slots
    is_kind = kind[..., None] == jnp.arange(len(sizes), dtype=jnp.int32)  # [.., P, sizes]
    count = jnp.sum(is_kind, axis=2, dtype=jnp.int32)
    ends = jnp.cumsum(count, axis=-1)
    earlier = jnp.cumsum(is_kind, axis=2, dtype=jnp.int32) - is_kind
    place = jnp.sum(jnp.where(is_kind, (ends - count)[:, :, None, :] + earlier, 0), axis=-1)
    place = jnp.where(kind < len(sizes), place, P)  # a slot that starts no copy: nowhere
    in_block = jnp.arange(P, dtype=jnp.int32)
    entry = table.reshape(lanes, blocks, P) | (in_block << _SLOT_SHIFT)
    listed = jnp.sum(
        jnp.where(place[..., None] == in_block, entry[..., None], 0), axis=2
    )
    return listed.reshape(lanes, blocks * P), ends.reshape(lanes, blocks * len(sizes))


def _each_copy(list_ref, ends_ref, lane, block, block_pages, sizes, copy):
    """Block ``block`` of ``lane``'s row of a :func:`_copy_list`, a copy at
    a time: ``copy(j, at, n)`` for the ``n`` pages ``at, at + 1, ..`` that
    fill the block's slots ``j .. j + n``, ``n`` static and one of
    ``sizes``; one counted loop a size.  Whoever starts a block's copies
    and whoever waits for them read the same entries, so every start has
    its wait, on the same semaphore with the same bytes."""
    begin = jnp.int32(0)
    for k, n in enumerate(sizes):
        end = ends_ref[lane, block * len(sizes) + k]

        def one(c, carry, n=n):
            entry = list_ref[lane, block * block_pages + c]
            copy(entry >> _SLOT_SHIFT, entry & ((1 << _SLOT_SHIFT) - 1), n)
            return carry

        jax.lax.fori_loop(begin, end, one, 0)
        begin = end


@functools.lru_cache(maxsize=None)
def _note_tiling(name: str, q_shape, pool_shape, dtype: str, pages: int) -> None:
    """One zero-length program span a traced shape (the cache is the
    "once"), ``paged_decode.tiling`` or ``latent_decode.tiling``, so that a
    trace says which walk ran: lanes, heads, the row's width, tokens a
    block."""
    from scalerl_tpu.runtime import tracing

    with tracing.span(
        name, kind="kernel", shape=list(q_shape),
        pool=list(pool_shape), dtype=dtype, pages_per_block=pages,
        block_tokens=pages * pool_shape[1],
    ):
        pass


def _decode_kernel(
    list_ref, ends_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
    k_buf, v_buf, sems, q_sc, acc_sc, m_sc, l_sc, walked_sc,
    *, scale, head_dim, slots, group=1,
):
    """One lane a grid step, ALL heads, walking the lane's live pages a
    block of ``P`` at a time.  The pools stay in HBM; ``k_buf``/``v_buf``
    are ``[2, P, page, H*D]`` VMEM buffers that the kernel fills itself,
    one async copy a run of adjacent live pages, the next block (this
    lane's, or the next lane's first) in flight while this one is attended
    to.  ``walked_sc``
    counts the blocks walked so far over all lanes: its parity is the
    buffer the current block sits in.

    A block is ``[T, H*D]``: tokens on sublanes, the heads' ``D``-wide
    segments side by side on lanes.  All heads' scores come from one MXU
    product against ``q_sc``, the query arranged block-diagonally (row
    ``h`` holds head ``h``'s segment of the scaled query, zeros
    elsewhere), so scores and softmax state are ``[heads, T]`` and
    ``[heads, 1]``; ``acc_sc`` is ``[heads, H*D]``, of which head ``h``'s
    output is row ``h``'s own segment.  Both products keep float32
    operands (:func:`_dot_f32`).  Pages past the lane's length are neither
    read from the table nor fetched: the positions they would fill are
    masked in the scores and zeroed in the V block, so nothing stale in
    VMEM reaches the result.

    **Grouped heads** (``group`` = query heads a key/value head, > 1): the
    pools' rows hold the ``KV`` key/value heads, ``width = KV * D``, and
    the block-diagonal query has ``group`` rows against each key/value
    head's ``D`` columns: row ``h`` holds query head ``h``'s vector in
    segment ``h // group``.  ``q_ref`` and ``o_ref`` are then ``[rows,
    D]``, a head a row."""
    b = pl.program_id(0)
    lanes = pl.num_programs(0)
    _, P, ps, width = k_buf.shape
    T = P * ps
    rows = acc_sc.shape[0]
    sizes = _run_sizes(P, k_hbm.shape[0])

    def live_pages(lane, i):
        # never a slot past the table's width, whatever the length says (an
        # index read from beyond it could send a copy anywhere), and never
        # no page at all: every lane's first block is some lane's prefetch
        return jnp.clip(pl.cdiv(len_ref[lane], ps), 1, slots) - i * P

    def each_live_run(lane, i, buf, act):
        def copy(j, at, n):
            to = pl.ds(j, n)
            act(pltpu.make_async_copy(k_hbm.at[pl.ds(at, n)], k_buf.at[buf, to], sems.at[0, buf]))
            act(pltpu.make_async_copy(v_hbm.at[pl.ds(at, n)], v_buf.at[buf, to], sems.at[1, buf]))

        _each_copy(list_ref, ends_ref, lane, i, P, sizes, copy)

    @pl.when(b == 0)
    def _first_block():
        walked_sc[0] = 0
        each_live_run(0, 0, 0, lambda copy: copy.start())

    # read after the reset above: what a scratch holds at entry is anyone's
    first = walked_sc[0]
    length = len_ref[b]
    blocks = pl.cdiv(live_pages(b, 0), P)
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    if group == 1:
        own = (col >= row * head_dim) & (col < (row + 1) * head_dim)
        q = jnp.where(own, q_ref[...].astype(jnp.float32) * scale, 0.0)
    else:
        seg = row // group
        own = (col >= seg * head_dim) & (col < (seg + 1) * head_dim)
        # every query row beside itself, once a key/value head
        q_wide = jnp.concatenate(
            [q_ref[...].astype(jnp.float32) * scale] * (width // head_dim), axis=1
        )
        q = jnp.where(own, q_wide, 0.0)
    q_sc[...] = jnp.concatenate(_bf16_terms(q), axis=0)
    acc_sc[...] = jnp.zeros_like(acc_sc)
    m_sc[...] = jnp.full_like(m_sc, _NEG_BIG)
    l_sc[...] = jnp.zeros_like(l_sc)

    def tokens_on_sublanes(pages):
        # merged as float32, whose sublane tile a page of 8 fills
        merged = pages.astype(jnp.float32).reshape(T, width)
        return merged.astype(pages.dtype)

    def attend(i, carry):
        buf = (first + i) % 2
        last = i + 1 == blocks
        next_lane = jnp.where(last, b + 1, b)

        @pl.when(next_lane < lanes)
        def _prefetch():
            each_live_run(
                next_lane, jnp.where(last, 0, i + 1), 1 - buf,
                lambda copy: copy.start(),
            )

        each_live_run(b, i, buf, lambda copy: copy.wait())

        def no_page(j, carry):
            v_buf[buf, j] = jnp.zeros((ps, width), v_buf.dtype)
            return carry

        jax.lax.fori_loop(live_pages(b, i), P, no_page, 0)  # only the last block

        k = tokens_on_sublanes(k_buf[buf])
        s = _dot_f32(q_sc[...], k, ((1,), (1,)))  # [rows, T]
        pos = i * T + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, jnp.float32(_NEG_BIG))
        m = m_sc[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_sc[...] = m_new
        v = tokens_on_sublanes(v_buf[buf])
        p_terms = jnp.concatenate(_bf16_terms(p), axis=0)
        acc_sc[...] = acc_sc[...] * corr + _dot_f32(p_terms, v, ((1,), (0,)))
        return carry

    jax.lax.fori_loop(0, blocks, attend, 0)
    walked_sc[0] = first + blocks
    # lengths >= 1 makes every head's sum positive; rows past the last head
    # (padding to whole sublane tiles) own no segment
    out = jnp.where(own, acc_sc[...] / jnp.maximum(l_sc[...], 1e-30), 0.0)
    if group == 1:
        o_ref[...] = jnp.sum(out, axis=0, keepdims=True).astype(o_ref.dtype)
    else:
        # a row's own segment is the only one that is not zero
        o_ref[...] = sum(
            out[:, j * head_dim:(j + 1) * head_dim] for j in range(width // head_dim)
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

    The page table, as :func:`_copy_list` lists its copies, and lengths
    ride as scalar-prefetch operands (``pltpu.PrefetchScalarGridSpec``):
    they land in SMEM before the kernel body runs, which reads a lane's
    list to choose the pool pages each of its copies fetches.  The pools are handed over where
    they are (``pltpu.ANY``): no ``BlockSpec`` pipeline touches them.

    The call runs under a ``jax.jit`` of its own: a model's layers call it
    on the same shapes, so a program that holds it traces the kernel's
    body and lowers it once, not once a layer (XLA inlines the calls: the
    compiled program is the same).  ``parts`` only keys that ``jit``
    (:func:`_kernel_parts`).
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if interpret is None:
        interpret = _interpret_default()
    return _paged_decode(
        q, k_pages, v_pages, page_table, lengths, float(scale), interpret, _kernel_parts()
    )


def _kernel_parts():
    """What the kernels' bodies are built from, as this module and ``pltpu``
    hold it now: part of the inner ``jit``'s key, so that a tool which
    swaps one for a variant (``benchmark/tools/latent_decode_probe.py``:
    copies only, arithmetic only) is given a trace of the variant and not
    the kernel traced before it."""
    return _bf16_terms, _dot_terms, pltpu.make_async_copy


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "parts"))
def _paged_decode(q, k_pages, v_pages, page_table, lengths, scale, interpret, parts):
    B, T, H, D = q.shape
    if T != 1:
        raise ValueError(f"decode attention takes one query token, got T={T}")
    k_pages, v_pages = _dense_pool(k_pages), _dense_pool(v_pages)
    ps, width = k_pages.shape[1], k_pages.shape[2]
    group = H * D // width  # query heads a key/value head
    if width % D or group * width != H * D:
        raise ValueError(
            f"a pool row holds whole key/value heads of the query's size, "
            f"a divisor of its {H} heads: got pools {k_pages.shape}, q {q.shape}"
        )
    P = pages_per_block(ps, width, k_pages.dtype.itemsize)
    rows = -(-H // 16) * 16  # whole bfloat16 sublane tiles of heads
    _note_tiling("paged_decode.tiling", tuple(q.shape), tuple(k_pages.shape), str(k_pages.dtype), P)

    if group == 1:
        # q and o go a lane's row at a time through the pipeline; Mosaic tiles
        # the last two block dims, and [1, H*D] spans both axes
        row = pl.BlockSpec((None, 1, H * D), lambda b, *_: (b, 0, 0))
        q_in, out_shape = q.reshape(B, 1, H * D), (B, 1, H * D)
    else:
        # a head a row (to whole tiles of rows; a zero query row reads
        # uniformly and is sliced off)
        row = pl.BlockSpec((None, rows, D), lambda b, *_: (b, 0, 0))
        q_in = jnp.pad(q.reshape(B, H, D), ((0, 0), (0, rows - H), (0, 0)))
        out_shape = (B, rows, D)
    pool = pl.BlockSpec(memory_space=pltpu.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # the copy list, its ends, the lengths
        grid=(B,),
        in_specs=[row, pool, pool],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((2, P, ps, width), k_pages.dtype),
            pltpu.VMEM((2, P, ps, width), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),  # [K or V, buffer]
            pltpu.VMEM((3 * rows, width), jnp.bfloat16),  # block-diagonal query
            pltpu.VMEM((rows, width), jnp.float32),  # accumulator
            pltpu.VMEM((rows, 1), jnp.float32),  # running maximum
            pltpu.VMEM((rows, 1), jnp.float32),  # running sum
            pltpu.SMEM((1,), jnp.int32),  # blocks walked
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _decode_kernel, scale=scale, head_dim=D, slots=page_table.shape[1], group=group
        ),
        name="paged_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, q.dtype),
        # the buffers and the block count carry from lane to lane
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(
        *_copy_list(page_table, lengths, ps, P, k_pages.shape[0]),
        lengths.astype(jnp.int32),
        q_in,
        k_pages,
        v_pages,
    )
    if group > 1:
        out = out[:, :H]
    return out.reshape(B, 1, H, D)


# ======================================================================
# The latent (MLA) cache: one row a token that every head shares
# ======================================================================
# A latent-attention layer caches ``[c | rotated k_pe]`` a token, ``W`` =
# ``kv_lora_rank + qk_rope_head_dim`` wide (576 at the published sizes),
# in ONE lane-dense pool with no V pool: the values are the row's first
# ``value_width`` columns.  The pool is ``[num_pages, page_size,
# latent_pool_width(W)]``: ``W`` rounded up to whole 128-lane tiles (640),
# the pad columns zero.  The TPU stores a 576-wide row in 640 lanes
# whatever the shape says, and a page can be copied out of HBM only in
# whole tiles, so the pool says what it is.  The decode query arrives
# *absorbed*, ``q_abs = [W_uk^T q_nope | q_pe]`` (``[H, W]`` a lane), so
# all heads' scores are one ``[H, W] x [W, T]`` product against rows that
# are read once.


def latent_pool_width(row_width: int) -> int:
    """Lanes a latent pool gives a ``row_width``-wide row: whole tiles."""
    return -(-row_width // 128) * 128


def latent_attention(
    q: jnp.ndarray,
    rows: jnp.ndarray,
    mask: jnp.ndarray,
    value_width: int,
    scale: float,
) -> jnp.ndarray:
    """Absorbed attention in plain XLA: ``q [B, T, H, W]`` against latent
    ``rows [B, S, W]`` under ``mask [B, T, S]`` (True = attend), float32
    scores and softmax; returns ``[B, T, H, value_width]`` float32, the
    probability-weighted sum of the rows' first ``value_width`` columns.
    Fully masked query rows degrade to uniform, finite, like
    ``models/transformer._masked_attention``."""
    rows = rows.astype(jnp.float32)
    scores = jnp.einsum("bthw,bsw->bhts", q.astype(jnp.float32), rows) * scale
    scores = jnp.where(mask[:, None, :, :], scores, jnp.float32(_NEG_BIG))
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhts,bsc->bthc", probs, rows[..., :value_width])


def paged_latent_attention_reference(
    q: jnp.ndarray,
    pool: jnp.ndarray,
    page_table: jnp.ndarray,
    lengths: jnp.ndarray,
    value_width: int,
    scale: float,
) -> jnp.ndarray:
    """XLA gather twin of :func:`paged_decode_latent`, the oracle it is
    pinned to: ``q [B, 1, H, W]`` absorbed queries, ``pool [N, page_size,
    latent_pool_width(W)]``, ``page_table [B, M]``, ``lengths [B]`` (>= 1); returns ``[B, 1,
    H, value_width]`` float32."""
    rows = gather_pages(pool, page_table, 1)[:, :, 0, : q.shape[-1]]  # [B, S, W]
    valid = jnp.arange(rows.shape[1])[None, :] < lengths[:, None]
    return latent_attention(q, rows, valid[:, None, :], value_width, scale)


def _latent_kernel(
    list_ref, ends_ref, len_ref, q_ref, pool_hbm, o_ref,
    buf, sems, q_sc, acc_sc, m_sc, l_sc, walked_sc,
    *, scale, value_width, slots,
):
    """:func:`_decode_kernel`'s walk over ONE pool: a lane a grid step,
    its live pages a block of ``P`` at a time, one async copy a run of
    adjacent live pages into the double-buffered ``buf [2, P, page, W]``, the next block (this
    lane's, or the next lane's first) in flight while this one is attended
    to.  The one copied block serves scores AND values: its bfloat16 terms
    are made once, the score product contracts all ``W`` columns against
    the absorbed query ``q_sc [3 * heads, W]``, and the value product
    takes the same terms' first ``value_width`` columns.  Pages past the
    lane's length are not fetched; the positions they would fill are
    masked in the scores and zeroed in the block, so nothing stale in
    VMEM reaches the result."""
    b = pl.program_id(0)
    lanes = pl.num_programs(0)
    _, P, ps, width = buf.shape
    T = P * ps
    sizes = _run_sizes(P, pool_hbm.shape[0])

    def live_pages(lane, i):
        return jnp.clip(pl.cdiv(len_ref[lane], ps), 1, slots) - i * P

    def each_live_run(lane, i, at_buf, act):
        def copy(j, at, n):
            act(pltpu.make_async_copy(
                pool_hbm.at[pl.ds(at, n)], buf.at[at_buf, pl.ds(j, n)], sems.at[at_buf]
            ))

        _each_copy(list_ref, ends_ref, lane, i, P, sizes, copy)

    @pl.when(b == 0)
    def _first_block():
        walked_sc[0] = 0
        each_live_run(0, 0, 0, lambda copy: copy.start())

    first = walked_sc[0]
    length = len_ref[b]
    blocks = pl.cdiv(live_pages(b, 0), P)
    q_sc[...] = jnp.concatenate(
        _bf16_terms(q_ref[...].astype(jnp.float32) * scale), axis=0
    )
    acc_sc[...] = jnp.zeros_like(acc_sc)
    m_sc[...] = jnp.full_like(m_sc, _NEG_BIG)
    l_sc[...] = jnp.zeros_like(l_sc)

    def attend(i, carry):
        at_buf = (first + i) % 2
        last = i + 1 == blocks
        next_lane = jnp.where(last, b + 1, b)

        @pl.when(next_lane < lanes)
        def _prefetch():
            each_live_run(
                next_lane, jnp.where(last, 0, i + 1), 1 - at_buf,
                lambda copy: copy.start(),
            )

        each_live_run(b, i, at_buf, lambda copy: copy.wait())

        def no_page(j, carry):
            buf[at_buf, j] = jnp.zeros((ps, width), buf.dtype)
            return carry

        jax.lax.fori_loop(live_pages(b, i), P, no_page, 0)  # only the last block

        # merged as float32, whose sublane tile a page of 8 fills
        rows = buf[at_buf].astype(jnp.float32).reshape(T, width).astype(buf.dtype)
        terms = _bf16_terms(rows)
        s = _dot_terms(q_sc[...], terms, ((1,), (1,)))  # [heads, T]
        pos = i * T + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, jnp.float32(_NEG_BIG))
        m = m_sc[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_sc[...] = m_new
        p_terms = jnp.concatenate(_bf16_terms(p), axis=0)
        values = tuple(t[:, :value_width] for t in terms)
        acc_sc[...] = acc_sc[...] * corr + _dot_terms(p_terms, values, ((1,), (0,)))
        return carry

    jax.lax.fori_loop(0, blocks, attend, 0)
    walked_sc[0] = first + blocks
    o_ref[...] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)).astype(o_ref.dtype)


def paged_decode_latent(
    q: jnp.ndarray,
    pool: jnp.ndarray,
    page_table: jnp.ndarray,
    lengths: jnp.ndarray,
    value_width: int,
    scale: float,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Pallas absorbed decode attention over a latent pool; same contract
    as :func:`paged_latent_attention_reference`.  Grid ``(lanes,)``, the
    pool left in HBM (``pltpu.ANY``), table and lengths scalar-prefetched,
    float32 scores, softmax state and accumulator; under a ``jax.jit`` of
    its own, like :func:`paged_decode_attention`."""
    if interpret is None:
        interpret = _interpret_default()
    return _paged_latent(
        q, pool, page_table, lengths, int(value_width), float(scale), interpret, _kernel_parts()
    )


@functools.partial(jax.jit, static_argnames=("value_width", "scale", "interpret", "parts"))
def _paged_latent(q, pool, page_table, lengths, value_width, scale, interpret, parts):
    B, T, H, W = q.shape
    if T != 1:
        raise ValueError(f"decode attention takes one query token, got T={T}")
    if pool.shape[2] != latent_pool_width(W) or not 0 < value_width <= W:
        raise ValueError(
            f"a latent pool row is the absorbed query's width in whole "
            f"tiles (the query may come padded to them): got pool "
            f"{pool.shape}, q {q.shape}, value_width {value_width}"
        )
    ps = pool.shape[1]
    P = pages_per_block(ps, pool.shape[2], pool.dtype.itemsize)
    rows = -(-H // 16) * 16  # whole bfloat16 sublane tiles of heads
    _note_tiling("latent_decode.tiling", tuple(q.shape), tuple(pool.shape), str(pool.dtype), P)
    qp = q.reshape(B, H, W)
    if (rows, pool.shape[2]) != (H, W):
        # zeros meet the pool's pad columns, and the rows past the last head
        qp = jnp.pad(qp, ((0, 0), (0, rows - H), (0, pool.shape[2] - W)))
        W = pool.shape[2]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # the copy list, its ends, the lengths
        grid=(B,),
        in_specs=[
            pl.BlockSpec((None, rows, W), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.ANY),
        ],
        out_specs=pl.BlockSpec((None, rows, value_width), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, P, ps, W), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),  # one a buffer
            pltpu.VMEM((3 * rows, W), jnp.bfloat16),  # the absorbed query's terms
            pltpu.VMEM((rows, value_width), jnp.float32),  # accumulator
            pltpu.VMEM((rows, 1), jnp.float32),  # running maximum
            pltpu.VMEM((rows, 1), jnp.float32),  # running sum
            pltpu.SMEM((1,), jnp.int32),  # blocks walked
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _latent_kernel, scale=scale, value_width=value_width, slots=page_table.shape[1]
        ),
        name="paged_decode_latent",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, rows, value_width), jnp.float32),
        # the buffers and the block count carry from lane to lane
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(
        *_copy_list(page_table, lengths, ps, P, pool.shape[0]),
        lengths.astype(jnp.int32),
        qp,
        pool,
    )
    return out[:, None, :H]


def make_paged_attn_fn(impl: str = "auto", attention: str = "mha"):
    """The ``TransformerPolicy.paged_attn_fn`` seam: resolve once, close
    over the choice, keep the jitted decode program shape-stable.  The
    model's cache kind (``BlockSpec.attention``) picks the pair: K and V
    pools of ``H*D`` rows (``mha``) or the one latent pool (``mla``)."""
    pallas = resolve_paged_attn(impl) == "pallas"
    if attention == "mla":
        return paged_decode_latent if pallas else paged_latent_attention_reference
    return paged_decode_attention if pallas else paged_attention_reference
