"""Paged KV plane (ISSUE 11): the jax-free page allocator's invariants,
Pallas-vs-XLA paged decode attention parity across page-table layouts, the
transformer's paged prefill/decode paths against the dense oracle, and the
quantized snapshot format.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from scalerl_tpu.genrl.paging import PageAllocator, rewind_pages
from scalerl_tpu.models.transformer import (
    ModelCache,
    TransformerPolicy,
    prompt_attention_mask,
    sequence_attention_mask,
)
from scalerl_tpu.ops.pallas_paged_attention import (
    _VMEM_BUDGET,
    largest_copy,
    pages_per_block,
    paged_attention_reference,
    paged_decode_attention,
    resolve_paged_attn,
)
from scalerl_tpu.runtime.quantize import (
    QuantizedLeaf,
    dequantize_tree,
    quantize_tree,
    tree_wire_bytes,
)


# ---------------------------------------------------------------------------
# page allocator (jax-free)


def test_allocator_alloc_free_round_trip():
    a = PageAllocator(num_pages=9, page_size=4)
    assert a.capacity == 8 and a.free_pages == 8
    assert a.try_reserve(5)
    pages = a.alloc(5)
    assert len(set(pages)) == 5 and 0 not in pages
    assert a.allocated_pages == 5 and a.free_pages == 3
    a.free(pages)
    a.release(5)
    assert a.free_pages == 8 and a.reserved == 0
    assert a.pages_for_tokens(1) == 1 and a.pages_for_tokens(9) == 3


def test_allocator_exhaustion_backpressures_never_corrupts():
    a = PageAllocator(num_pages=5, page_size=4)  # capacity 4
    assert a.try_reserve(3)
    assert not a.try_reserve(2)  # would exceed capacity: shed/queue
    assert a.try_reserve(1)
    pages = a.alloc(3)
    # double-free and foreign-free are hard errors, not silent corruption
    a.free(pages[:1])
    with pytest.raises(RuntimeError):
        a.free(pages[:1])
    with pytest.raises(RuntimeError):
        a.free([0])
    with pytest.raises(RuntimeError):
        a.alloc(99)
    with pytest.raises(RuntimeError):
        a.release(99)


def test_allocator_no_aliasing_under_randomized_schedule():
    """Randomized admit/finish churn: at every step no page is owned by
    two live lanes and the free list + live set partition the pool."""
    rng = np.random.default_rng(0)
    a = PageAllocator(num_pages=17, page_size=2)
    live = {}
    for step in range(300):
        if live and (rng.random() < 0.45 or a.reserved > a.capacity - 3):
            lane = rng.choice(list(live))
            pages, reserved = live.pop(lane)
            a.free(pages)
            a.release(reserved)
        else:
            want = int(rng.integers(1, 4))
            if a.try_reserve(want):
                live[step] = (a.alloc(int(rng.integers(1, want + 1))), want)
        owned = [p for pages, _ in live.values() for p in pages]
        assert len(owned) == len(set(owned)), "page aliased to two lanes"
        assert set(owned) == set(a._refs)
        assert not set(owned) & set(a._free)
        assert len(owned) + a.free_pages == a.capacity
    for pages, reserved in live.values():
        a.free(pages)
        a.release(reserved)
    assert a.free_pages == a.capacity and a.reserved == 0


def test_allocator_refcount_share_and_free_to_zero():
    """ISSUE 14: share() bumps per-page refcounts on behalf of a second
    holder; free() decrements, and the page returns to the free list only
    at zero — the CoW prefix rule."""
    a = PageAllocator(num_pages=9, page_size=4)
    assert a.try_reserve(4)
    pages = a.alloc(2, holder="lane[0]")
    a.share(pages, holder="lane[1]")
    a.share(pages[:1], holder="prefix-cache")
    assert a.refcount(pages[0]) == 3 and a.refcount(pages[1]) == 2
    assert a.shared_pages == 2
    assert a.stats()["shared"] == 2
    free_before = a.free_pages
    a.free(pages, holder="lane[0]")
    assert a.free_pages == free_before  # still held: nothing recycled
    a.free(pages, holder="lane[1]")
    assert a.free_pages == free_before + 1  # pages[1] hit zero
    assert a.refcount(pages[0]) == 1
    assert a.holders(pages[0]) == ["prefix-cache"]
    a.free(pages[:1], holder="prefix-cache")
    assert a.free_pages == free_before + 2
    a.release(4)


def test_allocator_error_paths_name_page_and_holder():
    """Double-free and foreign-free raise with the offending page id and
    the holder(s) involved — the diagnosable half of the no-aliasing
    invariant."""
    a = PageAllocator(num_pages=6, page_size=2)
    assert a.try_reserve(2)
    pages = a.alloc(2, holder="lane[3]")
    a.free(pages, holder="lane[3]")
    with pytest.raises(RuntimeError) as e:
        a.free(pages[:1], holder="lane[3]")  # double free
    assert str(pages[0]) in str(e.value) and "lane[3]" in str(e.value)
    pages = a.alloc(1, holder="lane[1]")
    with pytest.raises(RuntimeError) as e:
        a.free(pages, holder="lane[2]")  # foreign free
    assert str(pages[0]) in str(e.value)
    assert "lane[2]" in str(e.value) and "lane[1]" in str(e.value)
    with pytest.raises(RuntimeError) as e:
        a.share([5], holder="lane[9]")  # sharing a never-allocated page
    assert "5" in str(e.value) and "lane[9]" in str(e.value)
    with pytest.raises(RuntimeError):
        a.share([0], holder="lane[0]")  # the null page is never shareable


def test_allocator_reclaim_hook_fires_when_free_list_short():
    calls = []
    a = PageAllocator(num_pages=5, page_size=2)  # capacity 4
    held = a.alloc(4, holder="x")

    def reclaim(n):
        calls.append(n)
        a.free(held[:n], holder="x")
        del held[:n]
        return n

    a.set_reclaim_hook(reclaim)
    got = a.alloc(2, holder="y")
    assert calls == [2] and len(got) == 2


# -- adjacency is preferred and never required (ISSUE 50) --------------------


def test_alloc_continues_the_holders_run_when_the_next_page_is_free():
    a = PageAllocator(num_pages=65, page_size=8, stretch=16)
    first = a.alloc(3, holder="lane[0]")
    assert first == [1, 2, 3]  # a fresh run: one page after another
    other = a.alloc(1, holder="lane[1]")  # starts where it can grow: not at 4
    assert other[0] > 4 and (other[0] - 1) % 16 == 0
    assert a.alloc(2, holder="lane[0]", after=first[-1]) == [4, 5]
    # the page after lane[1]'s is taken: lane[1] cannot continue, and falls back
    blocker = a.alloc(1, holder="lane[2]", after=other[0])
    assert blocker == [other[0] + 1]
    hop = a.alloc(1, holder="lane[1]", after=other[0])
    assert hop[0] not in (other[0] + 1, 0) and a.refcount(hop[0]) == 1
    s = a.stats()
    # handed out next to the holder's last: 2 and 3, 4 and 5, the blocker
    assert (s["allocated_total"], s["adjacent"]) == (8, 5)
    # a rewound tail goes back to the free list and is taken again in order
    pages = first + [4, 5]
    assert rewind_pages(a, pages, 2, holder="lane[0]") == 3 and pages == [1, 2]
    assert a.alloc(3, holder="lane[0]", after=pages[-1]) == [3, 4, 5]


def test_a_fresh_run_starts_at_a_wholly_free_stretch():
    """Stretches of ``stretch`` pages (the decode kernels' block): a run
    starts at the first page of the lowest one that is wholly free, and
    grows where it stands; without stretches (the default) a page comes
    off the free list as it always did, the last one freed first."""
    a = PageAllocator(num_pages=70, page_size=8, stretch=16)
    starts = [a.alloc(1, holder=f"lane[{i}]")[0] for i in range(4)]
    assert starts == [1, 17, 33, 49]
    for i, p in enumerate(starts):
        assert a.alloc(2, holder=f"lane[{i}]", after=p) == [p + 1, p + 2]
    assert a.alloc(1, holder="lane[4]") == [65]  # the last, short stretch is whole too
    a.free([18, 19], holder="lane[1]")
    assert a.alloc(1, holder="lane[5]") == [19]  # none whole: the last page freed
    a.free([17, 19])
    assert a.alloc(1, holder="lane[5]") == [17]  # whole again
    plain = PageAllocator(num_pages=70, page_size=8)
    got = plain.alloc(5)
    plain.free([got[1], got[3]])
    assert got == [1, 2, 3, 4, 5] and plain.alloc(3) == [4, 2, 6]


@pytest.mark.parametrize("stretch", [1, 16])
def test_every_free_page_is_handed_out_before_alloc_raises(stretch):
    """Whatever the hints and however fragmented the pool: ``alloc`` ends
    with an empty free list, never with a refusal while a page is free."""
    rng = np.random.default_rng(50)
    a = PageAllocator(num_pages=100, page_size=8, stretch=stretch)
    held = a.alloc(99)
    rng.shuffle(held)
    back, held = held[:60], held[60:]
    a.free(back)
    got = []
    while a.free_pages:
        hint = int(rng.integers(1, 100)) if rng.random() < 0.7 else None
        got += a.alloc(min(int(rng.integers(1, 4)), a.free_pages), after=hint)
        assert len(set(got)) == len(got) and not set(got) & set(held)
    assert sorted(got) == sorted(back) and a.allocated_pages == 99
    with pytest.raises(RuntimeError, match="reservation accounting broken"):
        a.alloc(1, after=got[-1])
    a.free(got + held)
    assert a.free_pages == a.capacity
    one, two = a.alloc(2)
    assert two == one + 1  # whole again: a page and the one after it


def test_the_reclaim_hook_is_asked_only_on_a_shortfall():
    """No stretch wholly free is no shortfall: a run starts at the last
    page freed and the cache keeps its pages; the hook hears of the pages
    that are missing, as before, and of nothing else."""
    a = PageAllocator(num_pages=33, page_size=8, stretch=16)
    cached = a.alloc(32, holder="prefix-cache")
    a.free(cached[3:5] + cached[20:22], holder="prefix-cache")  # 4, 5, 21, 22 free
    del cached[20:22], cached[3:5]
    calls = []

    def reclaim(n):
        calls.append(n)
        a.free([cached.pop() for _ in range(n)], holder="prefix-cache")
        return n

    a.set_reclaim_hook(reclaim)
    assert a.alloc(1, holder="lane[0]") == [22] and calls == []
    assert a.alloc(2, holder="lane[0]", after=22) == [21, 5] and calls == []
    assert a.alloc(3, holder="lane[1]") == [31, 32, 4] and calls == [2]
    assert a.free_pages == 0


# ---------------------------------------------------------------------------
# page-cursor rewind (ISSUE 16): the speculative-decode rollback primitive


def test_rewind_pages_truncates_tail_and_keeps_cow_prefix_untouched():
    """A lane pre-extended for the draft horizon rewinds to its
    post-verify cursor: tail pages free (refcount decrement), the kept
    prefix — including pages CoW-shared with a sibling lane — is never
    touched."""
    a = PageAllocator(num_pages=17, page_size=4)
    assert a.try_reserve(8)
    shared = a.alloc(2, holder="lane[0]")
    a.share(shared, holder="lane[1]")  # sibling group lane's prefix hold
    tail = a.alloc(3, holder="lane[0]")
    pages = shared + tail
    free_before = a.free_pages
    # cursor landed at 11 tokens -> ceil(11/4) = 3 pages kept
    n = rewind_pages(a, pages, a.pages_for_tokens(11), holder="lane[0]")
    assert n == 2
    assert pages == shared + tail[:1]  # truncated IN PLACE
    assert a.free_pages == free_before + 2
    for p in shared:  # CoW prefix refcounts untouched by the rewind
        assert a.refcount(p) == 2
        assert sorted(a.holders(p)) == ["lane[0]", "lane[1]"]
    with pytest.raises(ValueError):
        rewind_pages(a, pages, -1)
    assert rewind_pages(a, pages, len(pages)) == 0  # nothing past keep


def test_rewind_tail_page_shared_with_prefix_cache_stays_live():
    """Rewinding a tail page the prefix cache still holds drops only the
    lane's ref: the page stays allocated for the cache — rollback is
    refcount bookkeeping, never a recycle of live data."""
    a = PageAllocator(num_pages=9, page_size=4)
    assert a.try_reserve(4)
    pages = a.alloc(3, holder="lane[2]")
    cached = pages[-1]
    a.share([cached], holder="prefix-cache")
    free_before = a.free_pages
    assert rewind_pages(a, pages, 1, holder="lane[2]") == 2
    # pages[1] hit zero refs and recycled; the cached page did not
    assert a.free_pages == free_before + 1
    assert a.refcount(cached) == 1
    assert a.holders(cached) == ["prefix-cache"]
    a.free([cached], holder="prefix-cache")
    assert a.free_pages == free_before + 2


def test_rewind_randomized_schedule_allocator_invariant():
    """Randomized admit / draft-extend / rewind / finish churn: at every
    step the free list and the live holds partition the pool
    (free + held == capacity) and no page is aliased across lanes."""
    rng = np.random.default_rng(1)
    a = PageAllocator(num_pages=23, page_size=4)
    lanes = {}
    for step in range(400):
        r = rng.random()
        if lanes and (r < 0.25 or a.free_pages < 4):
            lane = int(rng.choice(list(lanes)))
            pages = lanes.pop(lane)
            a.free(pages, holder=f"lane[{lane}]")
        elif lanes and r < 0.6:
            # one speculative cycle: pre-extend for the draft horizon,
            # verify accepts a shorter run, rewind to the new cursor
            lane = int(rng.choice(list(lanes)))
            pages = lanes[lane]
            grow = min(int(rng.integers(1, 4)), a.free_pages)
            if grow:
                pages.extend(a.alloc(grow, holder=f"lane[{lane}]"))
            keep = int(rng.integers(1, len(pages) + 1))
            n = rewind_pages(a, pages, keep, holder=f"lane[{lane}]")
            assert len(pages) == keep and n >= 0
        elif a.free_pages >= 2:
            want = min(int(rng.integers(1, 3)), a.free_pages)
            lanes[step] = a.alloc(want, holder=f"lane[{step}]")
        held = [p for pages in lanes.values() for p in pages]
        assert len(held) == len(set(held)), "page aliased to two lanes"
        assert len(held) + a.free_pages == a.capacity
        assert not set(held) & set(a._free)
    for lane, pages in lanes.items():
        a.free(pages, holder=f"lane[{lane}]")
    assert a.free_pages == a.capacity


# ---------------------------------------------------------------------------
# prefix cache (jax-free; ISSUE 14)


def _cache(num_pages=33, ps=4):
    from scalerl_tpu.genrl.prefix_cache import PrefixCache

    a = PageAllocator(num_pages=num_pages, page_size=ps)
    return a, PrefixCache(a, ps)


def test_prefix_cache_lookup_longest_full_page_chain():
    a, c = _cache()
    prompt = np.arange(1, 14, dtype=np.int32)  # 13 tokens, ps=4
    pages = a.alloc(3, holder="lane[0]")  # 3 full pages (12 tokens)
    assert c.insert(prompt, 13, pages) == 3
    assert a.refcount(pages[0]) == 2  # cache holds its own ref
    # full prefix hit, capped at prompt_len - 1 so a tail always remains
    assert c.lookup(prompt, 12) == pages
    assert c.lookup(prompt, 11) == pages[:2]  # 11 tokens -> 2 full blocks
    # a different third block diverges after two pages
    other = prompt.copy()
    other[9] = 99
    assert c.lookup(other, 12) == pages[:2]
    # nothing cached for a cold prompt, and sub-page prompts never match
    assert c.lookup(np.asarray([7, 7, 7], np.int32), 2) == []
    assert c.hits >= 2 and c.misses >= 1


def test_prefix_cache_lru_evicts_only_refcount_free_leaves():
    a, c = _cache(num_pages=9)
    p1 = np.arange(1, 9, dtype=np.int32)  # 8 tokens = 2 pages
    pages1 = a.alloc(2, holder="lane[0]")
    c.insert(p1, 8, pages1)
    p2 = np.asarray([9, 9, 9, 9, 8, 8, 8, 8], np.int32)
    pages2 = a.alloc(2, holder="lane[1]")
    c.insert(p2, 8, pages2)
    # lane[1] still maps chain 2; lane[0] released chain 1's lane refs
    a.free(pages1, holder="lane[0]")
    assert c.cached_pages == 4
    # evict 1: the LRU evictable LEAF is chain 1's tail (cache-only)
    assert c.evict(1) == 1
    assert a.refcount(pages1[1]) == 0
    assert c.lookup(p1, 8) == pages1[:1]  # head of chain 1 still cached
    # chain 2's pages are pinned by lane[1]: nothing more to evict after
    # chain 1 is gone
    assert c.evict(10) == 1  # only chain 1's head was still evictable
    assert c.lookup(p2, 8) == pages2  # untouched
    a.free(pages2, holder="lane[1]")


def test_prefix_cache_flush_releases_cache_refs_only():
    a, c = _cache()
    prompt = np.arange(1, 9, dtype=np.int32)
    pages = a.alloc(2, holder="lane[0]")
    c.insert(prompt, 8, pages)
    assert a.refcount(pages[0]) == 2
    dropped = c.flush()
    assert dropped == 2 and c.cached_pages == 0
    # the live lane's refs survive the flush
    assert a.refcount(pages[0]) == 1
    assert c.lookup(prompt, 8) == []
    a.free(pages, holder="lane[0]")
    assert a.free_pages == a.capacity


def test_paged_reference_shared_table_layouts():
    """The parity oracle's shared-layout cases (ISSUE 14): the SAME
    physical pages appearing in several lanes' tables (a CoW-forked
    group) attend identically to a private-copy layout — in the XLA
    reference AND the Pallas kernel."""
    rng = np.random.default_rng(6)
    kp, vp = _pools(rng)
    B = 3
    q = jnp.asarray(rng.normal(size=(B, 1, 2, 8)), jnp.float32)
    # lanes 0..2 share prefix pages (1, 2); private tails 4 / 5 / 6
    shared = jnp.asarray([[1, 2, 4], [1, 2, 5], [1, 2, 6]], jnp.int32)
    ln = jnp.asarray([10, 11, 9], jnp.int32)
    ref = paged_attention_reference(q, kp, vp, shared, ln)
    # private-copy twin: prefix content duplicated into pages (7, 8) for
    # lane 1 — same logical context, different physical layout
    kp2 = kp.at[7].set(kp[1]).at[8].set(kp[2])
    vp2 = vp.at[7].set(vp[1]).at[8].set(vp[2])
    private = jnp.asarray([[1, 2, 4], [7, 8, 5], [1, 2, 6]], jnp.int32)
    ref2 = paged_attention_reference(q, kp2, vp2, private, ln)
    np.testing.assert_allclose(np.asarray(ref2), np.asarray(ref), atol=1e-6)
    ker = paged_decode_attention(q, kp, vp, shared, ln, interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), atol=1e-5)


# ---------------------------------------------------------------------------
# paged decode attention: Pallas kernel vs XLA gather reference


def _pools(rng, N=9, ps=4, H=2, D=8):
    k = jnp.asarray(rng.normal(size=(N, ps, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(N, ps, H, D)), jnp.float32)
    return k, v


@pytest.mark.parametrize(
    "table,lengths",
    [
        # contiguous layout, full pages
        ([[1, 2, 3], [4, 5, 6]], [12, 8]),
        # fragmented layout (pages out of order across the pool)
        ([[7, 1, 5], [3, 8, 2]], [12, 12]),
        # partially-filled last page + junk tail entries (null page 0)
        ([[5, 3, 0], [6, 0, 0]], [7, 2]),
    ],
)
def test_paged_kernel_matches_reference_across_layouts(table, lengths):
    rng = np.random.default_rng(3)
    kp, vp = _pools(rng)
    B = len(table)
    q = jnp.asarray(rng.normal(size=(B, 1, 2, 8)), jnp.float32)
    t = jnp.asarray(table, jnp.int32)
    ln = jnp.asarray(lengths, jnp.int32)
    ref = paged_attention_reference(q, kp, vp, t, ln)
    ker = paged_decode_attention(q, kp, vp, t, ln, interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("scratch", ["plain", "nan"])
@pytest.mark.parametrize(
    "W,VW,block,M,lengths",
    [
        # the published row (576 -> 640 columns, 2.5 KiB): a block of 128 tokens
        # ends inside a page, on a page, inside the second block
        (576, 512, 16, 32, [1, 7, 8, 130, 160]),
        # on and around the 128-token block's boundary
        (576, 512, 16, 32, [128, 129, 1, 256, 255]),
        # a narrow row (48 -> 128 columns, 512 B): a block of 512 tokens; on
        # and around its first and second boundary, the table no multiple of it
        (48, 32, 64, 136, [511, 512, 513, 1023, 1025]),
    ],
    ids=["inside_blocks", "around_128", "around_512"],
)
def test_latent_kernel_matches_reference(W, VW, block, M, lengths, scratch):
    """``paged_decode_latent`` (the absorbed MLA decode over ONE pool of
    shared rows, ISSUE 30) in interpret mode against its XLA gather twin:
    fragmented tables whose slots past the length name other lanes' pages,
    lengths that end inside a block and on and around its boundary, at the
    block the rule gives the row (asserted: ISSUE 53), and, under ``nan``,
    the TPU interpreter with every scratch buffer filled with NaN, so that
    a position the kernel neither fetched nor zeroed would poison the
    result.  The pool's pad columns (to whole tiles of 128 lanes) hold
    junk: the query is zero there."""
    from jax.experimental.pallas import tpu as pltpu

    from scalerl_tpu.ops.pallas_paged_attention import (
        latent_pool_width,
        paged_decode_latent,
        paged_latent_attention_reference,
    )

    rng = np.random.default_rng(8)
    B, H, ps = len(lengths), 4, 8
    N = B * M // 2 + 8
    assert pages_per_block(ps, latent_pool_width(W), 4) == block
    q = jnp.asarray(rng.normal(size=(B, 1, H, W)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(N, ps, latent_pool_width(W))), jnp.float32)
    table = jnp.asarray(rng.permutation(np.arange(1, N))[: B * M // 2].reshape(B, M // 2))
    table = jnp.concatenate([table, table[::-1]], axis=1).astype(jnp.int32)
    ln = jnp.asarray(lengths, jnp.int32)
    # the reference to its end before the interpreter's host callbacks start
    ref = jax.block_until_ready(paged_latent_attention_reference(q, pool, table, ln, VW, 0.2))
    interpret = pltpu.InterpretParams() if scratch == "nan" else True
    out = paged_decode_latent(q, pool, table, ln, VW, 0.2, interpret=interpret)
    assert out.shape == (B, 1, H, VW) and bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    with pytest.raises(ValueError, match=r"whole\s+tiles"):
        paged_decode_latent(q, pool[:, :, :W], table, ln, VW, 0.2, interpret=True)


def test_paged_reference_fragmentation_independence():
    """The same logical context through two different physical page
    layouts produces identical attention output — content addressing is
    entirely through the table."""
    rng = np.random.default_rng(4)
    kp, vp = _pools(rng)
    q = jnp.asarray(rng.normal(size=(1, 1, 2, 8)), jnp.float32)
    # layout A: logical tokens in pages (1, 2); layout B: same content
    # copied into pages (6, 3)
    kp2 = kp.at[6].set(kp[1]).at[3].set(kp[2])
    vp2 = vp.at[6].set(vp[1]).at[3].set(vp[2])
    ln = jnp.asarray([6], jnp.int32)
    a = paged_attention_reference(q, kp, vp, jnp.asarray([[1, 2]]), ln)
    b = paged_attention_reference(q, kp2, vp2, jnp.asarray([[6, 3]]), ln)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    ka = paged_decode_attention(
        q, kp2, vp2, jnp.asarray([[6, 3]]), ln, interpret=True
    )
    np.testing.assert_allclose(np.asarray(ka), np.asarray(a), atol=1e-5)


def test_paged_kernel_grad_free_by_construction():
    """Decode attention is inference-only: no vjp is registered, so
    differentiating through it raises instead of silently returning a
    wrong gradient (the learner recomputes logits densely)."""
    rng = np.random.default_rng(5)
    kp, vp = _pools(rng)
    q = jnp.asarray(rng.normal(size=(1, 1, 2, 8)), jnp.float32)
    t = jnp.asarray([[1, 2]], jnp.int32)
    ln = jnp.asarray([5], jnp.int32)

    def loss(q):
        return paged_decode_attention(q, kp, vp, t, ln, interpret=True).sum()

    with pytest.raises(Exception):
        jax.grad(loss)(q)


# the lane-dense pool (ISSUE 24): [N, ps, H*D], the engine's stored form.
# H*D a multiple of 128 (two heads of 64: one whole vreg row) and not (two
# heads of 8: the block spans the axis), page sizes 8 and 16

_DENSE_GEOMETRIES = [(2, 64), (2, 8)]
_DENSE_LAYOUTS = {
    # lane tables over a 9-page pool, and the lanes' lengths in pages:
    # the test scales them by the page size (minus a ragged tail)
    "contiguous": ([[1, 2, 3], [4, 5, 6]], [3.0, 2.0]),
    "fragmented": ([[7, 1, 5], [3, 8, 2]], [3.0, 3.0]),
    # a CoW-forked group: prefix pages (1, 2) in every lane's table
    "cow_shared": ([[1, 2, 4], [1, 2, 5], [1, 2, 6]], [2.5, 2.75, 2.25]),
    # partially-filled last page + junk tail entries (null page 0)
    "partial_last_page": ([[5, 3, 0], [6, 0, 0]], [1.75, 0.25]),
}


def _dense_pools(rng, ps, H, D, N=9):
    k = jnp.asarray(rng.normal(size=(N, ps, H * D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(N, ps, H * D)), jnp.float32)
    return k, v


def _dense_case(layout, H, D, ps, seed=24):
    rng = np.random.default_rng(seed)
    kp, vp = _dense_pools(rng, ps, H, D)
    table, pages = _DENSE_LAYOUTS[layout]
    q = jnp.asarray(rng.normal(size=(len(table), 1, H, D)), jnp.float32)
    t = jnp.asarray(table, jnp.int32)
    ln = jnp.asarray([max(1, int(n * ps)) for n in pages], jnp.int32)
    return q, kp, vp, t, ln


@pytest.mark.parametrize("ps", [8, 16])
@pytest.mark.parametrize("H,D", _DENSE_GEOMETRIES)
@pytest.mark.parametrize("layout", sorted(_DENSE_LAYOUTS))
def test_dense_pool_kernel_matches_reference(layout, H, D, ps):
    """The kernel on dense ``[ps, H*D]`` pages (all heads' scores from one
    product against the block-diagonal query, each head's output read from
    its own segment) against the gather reference, and the reference
    against a plain per-lane softmax over the lane's own tokens."""
    q, kp, vp, t, ln = _dense_case(layout, H, D, ps)
    ref = paged_attention_reference(q, kp, vp, t, ln)
    ker = paged_decode_attention(q, kp, vp, t, ln, interpret=True)
    assert ker.shape == q.shape
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), atol=1e-5)
    for b in range(q.shape[0]):
        n = int(ln[b])
        rows = np.concatenate([np.asarray(kp)[i] for i in np.asarray(t)[b]])
        vals = np.concatenate([np.asarray(vp)[i] for i in np.asarray(t)[b]])
        k_b = rows[:n].reshape(n, H, D)
        v_b = vals[:n].reshape(n, H, D)
        sc = np.einsum("hd,shd->hs", np.asarray(q)[b, 0], k_b) / np.sqrt(D)
        pr = np.exp(sc - sc.max(-1, keepdims=True))
        pr /= pr.sum(-1, keepdims=True)
        np.testing.assert_allclose(
            np.asarray(ref)[b, 0], np.einsum("hs,shd->hd", pr, v_b), atol=1e-5
        )


@pytest.mark.parametrize("ps", [8, 16])
@pytest.mark.parametrize("H,D", _DENSE_GEOMETRIES)
def test_four_d_entry_is_the_dense_one(H, D, ps):
    """``[N, ps, H, D]`` pools (what tests and the benchmark's kernel
    compile hold) enter through a reshape onto the same dense kernel and
    reference: the same bits."""
    q, kp, vp, t, ln = _dense_case("fragmented", H, D, ps)
    kp4 = kp.reshape(kp.shape[0], ps, H, D)
    vp4 = vp.reshape(vp.shape[0], ps, H, D)
    np.testing.assert_array_equal(
        np.asarray(paged_decode_attention(q, kp4, vp4, t, ln, interpret=True)),
        np.asarray(paged_decode_attention(q, kp, vp, t, ln, interpret=True)),
    )
    np.testing.assert_array_equal(
        np.asarray(paged_attention_reference(q, kp4, vp4, t, ln)),
        np.asarray(paged_attention_reference(q, kp, vp, t, ln)),
    )


# the walk over a lane's live pages, a block of P pages at a step (ISSUE
# 26).  A block follows from the row's bytes (ISSUE 53), so each case names
# the rows it is run at and the pages its block then holds: the test
# asserts that block, so a later rule cannot leave the lengths below on no
# boundary without failing here.

# rows whose block is 128 tokens, 16 pages of 8 and 8 of 16: eight heads of
# 128 (4 KiB, a whole number of 128-lane tiles) and nine of 72 (2,592 B,
# not one: the block spans the axis)
_WIDE_ROWS = [(8, 128), (9, 72)]
# ... and 512 tokens, 64 pages of 8 and 32 of 16: two heads of 128 (1 KiB,
# zaya's and nemotron's) and two of 8 (64 B: no row gets a longer block)
_NARROW_ROWS = [(2, 128), (2, 8)]

_WALK_CASES = {
    # name: (rows, pages a block P, page size, table slots M, the lanes' lengths)
    # k*P*ps - 1, k*P*ps, k*P*ps + 1 for k = 1, 2; M is no multiple of P
    "around_block_boundaries": (_WIDE_ROWS, 16, 8, 40, [127, 128, 129, 255, 256, 257]),
    "one_token_and_the_full_width": (_WIDE_ROWS, 16, 8, 40, [1, 320, 1, 319, 313]),
    "table_narrower_than_a_block": (_WIDE_ROWS, 16, 8, 5, [1, 40, 17, 33]),  # M < P
    "one_long_lane_among_dead_ones": (_WIDE_ROWS, 16, 8, 40, [1, 1, 300, 1, 1]),
    "pages_of_16": (_WIDE_ROWS, 8, 16, 20, [127, 128, 129, 320, 1, 16, 17]),
    "long_block_boundaries": (_NARROW_ROWS, 64, 8, 136, [511, 512, 513, 1023, 1025]),
    "long_block_one_token_and_the_full_width": (_NARROW_ROWS[:1], 64, 8, 136, [1, 1088, 1, 1087, 1081]),
    "long_block_table_narrower": (_NARROW_ROWS[:1], 64, 8, 40, [1, 320, 129, 257]),  # M < P
    "long_block_one_long_lane_among_dead_ones": (_NARROW_ROWS[:1], 64, 8, 136, [1, 1, 1000, 1, 1]),
    "long_block_pages_of_16": (_NARROW_ROWS, 32, 16, 72, [511, 512, 513, 1025, 1, 16, 17]),
}


@pytest.mark.parametrize(
    "case,H,D",
    [(case, H, D) for case in sorted(_WALK_CASES) for H, D in _WALK_CASES[case][0]],
)
def test_kernel_walks_only_the_live_pages(case, H, D):
    """Lengths on and around the block's boundaries, tables that are no
    multiple of a block or narrower than one, dead lanes beside a long one,
    at a block of 128 tokens and at one of 512: the kernel against the
    gather reference, and against itself on a table whose slots past each
    lane's length hold other pages' ids where the first holds the null
    page.  Neither may show in the result.  The long blocks run in the TPU
    interpreter with every scratch buffer NaN: a position of a block's
    tail that the kernel neither fetched nor zeroed would show."""
    from jax.experimental.pallas import tpu as pltpu

    _, P, ps, M, lengths = _WALK_CASES[case]
    assert pages_per_block(ps, H * D, 4) == P
    B = len(lengths)
    N = B * M + 1
    rng = np.random.default_rng(26)
    kp, vp = _dense_pools(rng, ps, H, D, N=N)
    q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
    ln = jnp.asarray(lengths, jnp.int32)
    junk = rng.permutation(np.arange(1, N)).reshape(B, M)
    live = np.arange(M)[None, :] * ps < np.asarray(lengths)[:, None]
    tables = [jnp.asarray(np.where(live, junk, fill), jnp.int32) for fill in (0, junk)]
    # the reference first and to its end: the interpreter computes in host
    # callbacks, which deadlock against work dispatched beside them
    ref = jax.block_until_ready(paged_attention_reference(q, kp, vp, tables[0], ln))
    interpret = pltpu.InterpretParams() if P * ps > 128 else True
    ker = [
        np.asarray(paged_decode_attention(q, kp, vp, t, ln, interpret=interpret)) for t in tables
    ]
    assert np.all(np.isfinite(ker[0]))
    np.testing.assert_allclose(ker[0], np.asarray(ref), atol=1e-5)
    np.testing.assert_array_equal(ker[1], ker[0])


@pytest.mark.parametrize(
    "ps,width,itemsize,pages",
    [
        (8, 16 * 64, 4, 16),  # gpt2m_group_rollout: 4 KiB a token, 512 KiB a block of a pool
        (8, 16 * 128, 4, 16),  # olmoe_group_rollout: 8 KiB; 64 tokens would fill it, held at 128
        (8, 640, 4, 16),  # longcat / xing4: the latent row, 2.5 KiB; 204 tokens, whole steps of 128
        (8, 2 * 256, 4, 32),  # qwen3next_group_rollout: 2 KiB, 256 tokens
        (8, 2 * 128, 4, 64),  # zaya / nemotron_group_rollout: 1 KiB, 512 tokens
        (16, 4 * 128, 4, 16),
        (8, 8 * 32, 2, 64),  # the chip_smoke shape on bfloat16 pools: no block longer than 512 tokens
        (4, 2 * 8, 4, 128),  # this file's tiny pools: more pages than a table has
        (8, 128 * 128, 4, 4),  # a row so wide that the budget cuts the block
        (256, 1024, 4, 1),  # never under one page
    ],
)
def test_pages_per_block_from_shapes(ps, width, itemsize, pages):
    """The block is a function of the pool row's bytes alone: the whole
    steps of 128 tokens that fill 512 KiB of a pool, at least one and at
    most four, where the K and V blocks, two buffers each, fit the VMEM
    budget."""
    P = pages_per_block(ps, width, itemsize)
    assert P == pages
    scratch = 4 * P * ps * width * itemsize
    assert scratch <= _VMEM_BUDGET or P == 1
    if scratch < _VMEM_BUDGET // 2:
        assert P * ps >= 128 and P * ps % 128 == 0
        assert P * ps == 128 or P * ps * width * itemsize <= 512 * 2**10


@pytest.mark.parametrize(
    "ps,width,pool_pages,pages",
    [
        (8, 16 * 64, 513, 16),  # a block of 16 pages is one copy
        (8, 2 * 128, 1025, 16),  # zaya, nemotron: a block of 64 pages holds four
        (8, 2 * 256, 1025, 16),  # qwen3next: two
        (8, 2 * 128, 10, 4),  # never more than the pool a copy reads from
        (256, 1024, 2049, 1),
    ],
)
def test_the_allocators_stretch_is_one_copy_not_one_block(ps, width, pool_pages, pages):
    """A fresh run needs a free stretch of the walk's largest COPY (ISSUE
    53): where a narrow pool's block grows to 32 or 64 pages, the engine
    hands its allocator the 16 pages it handed it before."""
    assert largest_copy(ps, width, 4, pool_pages) == pages
    if (width, pool_pages) != (2 * 128, 1025):
        return  # the rule alone; the engine over the one pool whose block is four copies
    from scalerl_tpu.genrl.continuous import ContinuousConfig, ContinuousEngine

    m = TransformerPolicy(
        num_actions=11, vocab_size=11, d_model=width, num_heads=2, num_layers=1, max_len=16,
    )
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32))
    eng = ContinuousEngine(
        m, params,
        ContinuousConfig(
            vocab_size=11, max_prompt_len=8, max_new_tokens=8, lanes=2, page_size=ps,
            num_pages=pool_pages,
        ),
    )
    assert eng.allocator.stretch == pages
    assert eng.stats()["block_tokens"] == pages_per_block(ps, width, 4) * ps


def test_resolve_paged_attn(monkeypatch):
    assert resolve_paged_attn("xla") == "xla"
    assert resolve_paged_attn("pallas") == "pallas"
    assert resolve_paged_attn("auto") == "xla"  # CPU backend
    monkeypatch.setenv("SCALERL_PAGED_ATTN", "pallas")
    assert resolve_paged_attn("auto") == "pallas"
    with pytest.raises(ValueError):
        resolve_paged_attn("vectorize")


# ---------------------------------------------------------------------------
# transformer paged paths vs the dense oracle (same params on every path)


@pytest.mark.slow
def test_paged_prefill_and_decode_match_dense_forward():
    """Paged prefill (compact right-padded prompts, K/V scattered into
    pages) + paged single-token decode steps reproduce the dense masked
    forward's logits at 1e-5 — through a FRAGMENTED page table."""
    V, P, R = 11, 4, 3
    ps = 2
    m = TransformerPolicy(
        num_actions=V, vocab_size=V, d_model=16, num_heads=2,
        num_layers=2, max_len=P + R,
    )
    B = 2
    lengths = np.array([4, 2], np.int32)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, V, size=(B, P + R)), jnp.int32)
    params = m.init(jax.random.PRNGKey(0), toks[:, :2])

    # dense oracle over the left-padded layout
    S = P + R
    left = np.zeros((B, S), np.int32)
    for b in range(B):
        n = lengths[b]
        left[b, P - n : P] = np.asarray(toks)[b, :n]
        left[b, P:] = np.asarray(toks)[b, P:]
    from scalerl_tpu.models.transformer import sequence_positions

    lens_j = jnp.asarray(lengths)
    full = m.apply(
        params, jnp.asarray(left),
        positions=sequence_positions(lens_j, P, S),
        attn_mask=sequence_attention_mask(lens_j, P, S),
    )

    # paged path: fragmented tables (lane 0 -> pages 5,2,7,1; lane 1 -> 3,6,4)
    pools = m.init_paged_cache(9, ps)  # 2 layers of 2 heads of 8
    assert isinstance(pools, ModelCache) and pools == ModelCache(k=pools.k, v=pools.v)
    table = np.zeros((B, 4), np.int32)
    table[0, :4] = [5, 2, 7, 1]
    table[1, :3] = [3, 6, 4]
    pos = np.arange(P)
    page_ids = np.zeros((B, P), np.int32)
    offsets = np.zeros((B, P), np.int32)
    for b in range(B):
        n = lengths[b]
        page_ids[b, :n] = table[b][pos[:n] // ps]
        offsets[b, :n] = pos[:n] % ps
    out, pools = m.apply(
        params, toks[:, :P],
        positions=jnp.broadcast_to(jnp.arange(P), (B, P)),
        attn_mask=prompt_attention_mask(lens_j, P),
        paged_cache=pools,
        page_ids=jnp.asarray(page_ids),
        page_offsets=jnp.asarray(offsets),
    )
    rows = np.arange(B)
    np.testing.assert_allclose(
        np.asarray(out.policy_logits)[rows, lengths - 1],
        np.asarray(full.policy_logits)[rows, P - 1],
        atol=1e-5,
    )

    # decode: feed the "response" tokens one at a time through the pages
    cl = lengths.copy()
    for t in range(R):
        tok_t = toks[:, P + t][:, None]
        pid = jnp.asarray(
            [table[b][cl[b] // ps] for b in range(B)], jnp.int32
        )[:, None]
        off = jnp.asarray(cl % ps, jnp.int32)[:, None]
        out, pools = m.apply(
            params, tok_t,
            positions=jnp.asarray(cl, jnp.int32)[:, None],
            paged_cache=pools,
            page_ids=pid,
            page_offsets=off,
            page_table=jnp.asarray(table),
            attn_lengths=jnp.asarray(cl + 1, jnp.int32),
        )
        np.testing.assert_allclose(
            np.asarray(out.policy_logits)[:, 0],
            np.asarray(full.policy_logits)[rows, P + t],
            atol=1e-5,
        )
        cl += 1


@pytest.mark.parametrize("ps", [8, 16])
@pytest.mark.parametrize("attn", ["reference", "kernel"])
def test_dense_pool_prefill_and_decode_match_dense_forward(attn, ps):
    """Paged prefill then single-token decode through the lane-dense pool
    (``[N, ps, H*D]``, rows of ``H*D`` scattered and gathered) against the
    dense masked forward, with the XLA reference and with the kernel
    behind the ``paged_attn_fn`` seam; lane 0's prompt fills a page, so
    its decode crosses onto the next page of a fragmented table."""
    from scalerl_tpu.models.transformer import sequence_positions

    V, P, R = 11, ps, 3
    fn = (
        functools.partial(paged_decode_attention, interpret=True)
        if attn == "kernel"
        else paged_attention_reference
    )
    m = TransformerPolicy(
        num_actions=V, vocab_size=V, d_model=16, num_heads=2,
        num_layers=2, max_len=P + R, paged_attn_fn=fn,
    )
    B = 2
    # lane 0 fills its prompt bucket; lane 1's context ends mid-page
    lengths = np.array([P, P - 3], np.int32)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, V, size=(B, P + R)), jnp.int32)
    params = m.init(jax.random.PRNGKey(0), toks[:, :2])
    S = P + R
    left = np.zeros((B, S), np.int32)
    for b in range(B):
        n = lengths[b]
        left[b, P - n : P] = np.asarray(toks)[b, :n]
        left[b, P:] = np.asarray(toks)[b, P:]
    lens_j = jnp.asarray(lengths)
    full = m.apply(
        params, jnp.asarray(left),
        positions=sequence_positions(lens_j, P, S),
        attn_mask=sequence_attention_mask(lens_j, P, S),
    )

    pools = m.init_paged_cache(7, ps)  # 2 layers of 2 heads of 8
    assert pools.k[0].shape == (7, ps, 16) and len(pools.k) == 2
    per_lane = -(-S // ps)
    table = np.zeros((B, per_lane), np.int32)
    table[0] = [5, 2, 6][:per_lane]
    table[1] = [3, 1, 4][:per_lane]
    pos = np.arange(P)
    page_ids = np.zeros((B, P), np.int32)
    offsets = np.zeros((B, P), np.int32)
    for b in range(B):
        n = lengths[b]
        page_ids[b, :n] = table[b][pos[:n] // ps]
        offsets[b, :n] = pos[:n] % ps
    out, pools = m.apply(
        params, toks[:, :P],
        positions=jnp.broadcast_to(jnp.arange(P), (B, P)),
        attn_mask=prompt_attention_mask(lens_j, P),
        paged_cache=pools,
        page_ids=jnp.asarray(page_ids),
        page_offsets=jnp.asarray(offsets),
    )
    assert pools.k[0].shape == (7, ps, 16)
    rows = np.arange(B)
    np.testing.assert_allclose(
        np.asarray(out.policy_logits)[rows, lengths - 1],
        np.asarray(full.policy_logits)[rows, P - 1],
        atol=1e-5,
    )

    @jax.jit
    def step(pools, tok, cl, pid, off):
        return m.apply(
            params, tok, positions=cl[:, None], paged_cache=pools,
            page_ids=pid, page_offsets=off, page_table=jnp.asarray(table),
            attn_lengths=cl + 1,
        )

    cl = lengths.copy()
    for t in range(R):
        pid = np.asarray([table[b][cl[b] // ps] for b in range(B)], np.int32)
        out, pools = step(
            pools, toks[:, P + t][:, None], jnp.asarray(cl, jnp.int32),
            jnp.asarray(pid)[:, None], jnp.asarray(cl % ps, jnp.int32)[:, None],
        )
        np.testing.assert_allclose(
            np.asarray(out.policy_logits)[:, 0],
            np.asarray(full.policy_logits)[rows, P + t],
            atol=1e-5,
        )
        cl += 1


# ---------------------------------------------------------------------------
# quantized snapshots (runtime/quantize.py)


def test_quantize_int8_round_trip_and_f32_sensitive_leaves():
    rng = np.random.default_rng(0)
    tree = {
        "kernel": jnp.asarray(rng.normal(0, 0.3, (16, 8)), jnp.float32),
        "bias": jnp.asarray(rng.normal(0, 0.3, (8,)), jnp.float32),
        "step": jnp.asarray(3, jnp.int32),
    }
    q = quantize_tree(tree, "int8")
    assert isinstance(q["kernel"], QuantizedLeaf)
    assert q["kernel"].q.dtype == jnp.int8
    # 1-D (f32-sensitive) and integer leaves pass through untouched
    assert not isinstance(q["bias"], QuantizedLeaf)
    assert not isinstance(q["step"], QuantizedLeaf)
    d = dequantize_tree(q)
    assert d["kernel"].dtype == jnp.float32
    amax = float(jnp.max(jnp.abs(tree["kernel"])))
    np.testing.assert_allclose(
        np.asarray(d["kernel"]), np.asarray(tree["kernel"]),
        atol=amax / 127.0 * 0.51 + 1e-7,
    )
    np.testing.assert_array_equal(np.asarray(d["bias"]), np.asarray(tree["bias"]))
    # the wire format is ~4x smaller for the quantized leaf
    assert tree_wire_bytes(q) < tree_wire_bytes(tree) / 2


def test_quantize_bf16_mode_and_validation():
    tree = {"w": jnp.ones((4, 4), jnp.float32) * 1.5}
    q = quantize_tree(tree, "bf16")
    assert q["w"].q.dtype == jnp.bfloat16
    d = dequantize_tree(q)
    assert d["w"].dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(d["w"]), 1.5)
    with pytest.raises(ValueError):
        quantize_tree(tree, "int4")


# -- grouped key/value heads (ISSUE 40) --------------------------------------


@pytest.mark.parametrize(
    "H,KV,D,ps",
    [(4, 2, 8, 4), (8, 2, 16, 8), (32, 2, 128, 8), (6, 3, 8, 4)],
    ids=["4over2", "8over2", "nemotron_32over2", "6over3"],
)
@pytest.mark.parametrize(
    "table,lengths",
    [
        ([[1, 2, 3], [4, 5, 6]], [3, 2]),  # whole pages (x ps below)
        ([[7, 1, 5], [3, 8, 2]], [3, 3]),  # fragmented
        ([[5, 3, 0], [6, 0, 0]], [1.75, 0.5]),  # a part-filled last page, junk tail entries
    ],
    ids=["contiguous", "fragmented", "partial"],
)
def test_grouped_heads_kernel_matches_reference(H, KV, D, ps, table, lengths):
    """Fewer key/value heads than query heads: the pools' rows hold ``KV x
    D`` values, the kernel's block-diagonal query has ``H / KV`` rows
    against each key/value head's columns, and query head ``i`` reads
    key/value head ``i // (H / KV)``: the kernel against the gather
    reference, and the reference against a plain per-lane softmax with the
    key/value heads repeated (1e-5: float32 sums in another order)."""
    rng = np.random.default_rng(H * KV + ps)
    N = 9
    kp = jnp.asarray(rng.normal(size=(N, ps, KV * D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(N, ps, KV * D)), jnp.float32)
    B = len(table)
    q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
    t = jnp.asarray(table, jnp.int32)
    ln = jnp.asarray([max(1, int(n * ps)) for n in lengths], jnp.int32)
    ref = paged_attention_reference(q, kp, vp, t, ln)
    ker = paged_decode_attention(q, kp, vp, t, ln, interpret=True)
    assert ker.shape == q.shape
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), atol=1e-5)
    for b in range(B):
        n = int(ln[b])
        rows = np.concatenate([np.asarray(kp)[i] for i in np.asarray(t)[b]])[:n].reshape(n, KV, D)
        vals = np.concatenate([np.asarray(vp)[i] for i in np.asarray(t)[b]])[:n].reshape(n, KV, D)
        k_b, v_b = np.repeat(rows, H // KV, axis=1), np.repeat(vals, H // KV, axis=1)
        sc = np.einsum("hd,shd->hs", np.asarray(q)[b, 0], k_b) / np.sqrt(D)
        pr = np.exp(sc - sc.max(-1, keepdims=True))
        pr /= pr.sum(-1, keepdims=True)
        np.testing.assert_allclose(
            np.asarray(ref)[b, 0], np.einsum("hs,shd->hd", pr, v_b), atol=1e-5
        )
    # four-dimensional pools enter through the same reshape
    np.testing.assert_array_equal(
        np.asarray(paged_decode_attention(
            q, kp.reshape(N, ps, KV, D), vp.reshape(N, ps, KV, D), t, ln, interpret=True
        )),
        np.asarray(ker),
    )


def test_grouped_heads_refuse_a_pool_that_is_no_divisor():
    q = jnp.zeros((1, 1, 4, 8))
    pool = jnp.zeros((3, 4, 3 * 8))  # three key/value heads under four query heads
    with pytest.raises(ValueError, match="whole key/value heads"):
        paged_decode_attention(
            q, pool, pool, jnp.zeros((1, 2), jnp.int32), jnp.ones((1,), jnp.int32), interpret=True
        )
