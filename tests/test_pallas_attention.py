"""Pallas flash attention vs the XLA reference (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scalerl_tpu.ops.pallas_attention import flash_attention
from scalerl_tpu.ops.ring_attention import full_attention


def _rand(key, *shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype=dtype)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("T", [16, 100])  # 100: not a block multiple -> padding
def test_flash_matches_full_attention(causal, T):
    B, H, D = 2, 2, 16
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = _rand(k1, B, T, H, D), _rand(k2, B, T, H, D), _rand(k3, B, T, H, D)
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    ref = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_cross_lengths():
    """Tq != Tk (non-causal cross attention path)."""
    B, H, D = 1, 2, 8
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand(k1, B, 24, H, D)
    k = _rand(k2, B, 56, H, D)
    v = _rand(k3, B, 56, H, D)
    out = flash_attention(q, k, v, block_q=16, block_k=16)
    ref = full_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match(causal):
    """The custom flash backward (dq / dk / dv kernels) vs autodiff through
    the reference attention."""
    B, T, H, D = 2, 48, 2, 8
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(2), 4)
    q, k, v = _rand(k1, B, T, H, D), _rand(k2, B, T, H, D), _rand(k3, B, T, H, D)
    cot = _rand(k4, B, T, H, D)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, block_q=16, block_k=16) * cot)

    def loss_ref(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=causal) * cot)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5,
            err_msg=f"d{name} mismatch",
        )


def test_flash_bfloat16_inputs():
    """bf16 q/k/v: f32 accumulation keeps the result close to the f32 ref."""
    B, T, H, D = 1, 32, 2, 16
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = _rand(k1, B, T, H, D), _rand(k2, B, T, H, D), _rand(k3, B, T, H, D)
    out = flash_attention(
        q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), v.astype(jnp.bfloat16),
        causal=True, block_q=16, block_k=16,
    )
    assert out.dtype == jnp.bfloat16
    ref = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=5e-2, rtol=5e-2
    )


@pytest.mark.slow  # ~14 s; kernel correctness stays tier-1-covered by the
# flash-vs-full fwd/grad oracles above (ISSUE 19 buy-back)
def test_flash_in_transformer_policy():
    """The kernel drops into TransformerPolicy's attn_fn seam and trains."""
    from scalerl_tpu.models.transformer import TransformerPolicy

    model = TransformerPolicy(
        num_actions=4, d_model=32, num_heads=2, num_layers=1, max_len=64,
        use_flash=True,
    )
    obs = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 8))
    params = model.init(jax.random.PRNGKey(1), obs)
    out = model.apply(params, obs)
    assert out.policy_logits.shape == (2, 40, 4)

    ref = TransformerPolicy(
        num_actions=4, d_model=32, num_heads=2, num_layers=1, max_len=64,
    )
    out_ref = ref.apply(params, obs)
    np.testing.assert_allclose(
        np.asarray(out.policy_logits), np.asarray(out_ref.policy_logits),
        atol=2e-4, rtol=2e-4,
    )

    # gradient flows through the custom vjp
    def loss(p):
        o = model.apply(p, obs)
        return jnp.mean(o.baseline ** 2) + jnp.mean(o.policy_logits ** 2)

    g = jax.grad(loss)(params)
    gnorm = sum(float(jnp.sum(x * x)) for x in jax.tree_util.tree_leaves(g))
    assert np.isfinite(gnorm) and gnorm > 0


# ---------------------------------------------------------------------------
# segment-packed flash attention (the ISSUE 15 training kernel)


def _seg_layout(B, T, spans):
    """segment ids from per-row (start, end, id) span lists."""
    seg = np.zeros((B, T), np.int32)
    for b, row in enumerate(spans):
        for s, e, i in row:
            seg[b, s:e] = i
    return jnp.asarray(seg)


def _seg_rand(seed, B, T, H, D):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (
        _rand(k1, B, T, H, D), _rand(k2, B, T, H, D), _rand(k3, B, T, H, D)
    )


# explicit score tiles (square, and wider or taller than their partner, so a
# major block holds several of one kind), and the tile chosen from the shape
_SEG_BLOCKS = [(8, 8), (8, 16), (16, 8), (None, None)]


@pytest.mark.parametrize(
    "spans",
    [
        # multi-segment rows + pad tails (cross-segment AND pad blocks)
        [[(0, 5, 1), (5, 14, 2), (14, 18, 3)], [(0, 20, 1)]],
        # one row entirely pad: every one of its blocks is skipped
        [[(0, 24, 1)], []],
        # segment boundaries straddling block boundaries (block 8)
        [[(0, 7, 1), (7, 9, 2), (9, 24, 3)], [(0, 8, 1), (8, 16, 2)]],
    ],
)
@pytest.mark.parametrize("blocks", _SEG_BLOCKS, ids=str)
def test_segment_flash_matches_reference(spans, blocks):
    from scalerl_tpu.ops.pallas_attention import (
        segment_attention_reference,
        segment_flash_attention,
    )

    B, T, H, D = 2, 24, 2, 8
    q, k, v = _seg_rand(0, B, T, H, D)
    seg = _seg_layout(B, T, spans)
    out = segment_flash_attention(q, k, v, seg, None, *blocks, None)
    ref = segment_attention_reference(q, k, v, seg)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


@pytest.mark.parametrize("blocks", _SEG_BLOCKS, ids=str)
def test_segment_flash_gradients_match_reference(blocks):
    """custom_vjp backward vs XLA autodiff through the dense oracle —
    the training-grade contract (values AND grads at 1e-5), with pad
    rows and cross-segment blocks in the layout."""
    from scalerl_tpu.ops.pallas_attention import (
        segment_attention_reference,
        segment_flash_attention,
    )

    B, T, H, D = 2, 24, 2, 8
    q, k, v = _seg_rand(1, B, T, H, D)
    seg = _seg_layout(
        B, T, [[(0, 5, 1), (5, 14, 2), (14, 18, 3)], [(0, 20, 1)]]
    )

    def loss_kernel(q, k, v):
        o = segment_flash_attention(q, k, v, seg, None, *blocks, None)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(segment_attention_reference(q, k, v, seg)))

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5
        )


def test_segment_flash_single_segment_is_causal_attention():
    """One full-length segment == plain causal attention: the packed
    kernel degrades to the existing contract when nothing is packed."""
    from scalerl_tpu.ops.pallas_attention import segment_flash_attention

    B, T, H, D = 1, 16, 2, 8
    q, k, v = _seg_rand(2, B, T, H, D)
    seg = jnp.ones((B, T), jnp.int32)
    out = segment_flash_attention(q, k, v, seg, None, 8, 8, None)
    ref = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_segment_flash_pad_rows_zero_and_ragged_tail():
    """Fully-masked (pad) query rows emit exact zeros, and a T that is
    not a block multiple pads legally (the pad tail rides id 0)."""
    from scalerl_tpu.ops.pallas_attention import segment_flash_attention

    B, T, H, D = 1, 19, 2, 8  # 19: ragged vs block 8
    q, k, v = _seg_rand(3, B, T, H, D)
    seg = np.zeros((B, T), np.int32)
    seg[0, :7] = 1
    out = np.asarray(
        segment_flash_attention(q, k, v, jnp.asarray(seg), None, 8, 8, None)
    )
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out[0, 7:], 0.0)
    assert np.abs(out[0, :7]).max() > 0


def test_segment_flash_under_jit_and_grad_of_ints():
    """jit-compatible, and jax.grad never asks for a segment-id
    cotangent (float0 handled by the vjp rule)."""
    from scalerl_tpu.ops.pallas_attention import segment_flash_attention

    B, T, H, D = 1, 16, 1, 8
    q, k, v = _seg_rand(4, B, T, H, D)
    seg = _seg_layout(B, T, [[(0, 6, 1), (6, 12, 2)]])

    @jax.jit
    def f(q, k, v):
        return jnp.sum(segment_flash_attention(q, k, v, seg) ** 2)

    g = jax.grad(f)(q, k, v)
    assert np.isfinite(np.asarray(g)).all()


def _seg_check(q, k, v, seg, blocks, out_tol, grad_tol):
    """Outputs and all three gradients against the dense oracle."""
    from scalerl_tpu.ops.pallas_attention import (
        segment_attention_reference,
        segment_flash_attention,
    )

    f32 = lambda x: np.asarray(x.astype(jnp.float32))  # noqa: E731
    out = segment_flash_attention(q, k, v, seg, None, *blocks, None)
    assert out.dtype == q.dtype
    np.testing.assert_allclose(
        f32(out), f32(segment_attention_reference(q, k, v, seg)),
        atol=out_tol, rtol=out_tol,
    )
    np.testing.assert_array_equal(f32(out)[np.asarray(seg) == 0], 0.0)

    def loss(attn):
        return lambda q, k, v: jnp.sum(jnp.sin(attn(q, k, v).astype(jnp.float32)))

    gk = jax.grad(
        loss(lambda q, k, v: segment_flash_attention(q, k, v, seg, None, *blocks, None)),
        argnums=(0, 1, 2),
    )(q, k, v)
    gr = jax.grad(
        loss(lambda q, k, v: segment_attention_reference(q, k, v, seg)),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b, name in zip(gk, gr, "qkv"):
        np.testing.assert_allclose(
            f32(a), f32(b), atol=grad_tol, rtol=grad_tol, err_msg=f"d{name}"
        )


@pytest.mark.parametrize(
    "H,D,dtype,out_tol,grad_tol",
    [
        (2, 8, jnp.float32, 2e-5, 1e-5),
        (10, 8, jnp.float32, 2e-5, 1e-5),  # a head count 4 and 8 do not divide
        (2, 128, jnp.float32, 2e-5, 1e-5),  # OLMoE's head size
        (2, 16, jnp.bfloat16, 5e-2, 5e-2),
    ],
    ids=["2x8-f32", "10-heads", "head-128", "bf16"],
)
def test_segment_flash_chosen_tile_edges(H, D, dtype, out_tol, grad_tol):
    """The tile chosen from the shape (512 rows), at a T it does not
    divide: row 0 has a segment boundary inside the first tile, a segment
    that crosses into the second, and a third tile that is all padding;
    row 1 is one full-length segment."""
    from scalerl_tpu.ops.pallas_attention import segment_flash_tiling

    B, T = 2, 1100
    tl = segment_flash_tiling(T, D, dtype)
    assert (tl.block_q, tl.block_k, tl.t_pad) == (512, 512, 1536)
    q, k, v = (x.astype(dtype) for x in _seg_rand(5, B, T, H, D))
    seg = _seg_layout(
        B, T, [[(0, 300, 1), (300, 700, 2), (700, 1000, 3)], [(0, T, 1)]]
    )
    _seg_check(q, k, v, seg, (None, None), out_tol, grad_tol)


@pytest.mark.parametrize(
    "D,Dv,dtype,blocks,out_tol,grad_tol",
    [
        (12, 8, jnp.float32, (8, 8), 2e-5, 1e-5),
        (12, 8, jnp.float32, (8, 16), 2e-5, 1e-5),
        (192, 128, jnp.float32, (None, None), 2e-5, 1e-5),  # latent attention's heads
        # bfloat16 operands and outputs: a rounding of 2^-8 on values of
        # order one, summed over a head of 24
        (24, 16, jnp.bfloat16, (None, None), 5e-2, 5e-2),
    ],
    ids=["12-8", "12-8-wide-k", "192-128", "bf16"],
)
def test_segment_flash_takes_a_v_narrower_than_q_and_k(D, Dv, dtype, blocks, out_tol, grad_tol):
    """Latent attention's head shape (ISSUE 32): q and k of one head size,
    v and the output of a smaller one, with no pad.  Outputs and all three
    gradients against the dense oracle (dv has v's width), and bit for bit
    what the same call gives with v padded to q's width and the pad
    sliced off: a column of ``p v`` never sees another."""
    from scalerl_tpu.ops.pallas_attention import segment_flash_attention

    B, T, H = 2, 40, 2
    q, k, _ = (x.astype(dtype) for x in _seg_rand(7, B, T, H, D))
    v = _seg_rand(8, B, T, H, Dv)[2].astype(dtype)
    seg = _seg_layout(B, T, [[(0, 9, 1), (9, 30, 2), (30, 36, 3)], [(0, T, 1)]])
    _seg_check(q, k, v, seg, blocks, out_tol, grad_tol)
    out = segment_flash_attention(q, k, v, seg, None, *blocks, None)
    assert out.shape == (B, T, H, Dv)
    padded = jnp.pad(v, ((0, 0),) * 3 + ((0, D - Dv),))
    wide = segment_flash_attention(q, k, padded, seg, None, *blocks, None)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(wide[..., :Dv]))


@pytest.mark.parametrize("blocks", [(8, 8), (8, 16), (16, 8)], ids=str)
def test_segment_flash_several_major_blocks(monkeypatch, blocks):
    """Rows longer than the VMEM budget holds (the planned 8k-token rows):
    the streamed operand then arrives in several major blocks, the
    accumulators live across them, and a major block outside a tile's
    live interval is neither fetched nor read.  A tiny budget stands in
    for a long row."""
    import scalerl_tpu.ops.pallas_attention as pa

    B, T, H, D = 2, 64, 2, 8
    monkeypatch.setattr(pa, "_SEG_VMEM_BUDGET", 150_000)
    tl = pa.segment_flash_tiling(T, D, jnp.float32, *blocks)
    assert tl.t_pad // tl.major > 1
    q, k, v = _seg_rand(6, B, T, H, D)
    seg = _seg_layout(
        B, T, [[(0, 20, 1), (20, 50, 2), (50, 58, 3)], [(0, T, 1)]]
    )
    _seg_check(q, k, v, seg, blocks, 2e-5, 1e-5)


@pytest.mark.parametrize(
    "T,D,dtype",
    [
        (1024, 64, jnp.float32),  # both learn cells
        (1024, 128, jnp.bfloat16),  # the OLMoE learner
        (1024, 192, jnp.bfloat16),  # joyai_packed_learn: latent attention's q and k
        (8192, 128, jnp.bfloat16),  # its planned 8k-token rows
        (8192, 128, jnp.float32),
        (384, 32, jnp.float32),
        (24, 8, jnp.float32),
        (19, 8, jnp.float32),
    ],
)
def test_segment_flash_tiling_is_legal(T, D, dtype):
    """The tile is chosen from T, D, the dtype and a VMEM budget (any head
    count is legal: a grid step holds one head): whole blocks, Mosaic's
    (8, 128) rule, and never more than the budget."""
    import scalerl_tpu.ops.pallas_attention as pa

    tl = pa.segment_flash_tiling(T, D, dtype)
    assert tl.major % tl.block_q == 0 and tl.major % tl.block_k == 0
    assert tl.t_pad % tl.major == 0 and T <= tl.t_pad < T + tl.major
    # a score tile's edge is a lane multiple unless it spans the padded row
    for block in (tl.block_q, tl.block_k):
        assert block % 128 == 0 or (block == tl.t_pad and block % 8 == 0)
    need = pa._seg_vmem_bytes(
        tl.block_q, tl.block_k, tl.major, D, jnp.dtype(dtype).itemsize
    )
    assert need <= pa._SEG_VMEM_BUDGET < pa._SEG_VMEM_LIMIT <= 96 * 2**20
    if T >= 8192:  # no whole row in VMEM
        assert tl.major < tl.t_pad
    # blocks the caller names are honoured
    named = pa.segment_flash_tiling(max(T, 256), D, dtype, 128, 128)
    assert (named.block_q, named.block_k) == (128, 128)


def _pallas_grids(jaxpr):
    """``{kernel name: grid}`` of every pallas_call under ``jaxpr``."""
    found = {}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] = tuple(eqn.params["grid_mapping"].grid)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.update(_pallas_grids(sub))
    return found


@pytest.mark.parametrize("H,most", [(16, 64), (10, 64)], ids=["gpt2m", "gpt2l-shard"])
def test_segment_flash_grid_at_the_learn_cells_shape(H, most):
    """The guard against sliding back: ISSUE 29 found 2,048 (1,280) grid
    steps of one 128x128 tile each in all three calls at these shapes."""
    from scalerl_tpu.ops.pallas_attention import segment_flash_attention

    B, T, D = 2, 1024, 64
    qkv = jax.ShapeDtypeStruct((B, T, H, D), jnp.float32)
    seg = jax.ShapeDtypeStruct((B, T), jnp.int32)

    def loss(q, k, v, seg):
        return jnp.sum(segment_flash_attention(q, k, v, seg, interpret=False))

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(qkv, qkv, qkv, seg)
    grids = _pallas_grids(jaxpr.jaxpr)
    assert set(grids) == {
        "segment_flash_fwd", "segment_flash_bwd_dq", "segment_flash_bwd_dkv"
    }
    for name, grid in grids.items():
        assert int(np.prod(grid)) <= most, (name, grid)


@pytest.mark.parametrize("tiles_are_q", [True, False])
@pytest.mark.parametrize("tile,block", [(8, 8), (16, 8), (8, 16)])
def test_segment_live_blocks_cover_exactly_the_live_pairs(tile, block, tiles_are_q):
    """The prefetched loop bounds against brute force: for the packer's
    layout a block lies inside a tile's interval exactly when some pair in
    it is unmasked; for ids in any order the interval still covers every
    live block (the masks alone decide values)."""
    from scalerl_tpu.ops.pallas_attention import _live_blocks

    rng = np.random.default_rng(0)
    T = 64
    packed = np.zeros((3, T), np.int32)
    packed[0, :20], packed[0, 20:50], packed[0, 50:58] = 1, 2, 3
    packed[1, :] = 1
    shuffled = rng.integers(0, 4, size=(3, T)).astype(np.int32)
    for seg, exact in ((packed, True), (shuffled, False)):
        lo, hi = (
            np.asarray(x).reshape(seg.shape[0], -1)
            for x in _live_blocks(jnp.asarray(seg), tile, block, tiles_are_q)
        )
        pos = np.arange(T)
        mask = (
            (seg[:, :, None] == seg[:, None, :])
            & (seg[:, :, None] > 0)
            & (pos[None, :, None] >= pos[None, None, :])
        )  # [B, q, k]
        if not tiles_are_q:
            mask = mask.transpose(0, 2, 1)  # [B, k, q]
        live = mask.reshape(seg.shape[0], T // tile, tile, T // block, block)
        live = live.any(axis=(2, 4))  # [B, tiles, blocks]
        inside = (np.arange(T // block) >= lo[..., None]) & (
            np.arange(T // block) < hi[..., None]
        )
        assert not (live & ~inside).any()
        if exact:
            np.testing.assert_array_equal(inside, live)


def test_segment_flash_tiling_is_recorded_once_a_shape(monkeypatch):
    """The mechanism always engages, so its counter is its geometry: one
    program span a traced shape, through ``runtime/tracing``."""
    from scalerl_tpu.ops.pallas_attention import segment_flash_attention
    from scalerl_tpu.runtime import tracing

    monkeypatch.setenv(tracing.ENV_SAMPLE, "1.0")
    tracing.reset()
    try:
        B, T, H, D = 1, 40, 3, 8  # a shape no other test traces
        q, k, v = _seg_rand(7, B, T, H, D)
        seg = jnp.ones((B, T), jnp.int32)
        f = jax.jit(lambda q, k, v: jnp.sum(segment_flash_attention(q, k, v, seg)))
        for _ in range(2):
            jax.grad(f)(q, k, v)
        spans = [
            s for s in tracing.get_tracer().finished()
            if s["name"] == "segment_flash.tiling"
        ]
        assert len(spans) == 1, spans
        attrs = spans[0]["attrs"]
        assert attrs["shape"] == [B, T, H, D] and attrs["dtype"] == "float32"
        assert (attrs["block_q"], attrs["block_k"], attrs["major"]) == (40, 40, 40)
        assert attrs["grid_steps"] == attrs["grid_steps_dkv"] == B * H
    finally:
        monkeypatch.delenv(tracing.ENV_SAMPLE)
        tracing.reset()


def test_resolve_segment_attn(monkeypatch):
    from scalerl_tpu.ops.pallas_attention import (
        make_segment_attn_fn,
        resolve_segment_attn,
        segment_flash_attention,
    )

    assert resolve_segment_attn("pallas") == "pallas"
    assert resolve_segment_attn("xla") == "xla"
    with pytest.raises(ValueError):
        resolve_segment_attn("mosaic")
    monkeypatch.setenv("SCALERL_SEGMENT_ATTN", "pallas")
    assert resolve_segment_attn("auto") == "pallas"
    assert make_segment_attn_fn("auto") is segment_flash_attention
    monkeypatch.delenv("SCALERL_SEGMENT_ATTN")
    # off-TPU auto resolves to the dense model path (None)
    if jax.default_backend() != "tpu":
        assert make_segment_attn_fn("auto") is None


# ---------------------------------------------------------------------------
# the paged decode kernels fetch a RUN of adjacent pool pages in one copy
# (ISSUE 50).  Adjacency is read off the table, so every table is a case.

# a block is 16 pages of a wide pool and 64 of a narrow one; the pool's last page is 199
_RUN_PS, _RUN_SLOTS, _RUN_POOL = 8, 40, 200


def _run_tables():
    """name -> (table rows, lengths): what a lane's table can look like."""
    rng = np.random.default_rng(50)
    M, N, ps = _RUN_SLOTS, _RUN_POOL, _RUN_PS
    scattered = rng.permutation(np.arange(1, N))

    def row(*pieces, slots=M):
        flat = np.concatenate([np.atleast_1d(p) for p in pieces])
        return np.concatenate([flat, np.zeros(slots - len(flat), np.int64)])[:slots]

    # a block of a narrow pool is 64 pages, 512 tokens (ISSUE 53): a lane of
    # one token, one that ends inside the block's first page, one of exactly
    # a block, one of a block and a page; a table of 72 slots is two blocks
    two, block_lengths = 72, [1, ps - 3, 64 * ps, 65 * ps]

    shared = np.arange(60, 80)  # a prompt's twenty pages: the break falls inside block 1
    return {
        "one_run": ([np.arange(1, 1 + M), np.arange(100, 100 + M)], [M * ps, 23 * ps]),
        # no two live entries adjacent: pages two apart, going down
        "fragmented": ([np.arange(N - 1, 0, -2)[:M], scattered[:M]], [M * ps, 300]),
        "shared_run_then_own_run": (
            [row(shared, np.arange(10, 30)), row(shared, np.arange(120, 140))],
            [39 * ps + 3, 33 * ps],
        ),
        # adjacent pages from slot 10 to slot 25, across the block's end at 16
        "run_across_a_block_boundary": (
            [row(scattered[:10], np.arange(140, 156), scattered[10:24])], [M * ps - 1],
        ),
        # three live pages of a run that the table goes on with past the length
        "partial_last_page": ([np.arange(30, 30 + M), row(np.arange(90, 100))], [2 * ps + 5, ps + 1]),
        # ... and where going on would leave the pool
        "run_ends_on_the_pools_last_page": (
            [row(np.arange(N - 19, N)), row(np.arange(N - 3, N), N - 1, N - 1)], [19 * ps, 3 * ps],
        ),
        "dead_lane_between_live_ones": (
            [np.arange(1, 1 + M), row(0), np.arange(150, 150 + M), row(0), row(np.arange(50, 70))],
            [200, 1, 320, 1, 160],
        ),
        "block_lengths_on_runs": (
            [row(1, slots=two), row(2, slots=two), row(np.arange(3, 67), slots=two), np.arange(100, 100 + two)],
            block_lengths,
        ),
        # pages two apart, going down (lanes may read the same pages)
        "block_lengths_on_scattered_pages": (
            [np.arange(N - 1 - k, 0, -2)[:two] for k in range(4)], block_lengths,
        ),
    }


_RUN_TABLES = _run_tables()


def _run_case(kernel, table, lengths):
    """(kernel's result, reference's result) on a seeded pool, in the TPU
    interpreter with every scratch buffer NaN and reads out of bounds
    raising: a position neither fetched nor zeroed, or a copy that leaves
    the pool, shows."""
    from jax.experimental.pallas import tpu as pltpu

    from scalerl_tpu.ops import pallas_paged_attention as ppa

    rng = np.random.default_rng(5)
    B, N, ps = len(table), _RUN_POOL, _RUN_PS
    t, ln = jnp.asarray(np.stack(table), jnp.int32), jnp.asarray(lengths, jnp.int32)
    interpret = pltpu.InterpretParams()
    if kernel == "latent":
        H, W, VW = 4, 576, 512  # the published row: 2.5 KiB, a block of 128 tokens
        assert ppa.pages_per_block(ps, ppa.latent_pool_width(W), 4) == 16
        q = jnp.asarray(rng.normal(size=(B, 1, H, W)), jnp.float32)
        pool = jnp.asarray(rng.normal(size=(N, ps, ppa.latent_pool_width(W))), jnp.float32)
        # the reference first and to its end: the interpreter computes in
        # host callbacks, which deadlock against work dispatched beside them
        ref = jax.block_until_ready(ppa.paged_latent_attention_reference(q, pool, t, ln, VW, 0.2))
        return ppa.paged_decode_latent(q, pool, t, ln, VW, 0.2, interpret=interpret), ref
    # rows of 2.5 KiB, whose block is 128 tokens as gpt2-medium's is;
    # ``narrow``: two key/value heads of 128 float32, 1 KiB, whose block is 512
    H, KV, D = {"decode": (5, 5, 128), "grouped": (10, 5, 128), "narrow": (8, 2, 128)}[kernel]
    assert ppa.pages_per_block(ps, KV * D, 4) == (64 if kernel == "narrow" else 16)
    q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(N, ps, KV * D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(N, ps, KV * D)), jnp.float32)
    ref = jax.block_until_ready(ppa.paged_attention_reference(q, kp, vp, t, ln))
    return ppa.paged_decode_attention(q, kp, vp, t, ln, interpret=interpret), ref


# the tables that say something new at a block of 64 pages: the two made for
# it, and those whose runs its longer stretch of slots cuts elsewhere
_NARROW_TABLES = {
    "block_lengths_on_runs", "block_lengths_on_scattered_pages", "one_run",
    "shared_run_then_own_run", "run_ends_on_the_pools_last_page", "dead_lane_between_live_ones",
}


@pytest.mark.parametrize(
    "case,kernel",
    [
        (case, kernel)
        for case in sorted(_RUN_TABLES)
        for kernel in ("decode", "grouped", "latent", "narrow")
        if kernel != "narrow" or case in _NARROW_TABLES
    ],
)
def test_paged_kernels_fetch_runs_of_adjacent_pages(case, kernel):
    """Both decode kernels, and grouped heads on a wide pool and on a
    narrow one (whose block is four times as long), against their gather
    references at 1e-5 on tables that are one run, no run at all, a shared
    run then an own one, a run across a block's end, a part-filled last
    page, a run that ends on the pool's last page, dead lanes between
    live ones and lanes that end at a block's marks: whatever the table,
    the same result."""
    out, ref = _run_case(kernel, *_RUN_TABLES[case])
    out = np.asarray(out)
    assert np.all(np.isfinite(out))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("width,block", [(16 * 64, 16), (2 * 128, 64)])
@pytest.mark.parametrize("case", sorted(_RUN_TABLES))
def test_host_counts_the_copies_the_kernels_rule_issues(case, width, block):
    """The list the kernels walk (``_copy_list``) read back as they read it
    (``_each_copy``: a block's entries, a size at a time up to that size's
    end): every copy fetches pages the table names at the slots it fills,
    every live slot is filled once and no other, and ``table_copies`` (the
    engine's ``pages_per_copy``) is that list's length reckoned on the
    host; no table costs more copies than it has live pages.  At a wide
    pool's block of 16 pages and a narrow pool's of 64."""
    from scalerl_tpu.ops import pallas_paged_attention as ppa

    table, lengths = _RUN_TABLES[case]
    table = np.stack(table).astype(np.int32)
    ps = _RUN_PS
    P = ppa.pages_per_block(ps, width, 4)
    assert P == block
    live = np.clip(-(-np.asarray(lengths) // ps), 1, table.shape[1])
    sizes = ppa._run_sizes(P, _RUN_POOL)
    listed, ends = map(
        np.asarray, ppa._copy_list(jnp.asarray(table), jnp.asarray(lengths), ps, P, _RUN_POOL)
    )
    filled = np.zeros(listed.shape, np.int64)
    counted = np.zeros((len(table), 2), np.int64)
    for lane in range(len(table)):
        for block in range(-(-live[lane] // P)):
            begin = 0
            for k, n in enumerate(sizes):
                end = ends[lane, block * len(sizes) + k]
                for entry in listed[lane, block * P + begin:block * P + end]:
                    j, at = block * P + (entry >> ppa._SLOT_SHIFT), entry & ((1 << ppa._SLOT_SHIFT) - 1)
                    np.testing.assert_array_equal(table[lane, j:j + n], at + np.arange(n))
                    filled[lane, j:j + n] += 1
                    counted[lane] += 1, n
                begin = end
    np.testing.assert_array_equal(filled, np.arange(filled.shape[1])[None, :] < live[:, None])
    copies = ppa.table_copies(table, live, ps, width, 4, _RUN_POOL)
    assert copies == counted[:, 0].sum()
    assert len(table) <= copies <= live.sum()
    if case == "fragmented":
        assert copies == live.sum()  # a page a copy: the parent's walk
    if case == "one_run":
        assert copies == 4 + 5  # 40 pages: 16, 16, 4 + 4; 23: 16, 4 + 1 + 1 + 1


@pytest.mark.parametrize("kernel", ["decode", "latent"])
def test_a_swapped_part_of_a_decode_kernel_is_traced_again(kernel, monkeypatch):
    """``benchmark/tools/latent_decode_probe.py`` times "copies only" and
    "arithmetic only" by swapping a part of the module between calls.  The
    kernels run under a ``jax.jit`` of their own, which would hand such a
    tool the trace it made first: the parts key that ``jit``
    (``_kernel_parts``), so the variant is what runs, and the kernel proper
    comes back with its parts."""
    from scalerl_tpu.ops import pallas_paged_attention as ppa

    table, lengths = _RUN_TABLES["shared_run_then_own_run"]
    out, _ = _run_case(kernel, table, lengths)

    def no_arithmetic(a_terms, b_terms, contract):
        return jnp.zeros((a_terms.shape[0] // 3, b_terms[0].shape[1 - contract[1][0]]), jnp.float32)

    with monkeypatch.context() as m:
        m.setattr(ppa, "_dot_terms", no_arithmetic)
        swapped, _ = _run_case(kernel, table, lengths)
    assert not np.allclose(np.asarray(swapped), np.asarray(out), atol=1e-3)
    again, _ = _run_case(kernel, table, lengths)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(out))
