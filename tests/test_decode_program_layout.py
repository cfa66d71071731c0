"""The engine's five programs compiled for a described ``v5e:2x2`` must read
the KV pools in place (ISSUE 24).

A pool whose minor dimension is under 128 lanes is stored page-index-minor
by the TPU runtime, while Mosaic takes its operands row-major: every decode
macro-step then transposed each whole pool into a padded temporary and back
(96 copies, 41% of the device's time and 6.4 GB of temporaries at
gpt2-medium; PERF.md, PR 24).  Nothing about that shows on the CPU, where
these tests otherwise run, so this file compiles a small engine's programs
with the chip's own compiler and reads the compiled text: no chip is
needed, nothing runs.

The topology is described inside a module-scoped fixture, never at import;
the benchmark's ``tests/benchmark/test_benchmark_real_shape_compiles.py``
does the same in another xdist worker, which the tier-1 command allows
with ``ALLOW_MULTIPLE_LIBTPU_LOAD=1``.  Where no topology can be described
the file skips.  The fused classic loop's program is held to the same
rule at the end of the file (ISSUE 31), and the segment kernels at latent
attention's head shape are compiled there at the learn cell's own sizes
(ISSUE 32), and the token learner's learn step is read for the copy of
its train state that a post-hoc guard costs (ISSUE 33), and a hybrid
stack's decode, prefill and fork programs are read for a copy of a Mamba
layer's recurrent state, which has to be carried in place beside the
pools (ISSUE 40), and a Gated DeltaNet stack's for a second read or a
copy of a layer's matrix state (ISSUE 42), and a compressed convolutional
attention stack's for a copy of a layer's window, which a decoded token
has to be written into where it lies (ISSUE 46): this is the one tier-1
file that loads the TPU's compiler outside ``tests/benchmark``.
"""

import contextlib
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from scalerl_tpu.genrl.continuous import ContinuousConfig, ContinuousEngine
from scalerl_tpu.models.transformer import TransformerPolicy, block_spec
from scalerl_tpu.ops.pallas_paged_attention import (
    paged_decode_attention,
    paged_decode_latent,
)

LAYERS, HEADS, LANES, PAGE, PAGES = 2, 16, 4, 8, 301
# the attention geometry of each block family the benchmark runs: a pool
# row is ``heads x head size`` wide, 1024 (gpt2-medium) and 2048 (OLMoE);
# longcat's is the latent row every head shares, 576 stored in 640 lanes
HEAD_DIM = {"gpt2": 64, "olmoe": 128, "longcat": 192}
LATENT = dict(
    q_lora_rank=256, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, ffn_hidden=256, zero_experts=4, experts_held=2,
)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever stops the description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _cache_off():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip: keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        compilation_cache.reset_cache()


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    with _cache_off():
        yield


@pytest.fixture(scope="module", params=sorted(HEAD_DIM))
def engine(request):
    """2 layers of gpt2-medium's attention geometry (16 heads of 64), and
    2 of OLMoE's (16 heads of 128, q/k norm and rotation before the cache
    write, a small routed FFN: pools ``[pages, 8, 2048]``), and 2 double
    layers of LongCat's latent attention at its published sizes (8 heads
    of 128 + 64, rows of 512 + 64: four pools ``[pages, 8, 640]``, no V),
    over 301 pages of 8; the compiled kernel is pinned behind the attention seam because
    ``auto`` resolves to the XLA gather on this CPU backend."""
    vocab = 128
    family = request.param
    head_dim = HEAD_DIM[family]
    kernel = paged_decode_latent if family == "longcat" else paged_decode_attention
    model = TransformerPolicy(
        num_actions=vocab, vocab_size=vocab,
        d_model=512 if family == "longcat" else HEADS * head_dim,
        # 8 heads keep every longcat weight smaller than its 1.5 M-value pool,
        # so that "a copy the size of a pool" can only be a pool
        num_heads=8 if family == "longcat" else HEADS, num_layers=LAYERS,
        mlp_ratio=1, max_len=256,
        paged_attn_fn=functools.partial(kernel, interpret=False),
        block=block_spec(
            family, head_dim=head_dim, num_experts=4, experts_per_token=2, expert_width=128,
            **(LATENT if family == "longcat" else {}),
        ),
    )
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32))
    return ContinuousEngine(
        model, params,
        ContinuousConfig(
            vocab_size=vocab, max_prompt_len=64, max_new_tokens=64,
            lanes=LANES, page_size=PAGE, num_pages=PAGES, steps_per_macro=2,
            spec_k=2,
        ),
        iter_mode="scan",
    )


def _described(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.result_type(x), sharding=sharding),
        tree,
    )


def _compiled_text(eng, one_chip, fn, *, params, extra):
    """``fn`` lowered on the engine's own state, described on the chip."""
    state = _described(
        (eng._pools, eng._logits_st, eng._value_st, eng._cl, eng._done, eng._resp), one_chip
    )
    head = (_described(eng._snapshot_params()[0], one_chip),) if params else ()
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)  # noqa: E731
    tail = [
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip) if s == "key" else i32(*s)
        for s in extra
    ]
    return fn.lower(*head, *state, *tail).compile().as_text()


def _elements(dims):
    return int(np.prod([int(d) for d in dims.split(",")]))


def _assert_pools_read_in_place(text, eng):
    """No ``copy`` or ``transpose`` the size of a pool, and every pool the
    program names is row-major on whole ``(8, 128)`` tiles.  Pools this
    small the compiler may prefetch into another memory space and write
    back (``copy-start``, the layout unchanged but for ``S(n)``): that is
    its own business and does not happen at a real pool's size."""
    pool = int(np.prod(jax.tree_util.tree_leaves(eng._pools)[0].shape))
    moved = [
        line.strip()[:160]
        for line in text.splitlines()
        for m in [re.search(r"= \w+\[([\d,]+)\]\S* (copy|transpose)\(", line)]
        if m and _elements(m.group(1)) >= pool
    ]
    assert not moved, f"{len(moved)} whole-pool relayouts, the first: {moved[0]}"
    layouts = set(re.findall(rf"f32\[{PAGES},[\d,]+\]\{{[^}}]*\}}", text))
    assert layouts, "the program names no pool"
    wrong = {l for l in layouts if not re.search(r"\{2,1,0(:T\(8,128\)(S\(\d\))?)?\}$", l)}
    assert not wrong, f"pools not row-major in place: {wrong}"


def test_decode_macro_step_reads_the_pools_in_place(engine, one_chip):
    M = engine._table.shape[1]
    text = _compiled_text(
        engine, one_chip, engine._decode_fn, params=True, extra=[(LANES, M), "key"]
    )
    assert text.count("tpu_custom_call") >= LAYERS
    _assert_pools_read_in_place(text, engine)
    # the donated pools come back as themselves: output i aliases the
    # parameter that follows the model's own leaves
    leaves = len(jax.tree_util.tree_leaves(engine._snapshot_params()[0]))
    header = text[text.index("input_output_alias={"):].split("\n", 1)[0]
    aliased = {
        int(out): int(param)
        for out, param in re.findall(r"\{(\d+)\}: \((\d+), \{\}", header)
    }
    pools = len(jax.tree_util.tree_leaves(engine._pools))
    assert pools == 2 * LAYERS  # K and V a block, or a double layer's two latent pools
    for i in range(pools):
        assert aliased.get(i) == leaves + i, (i, aliased)


@pytest.mark.parametrize("program", ["local_prefill", "tail_prefill", "fork", "verify"])
def test_other_programs_leave_the_pools_in_place(engine, one_chip, program):
    M = engine._table.shape[1]
    A, P = 2, 64
    if program == "local_prefill":
        fn = engine._prefill_fn(("local", P, A))
        extra = [(A, P), (A,), (A,), (A, P), (A, P)]
    elif program == "tail_prefill":
        fn = engine._prefill_fn(("prefix", P, A))
        extra = [(A, P), (A,), (A,), (A, P), (A, P), (A, M), (A,)]
    elif program == "fork":
        fn, extra = engine._fork_fn(A), [(A,)] * 4
    else:
        k = 2
        fn = engine._build_verify(k)
        extra = [(LANES, k), (LANES,), (LANES, k + 1), (LANES, k + 1), (LANES, M), (LANES,), "key"]
    text = _compiled_text(engine, one_chip, fn, params=program != "fork", extra=extra)
    _assert_pools_read_in_place(text, engine)


# -- a recurrent state beside the pools (ISSUE 40) ---------------------------


@pytest.fixture(scope="module")
def hybrid_engine():
    """The pattern ``M*EM`` at Nemotron-3-Nano's mixer sizes (64 Mamba
    heads of 64, state 128, 8 groups; 32 query heads of 128 over 2
    key/value heads) on a hidden size of 256, 4 lanes: a layer's state is
    ``[4, 64, 64, 128]`` float32 (8 MB: larger than any weight here, so
    that a copy of its size can only be a state) and its pools ``[301, 8,
    256]``.  The paged kernel is pinned compiled, as in the fixture above."""
    from scalerl_tpu.models.transformer import pattern_specs

    vocab, pattern = 128, "M*EM"
    spec = block_spec(
        "nemotron_h", head_dim=128, num_experts=8, experts_per_token=2, expert_width=128,
        norm_topk_prob=True, experts_held=4, scoring="sigmoid", shared_experts=1,
        shared_width=256, kv_heads=2, expert_act="relu2", ssm_heads=64, ssm_head_dim=64,
        ssm_state=128, ssm_groups=8,
    )
    model = TransformerPolicy(
        num_actions=vocab, vocab_size=vocab, d_model=256, num_heads=32,
        num_layers=len(pattern), max_len=256, block=spec, layers=pattern_specs(spec, pattern),
        paged_attn_fn=functools.partial(paged_decode_attention, interpret=False),
    )
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32))
    return ContinuousEngine(
        model, params,
        ContinuousConfig(
            vocab_size=vocab, max_prompt_len=64, max_new_tokens=64,
            lanes=LANES, page_size=PAGE, num_pages=PAGES, steps_per_macro=2,
        ),
        iter_mode="scan",
    )


def _assert_state_in_place(text, eng):
    """No ``copy`` or ``transpose`` of the shape of a layer's recurrent
    state or of a pool (a fork's gathered rows are a few lanes' worth, not
    the array; the convolution's window, 1/28 of the state, is rewritten
    whole by every token's shift, and one this small the compiler moves
    through another memory space), and every state array the program
    names is row-major on whole ``(8, 128)`` tiles: the 128 states on the
    minor axis (a ``while`` carry takes its logical shape's layout)."""
    cache = eng._pools
    whole = {",".join(str(d) for d in x.shape) for x in cache.k + cache.v + cache.ssm}
    moved = [
        line.strip()[:160]
        for line in text.splitlines()
        for m in [re.search(r"= \w+\[([\d,]+)\]\S* (copy|transpose)\(", line)]
        if m and m.group(1) in whole
    ]
    assert not moved, f"{len(moved)} whole-state or whole-pool copies, the first: {moved[0]}"
    # nor a pass over a state under another shape: a gather of rows this
    # wide first split the whole array in two halves (the fork, PR 40)
    half = _elements(",".join(str(d) for d in cache.ssm[0].shape)) // 2
    passes = [
        line.strip()[:160]
        for line in text.splitlines()
        for m in [re.search(rf"= \(?f32\[({LANES},[\d,]+)\]", line)]  # lane-indexed, like a state
        if m and m.group(1) not in whole and _elements(m.group(1)) >= half
    ]
    assert not passes, f"{len(passes)} arrays of half a state or more, the first: {passes[0]}"
    layouts = set(re.findall(rf"f32\[{LANES},64,64,128\]\{{[^}}]*\}}", text))
    assert layouts, "the program names no state"
    wrong = {l for l in layouts if not re.search(r"\{3,2,1,0(:T\(8,128\)(S\(\d\))?)?\}$", l)}
    assert not wrong, f"states not row-major in place: {wrong}"


def test_hybrid_decode_carries_the_state_in_place(hybrid_engine, one_chip):
    """The decode macro-step of a stack with Mamba layers: ONE fusion a
    Mamba layer a substep that takes the state and gives ``(y, state)``
    (``ssm_decode_update``: the state read once and written once), one
    ``paged_decode`` an attention layer, every pool and state array
    donated and returned as itself, none copied."""
    eng = hybrid_engine
    M = eng._table.shape[1]
    text = _compiled_text(eng, one_chip, eng._decode_fn, params=True, extra=[(LANES, M), "key"])
    assert "tpu_custom_call" in text and "paged_decode" in text  # one attention layer
    updates = re.findall(
        rf"= \(f32\[{LANES},64,64\]\S*, f32\[{LANES},64,64,128\]\S*\) fusion\(.*ssm_decode_update", text
    )
    assert len(updates) == 2, f"{len(updates)} state updates for two Mamba layers"
    _assert_state_in_place(text, eng)
    leaves = len(jax.tree_util.tree_leaves(eng._snapshot_params()[0]))
    header = text[text.index("input_output_alias={"):].split("\n", 1)[0]
    aliased = {
        int(out): int(param)
        for out, param in re.findall(r"\{(\d+)\}: \((\d+), \{\}", header)
    }
    cache = len(jax.tree_util.tree_leaves(eng._pools))
    assert cache == 2 + 2 + 2  # K and V of one attention, state and window of two Mamba layers
    for i in range(cache):
        assert aliased.get(i) == leaves + i, (i, aliased)


@pytest.mark.parametrize("program", ["local_prefill", "fork"])
def test_hybrid_prefill_and_fork_leave_the_state_in_place(hybrid_engine, one_chip, program):
    eng = hybrid_engine
    A, P = 2, 64
    if program == "local_prefill":
        fn = eng._prefill_fn(("local", P, A))
        extra = [(A, P), (A,), (A,), (A, P), (A, P)]
    else:
        fn, extra = eng._fork_fn(A), [(A,)] * 4
    text = _compiled_text(eng, one_chip, fn, params=program != "fork", extra=extra)
    _assert_state_in_place(text, eng)


# -- a matrix state a head beside the pools (ISSUE 42) ------------------------


@pytest.fixture(scope="module")
def delta_engine():
    """``L L F`` (an interval of 3) at Qwen3-Next's mixer sizes (32 value
    heads of 128 over 16 key heads of 128; 16 query heads of 256 over 2
    key/value heads, 64 rotating features) on a hidden size of 256, 4
    lanes: a layer's state is ``[4, 32, 128, 128]`` float32 (8 MB: larger
    than any weight here) and its pools ``[301, 8, 512]``.  The paged
    kernel is pinned compiled; the delta rule's kernel is what the mixer
    takes on a TPU backend, which the tests below stand in for."""
    from scalerl_tpu.models.transformer import interval_specs

    vocab = 128
    spec = block_spec(
        "qwen3_next", head_dim=256, norm_eps=1e-6, rope_theta=1e7, num_experts=8,
        experts_per_token=2, expert_width=128, norm_topk_prob=True, experts_held=4,
        shared_experts=1, shared_width=128, kv_heads=2, ssm_heads=32, ssm_head_dim=128,
        ssm_state=128, ssm_groups=16, ssm_chunk=64, rotary_dim=64,
    )
    model = TransformerPolicy(
        num_actions=vocab, vocab_size=vocab, d_model=256, num_heads=16, num_layers=3,
        max_len=256, block=spec, layers=interval_specs(spec, 3, 3),
        paged_attn_fn=functools.partial(paged_decode_attention, interpret=False),
    )
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32))
    return ContinuousEngine(
        model, params,
        ContinuousConfig(
            vocab_size=vocab, max_prompt_len=64, max_new_tokens=64,
            lanes=LANES, page_size=PAGE, num_pages=PAGES, steps_per_macro=2,
        ),
        iter_mode="scan",
    )


def _assert_delta_state_in_place(text, eng):
    """No ``copy`` or ``transpose`` of the shape of a layer's matrix state
    or of a pool, and every state array the program names row-major on
    whole ``(8, 128)`` tiles (value features on the minor axis)."""
    cache = eng._pools
    whole = {",".join(str(d) for d in x.shape) for x in cache.k + cache.v + cache.ssm}
    moved = [
        line.strip()[:160]
        for line in text.splitlines()
        for m in [re.search(r"= \w+\[([\d,]+)\]\S* (copy|transpose)\(", line)]
        if m and m.group(1) in whole
    ]
    assert not moved, f"{len(moved)} whole-state or whole-pool copies, the first: {moved[0]}"
    layouts = set(re.findall(rf"f32\[{LANES},32,128,128\]\{{[^}}]*\}}", text))
    assert layouts, "the program names no state"
    wrong = {l for l in layouts if not re.search(r"\{3,2,1,0(:T\(8,128\)(S\(\d\))?)?\}$", l)}
    assert not wrong, f"states not row-major in place: {wrong}"


def test_delta_decode_reads_the_state_once_and_in_place(delta_engine, one_chip, monkeypatch):
    """The decode macro-step of a stack with Gated DeltaNet layers, as a
    TPU backend traces it: ONE ``gdn_decode_update`` kernel a delta-rule
    layer a substep and no fusion that takes a state (the plain form's two
    fusions a layer read it twice: PERF.md, PR 42), one ``paged_decode``
    at 16 query heads over 2 key/value heads of 256, every pool and state
    array donated and returned as itself, none copied."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eng = delta_engine
    M = eng._table.shape[1]
    text = _compiled_text(eng, one_chip, eng._build_decode(), params=True, extra=[(LANES, M), "key"])
    calls = re.findall(r"= \((?:[^()]|\([^()]*\))*\) custom-call\(.*gdn_decode_update", text)
    assert len(calls) == 2, f"{len(calls)} kernel calls for two delta-rule layers"
    assert all(f"f32[{LANES},32,128,128]" in call for call in calls)  # the state is a result
    assert "paged_decode" in text
    _assert_delta_state_in_place(text, eng)
    leaves = len(jax.tree_util.tree_leaves(eng._snapshot_params()[0]))
    header = text[text.index("input_output_alias={"):].split("\n", 1)[0]
    aliased = {
        int(out): int(param)
        for out, param in re.findall(r"\{(\d+)\}: \((\d+), \{\}", header)
    }
    cache = len(jax.tree_util.tree_leaves(eng._pools))
    assert cache == 2 + 2 + 2  # K and V of one attention, state and window of two delta layers
    for i in range(cache):
        assert aliased.get(i) == leaves + i, (i, aliased)


@pytest.mark.parametrize("program", ["local_prefill", "fork"])
def test_delta_prefill_and_fork_leave_the_state_in_place(delta_engine, one_chip, program):
    """The chunked prefill (a triangular solve a chunk, the state written
    at the true length) and the fork compile for the chip and copy no
    whole state or pool."""
    eng = delta_engine
    A, P = 2, 64
    if program == "local_prefill":
        fn = eng._prefill_fn(("local", P, A))
        extra = [(A, P), (A,), (A,), (A, P), (A, P)]
    else:
        fn, extra = eng._fork_fn(A), [(A,)] * 4
    text = _compiled_text(eng, one_chip, fn, params=program != "fork", extra=extra)
    _assert_delta_state_in_place(text, eng)


# -- a window beside a layer's own pools (ISSUE 46) ---------------------------


@pytest.fixture(scope="module")
def cca_engine():
    """Two ``zaya`` layers at ZAYA1's attention sizes (8 query heads over 2
    key/value heads of 128, 64 rotating features, both convolutions at 2
    taps: a window of ``[4, 2, 1408]`` float32 a layer, ring-ordered) on a
    hidden size of 256, 4 experts of 128 behind a router of width 64, 4
    lanes; pools ``[301, 8, 256]``.  The paged kernel is pinned compiled."""
    vocab = 128
    spec = block_spec(
        "zaya", head_dim=128, norm_eps=1e-5, rope_theta=5e6, num_experts=4,
        experts_per_token=1, expert_width=128, kv_heads=2, rotary_dim=64,
        cca_time0=2, cca_time1=2, router_width=64,
    )
    model = TransformerPolicy(
        num_actions=vocab, vocab_size=vocab, d_model=256, num_heads=8, num_layers=2,
        max_len=256, block=spec,
        paged_attn_fn=functools.partial(paged_decode_attention, interpret=False),
    )
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32))
    return ContinuousEngine(
        model, params,
        ContinuousConfig(
            vocab_size=vocab, max_prompt_len=64, max_new_tokens=64,
            lanes=LANES, page_size=PAGE, num_pages=PAGES, steps_per_macro=2,
        ),
        iter_mode="scan",
    )


def _assert_window_in_place(text, eng):
    """No ``copy`` or ``transpose`` of the shape of a layer's window or of
    a pool: a decoded token is scattered over the window's oldest row
    where it lies (a ring), so nothing shifts."""
    cache = eng._pools
    whole = {",".join(str(d) for d in x.shape) for x in cache.k + cache.v + cache.conv}
    moved = [
        line.strip()[:160]
        for line in text.splitlines()
        for m in [re.search(r"= \w+\[([\d,]+)\]\S* (copy|transpose)\(", line)]
        if m and m.group(1) in whole
    ]
    assert not moved, f"{len(moved)} whole-window or whole-pool copies, the first: {moved[0]}"


def test_cca_decode_carries_the_window_in_place(cca_engine, one_chip):
    """The decode macro-step of a stack of compressed convolutional
    attention layers: one ``paged_decode`` a layer at 8 query heads over 2
    key/value heads of 128, every pool and window donated and returned as
    itself, none copied (a window kept oldest-first shifts by a row a
    token and is copied whole, twice a layer a substep: PERF.md, PR 46),
    and the two scopes the benchmark's AOT script counts by are in the
    compiled text's ``op_name``."""
    eng = cca_engine
    M = eng._table.shape[1]
    text = _compiled_text(eng, one_chip, eng._decode_fn, params=True, extra=[(LANES, M), "key"])
    assert len(re.findall(r"custom-call\(.*paged_decode", text)) == 2
    assert "/cca_window/" in text and "/zaya_router/" in text
    _assert_window_in_place(text, eng)
    _assert_pools_read_in_place(text, eng)
    leaves = len(jax.tree_util.tree_leaves(eng._snapshot_params()[0]))
    header = text[text.index("input_output_alias={"):].split("\n", 1)[0]
    aliased = {
        int(out): int(param)
        for out, param in re.findall(r"\{(\d+)\}: \((\d+), \{\}", header)
    }
    cache = len(jax.tree_util.tree_leaves(eng._pools))
    assert cache == 2 + 2 + 2 and eng._pools.ssm == ()  # K, V and a window a layer
    for i in range(cache):
        assert aliased.get(i) == leaves + i, (i, aliased)


@pytest.mark.parametrize("program", ["local_prefill", "fork"])
def test_cca_prefill_and_fork_leave_the_window_in_place(cca_engine, one_chip, program):
    """The prefill (the window written at the true length, ring-ordered)
    and the fork compile for the chip and copy no whole window or pool."""
    eng = cca_engine
    A, P = 2, 64
    if program == "local_prefill":
        fn = eng._prefill_fn(("local", P, A))
        extra = [(A, P), (A,), (A,), (A, P), (A, P)]
    else:
        fn, extra = eng._fork_fn(A), [(A,)] * 4
    text = _compiled_text(eng, one_chip, fn, params=program != "fork", extra=extra)
    _assert_window_in_place(text, eng)



# -- a residual stream of rows around a latent attention (ISSUE 51) ------------


@pytest.fixture(scope="module")
def rows_engine():
    """A dense and a routed ``xing4`` layer at the latent attention's
    published row (8 heads of 128 + 64, rows of 512 + 64 in 640 lanes) on a
    hidden size of 512, a stream of 4 rows with 20 Sinkhorn iterations,
    YaRN of factor 64 over 4,096 positions, 4 experts of 128 beside a
    shared one, 4 lanes; two pools ``[301, 8, 640]``.  The latent kernel is
    pinned compiled."""
    from scalerl_tpu.models.transformer import RopeScaling, layer_specs

    vocab = 128
    spec = block_spec(
        "xing4", norm_eps=1e-6, num_experts=4, experts_per_token=2, expert_width=128,
        norm_topk_prob=True, q_lora_rank=256, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, ffn_hidden=256, routed_scaling=2.0,
        scoring="sigmoid", shared_experts=1, streams=4, hc_iters=20,
        rope_scaling=RopeScaling(64.0, 4096, 32.0, 1.0, 1.0, 1.0),
    )
    model = TransformerPolicy(
        num_actions=vocab, vocab_size=vocab, d_model=512, num_heads=8, num_layers=2,
        max_len=256, block=spec, layers=layer_specs(spec, 2, 1),
        paged_attn_fn=functools.partial(paged_decode_latent, interpret=False),
    )
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32))
    return ContinuousEngine(
        model, params,
        ContinuousConfig(
            vocab_size=vocab, max_prompt_len=64, max_new_tokens=64,
            lanes=LANES, page_size=PAGE, num_pages=PAGES, steps_per_macro=2,
        ),
        iter_mode="scan",
    )


def test_rows_decode_holds_one_loop_and_no_lane_state(rows_engine, one_chip):
    """The decode macro-step of a stack on a stream of rows: one
    ``paged_decode_latent`` a layer, every pool donated and returned as
    itself and none copied, NO array by lane beside them (the stream is an
    activation), the substep loop the program's only ``while`` (the 20
    iterations of four hyper-connections are unrolled), and the two scopes
    the benchmark's AOT script counts by in the compiled text's
    ``op_name``."""
    eng = rows_engine
    M = eng._table.shape[1]
    text = _compiled_text(eng, one_chip, eng._decode_fn, params=True, extra=[(LANES, M), "key"])
    assert len(re.findall(r"custom-call\(.*paged_decode_latent", text)) == 2
    assert "/mhc_maps/" in text and "/mhc_mix/" in text
    assert len(re.findall(r" while\(", text)) == 1
    _assert_pools_read_in_place(text, eng)
    cache = eng._pools
    assert len(cache.rows) == 2 and not (cache.k or cache.v or cache.ssm or cache.conv)
    assert eng.stats()["state_bytes_per_lane"] == 0
    leaves = len(jax.tree_util.tree_leaves(eng._snapshot_params()[0]))
    header = text[text.index("input_output_alias={"):].split("\n", 1)[0]
    aliased = {
        int(out): int(param)
        for out, param in re.findall(r"\{(\d+)\}: \((\d+), \{\}", header)
    }
    for i in range(2):
        assert aliased.get(i) == leaves + i, (i, aliased)


# -- the fused classic loop (ISSUE 31) ---------------------------------------


@pytest.fixture(scope="module", params=[512, 2048])
def fused_program(request, one_chip):
    """The ``impala_fused`` cell's program (unroll 20, 5 iterations a
    dispatch, bf16 torso) compiled at ``num_envs``: ``(num_envs, elements of
    a frame batch, compiled text)``."""
    from scalerl_tpu.agents.impala import ImpalaAgent
    from scalerl_tpu.config import ImpalaArguments
    from scalerl_tpu.envs import make_jax_vec_env
    from scalerl_tpu.runtime.device_loop import DeviceActorLearnerLoop

    num_envs, T = request.param, 20
    args = ImpalaArguments(
        env_id="SyntheticPixel-v0", use_lstm=False, hidden_size=512,
        rollout_length=T, batch_size=num_envs, max_timesteps=0,
        compute_dtype="bfloat16", logger_backend="none",
    )
    venv = make_jax_vec_env(args.env_id, num_envs=num_envs)
    agent = ImpalaAgent(
        args, obs_shape=venv.observation_shape, num_actions=venv.num_actions,
        obs_dtype=venv.env.observation_dtype,
    )
    loop = DeviceActorLearnerLoop(
        agent.model, venv, agent.make_learn_fn(), T, iters_per_call=5,
        iter_mode="scan",  # what "auto" resolves to on the chip
    )
    key = jax.random.PRNGKey(0)
    carry = jax.eval_shape(loop.init_carry, key)
    with _cache_off():
        text = (
            loop._train_many.lower(*_described((agent.state, carry, key), one_chip))
            .compile().as_text()
        )
    return num_envs, num_envs * int(np.prod(venv.observation_shape)), text


def test_fused_loop_stores_frames_lane_dense(fused_program):
    """The program holds no lane-padded uint8 frame array and relayouts no
    frame batch inside a loop.  With ``obs`` carried as ``[B, 84, 84, 4]``
    the renderer wrote ``u8[B,84,84,4]{3,2,1,0:T(8,128) (4,1)}``, 33.5
    times padded, and a ``copy`` read it back on every environment step:
    65% of the cell's device time (PERF.md, PR 31)."""
    from scalerl_tpu.utils import tiled_layout

    num_envs, frame_batch, text = fused_program
    faults = tiled_layout.lane_dense_faults(text, "u8", frame_batch, lane_dim=num_envs)
    assert not faults, faults


def test_fused_loop_merges_time_and_envs_without_a_copy(fused_program):
    """The learner merges ``[T, B]`` into conv1's batch axis.  With the
    rows stacked T major-most that merge was ``copy bf16[21,84,84,1,4,B]``,
    the whole scaled trajectory read and written once an iteration (8% of
    the cell's device time, PERF.md, PR 43); written into a buffer
    ``[84, 84, T+1, 4, B]`` pinned row-major it is a bitcast of the one
    convert pass, and no loop body copies an array that large in either
    dtype."""
    from scalerl_tpu.utils import tiled_layout

    num_envs, frame_batch, text = fused_program
    for dtype in ("bf16", "u8"):
        assert not tiled_layout.loop_body_copies(text, dtype, 10 * frame_batch)
    buf = f"[84,84,21,4,{num_envs}]{{4,3,2,1,0:"
    assert f"u8{buf}" in text  # the carry kept the pinned layout
    merged = re.findall(
        rf"= bf16\[{21 * num_envs},84,84,4\]\{{0,3,2,1:[^}}]*\}} bitcast\(%([\w.\-]+)\)", text
    )
    # conv1's operand comes straight from the convert pass over the buffer
    # (the same bitcast inside a fusion reads a parameter: not this one)
    assert any(
        re.search(rf"%{re.escape(src)} = bf16{re.escape(buf)}[^}}]*\}} fusion\(", text)
        for src in merged
    ), merged
    assert not [src for src in merged if src.startswith("copy")], merged


def test_segment_kernels_compile_at_latent_attentions_head_shape(one_chip):
    """``joyai_packed_learn``'s attention: 4 packed rows of 1,024, 32 heads,
    q and k 192 wide (one and a half lane tiles) and v 128, bfloat16,
    forward and both backward kernels.  Mosaic takes the 192-wide head and
    the narrower v as they are (ISSUE 32; it had refused a 576-wide page
    out of a 640-lane pool, PR 30): three custom calls and no pad of v in
    the program."""
    import json
    from pathlib import Path

    from scalerl_tpu.ops.pallas_attention import segment_flash_attention

    bench = Path(__file__).resolve().parents[1] / "benchmark"
    p = json.loads((bench / "workloads" / "joyai_packed_learn.json").read_text())["params"]
    cfg = json.loads((bench / "configs" / "joyai-llm-flash.json").read_text())
    rows, T, H = p["rows_per_step"], p["pack_len"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    sds = lambda *shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731

    def loss(q, k, v, seg):
        return jnp.sum(segment_flash_attention(q, k, v, seg, interpret=False).astype(jnp.float32))

    compiled = (
        jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        .trace(sds(rows, T, H, qk), sds(rows, T, H, qk), sds(rows, T, H, cfg["v_head_dim"]),
               sds(rows, T, dt=jnp.int32))
        .lower(lowering_platforms=("tpu",)).compile()
    )
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3
    for name in ("segment_flash_fwd", "segment_flash_bwd_dq", "segment_flash_bwd_dkv"):
        assert name in text


# -- the token learner's guard (ISSUE 33) ------------------------------------


def small_token_learner():
    """Two layers 256 wide, no kernel (the guard is the optimiser's tail,
    whatever the attention); sizes at which no activation has a parameter
    leaf's shape: 2 rows x 96 positions, vocabulary 384."""
    from scalerl_tpu.agents.token_ppo import TokenPPOAgent
    from scalerl_tpu.config import GenRLArguments
    from scalerl_tpu.genrl.rollout import packed_field_shapes

    rows, S = 2, 96
    args = GenRLArguments(
        vocab_size=384, d_model=256, n_layers=2, n_heads=4, prompt_len=32,
        max_new_tokens=64, telemetry_interval_s=0.0, logger_backend="none",
    )
    model = TransformerPolicy(
        num_actions=384, vocab_size=384, d_model=256, num_heads=4, num_layers=2, max_len=S,
    )
    batch = {
        name: jnp.zeros((rows,) + shape, dtype)
        for name, (shape, dtype) in packed_field_shapes(S).items()
    }
    return TokenPPOAgent(args, model), batch


def test_token_learn_step_builds_no_candidate_beside_its_state(one_chip):
    """With the state donated, the post-hoc guard's chosen branch was one
    ``copy`` a leaf of parameters and both moments into the donated
    buffers: 598 copies, 4.9 GB a step at gpt2-medium, the three largest
    ``copy`` rows of ``gpt2m_packed_learn`` (PERF.md, PR 33).  The token
    learner's own form has no ``conditional`` and copies no leaf; the
    post-hoc form around the same update, which this learner had before,
    must show both, or this test reads nothing."""
    import dataclasses

    from scalerl_tpu.agents.token_ppo import make_token_ppo_learn_fn
    from scalerl_tpu.parallel.train_step import guard_nonfinite_updates
    from scalerl_tpu.utils import tiled_layout

    agent, batch = small_token_learner()
    leaves = jax.tree_util.tree_leaves(agent.state.params)

    def faults(learn_fn):
        text = (
            jax.jit(learn_fn, donate_argnums=(0,))
            .lower(*_described((agent.state, batch), one_chip))
            .compile().as_text()
        )
        return tiled_layout.candidate_state_faults(text, [x.shape for x in leaves])

    found = faults(agent.make_learn_fn())
    assert not found, (len(found), found[:2])

    unguarded = make_token_ppo_learn_fn(
        agent.model, agent.optimizer, dataclasses.replace(agent.args, nonfinite_guard=False)
    )
    found = faults(guard_nonfinite_updates(unguarded))
    assert sum(f.startswith("conditional") for f in found) == 1, found[:2]
    assert sum(f.startswith("copy") for f in found) >= 2 * len(leaves), len(found)
