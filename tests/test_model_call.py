"""The one place in ``models/transformer.py`` that decides how the model is
being called (:class:`Call`), and the one cache class every family caches
into (:class:`ModelCache`), on one tiny model a layer family
(``tests/tiny_families.py``).

The cache's leaves (shapes, dtypes, order) are written out below as they
were at 2f98510, when the cache was three classes (``zaya``: as the PR
that added it made them): a program's arguments are the cache's leaves, so
a leaf that moved would move every rollout program's arguments with it.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scalerl_tpu.models.transformer import Call, ModelCache, fork_cache, run_ids
from tests.tiny_families import MODELS

PAGES, PAGE, LANES = 9, 4, 3
_POOL = lambda width: (PAGES, PAGE, width)  # noqa: E731
# family: field -> the shapes of its arrays, in layer order
LEAVES = {
    "gpt2": dict(k=[_POOL(64)] * 2, v=[_POOL(64)] * 2),
    "olmoe": dict(k=[_POOL(64)] * 2, v=[_POOL(64)] * 2),
    "longcat": dict(rows=[_POOL(128)] * 4),  # two attentions a double layer
    "joyai": dict(rows=[_POOL(128)] * 3),  # none for the module
    "nemotron": dict(  # M E M * E -: one attention, two Mamba layers
        k=[_POOL(16)], v=[_POOL(16)], ssm=[(LANES, 4, 8, 16)] * 2, conv=[(LANES, 3, 96)] * 2,
    ),
    "qwen3next": dict(  # L L L F L: one attention, four delta-rule layers
        k=[_POOL(32)], v=[_POOL(32)], ssm=[(LANES, 4, 8, 8)] * 4, conv=[(LANES, 3, 64)] * 4,
    ),
    "zaya": dict(  # every layer an attention with pages AND a window, no state (ISSUE 46)
        k=[_POOL(16)] * 3, v=[_POOL(16)] * 3, conv=[(LANES, 2 * 56)] * 3,
    ),
    # joyai's pools and nothing else: a stream of rows is an activation (ISSUE 51)
    "xing4": dict(rows=[_POOL(128)] * 3),
}
FAMILIES = sorted(LEAVES)


def _cache(family, dtype=jnp.float32):
    return MODELS[family].init_paged_cache(PAGES, PAGE, dtype=dtype, lanes=LANES)


@pytest.mark.parametrize("family", FAMILIES)
def test_every_family_caches_into_the_one_class_with_the_leaves_it_had(family):
    cache = _cache(family, jnp.bfloat16)
    assert type(cache) is ModelCache and ModelCache._fields == ("k", "v", "rows", "ssm", "conv")
    want = LEAVES[family]
    for name in ModelCache._fields:
        arrays = getattr(cache, name)
        assert isinstance(arrays, tuple)  # empty where the model has none
        assert [a.shape for a in arrays] == want.get(name, [])
        # pools in the dtype asked for, a lane's state always float32
        dtype = jnp.float32 if name in ("ssm", "conv") else jnp.bfloat16
        assert {a.dtype for a in arrays} <= {jnp.dtype(dtype)}
        assert not any(np.any(np.asarray(a, np.float32)) for a in arrays)
    # the leaves are the fields' arrays in field order and nothing else
    leaves = jax.tree_util.tree_leaves(cache)
    assert [a.shape for a in leaves] == [s for name in ModelCache._fields for s in want.get(name, [])]
    paths = [jax.tree_util.keystr(path) for path, _leaf in jax.tree_util.tree_flatten_with_path(cache)[0]]
    assert paths == [f".{name}[{i}]" for name in ModelCache._fields for i in range(len(want.get(name, [])))]
    # and each layer owns what its spec says, in layer order
    model = MODELS[family]
    for name in ModelCache._fields:
        assert sum(s.owns.get(name, 0) for s in model.layer_specs) == len(want.get(name, []))
    if model.lane_state:
        with pytest.raises(ValueError, match="sized by its lanes"):
            model.init_paged_cache(PAGES, PAGE)
    else:  # no lanes to size anything by
        assert jax.tree_util.tree_structure(model.init_paged_cache(PAGES, PAGE)) == (
            jax.tree_util.tree_structure(cache)
        )


@pytest.mark.parametrize("family", FAMILIES)
def test_a_fork_copies_pages_and_lanes_whatever_the_family(family):
    """Page 2 to page 5 in every pool and lane 1's rows to lane 2's in every
    state; pad rows copy the null page to itself and name a lane out of
    range, which drops."""
    cache = jax.tree_util.tree_map(
        lambda a: a + jnp.arange(1.0, a.shape[0] + 1).reshape((-1,) + (1,) * (a.ndim - 1)),
        _cache(family),
    )
    forked = fork_cache(
        cache, jnp.asarray([2, 0]), jnp.asarray([5, 0]), jnp.asarray([1, 0]), jnp.asarray([2, LANES])
    )
    assert type(forked) is ModelCache
    assert jax.tree_util.tree_structure(forked) == jax.tree_util.tree_structure(cache)
    for name in ModelCache._fields:
        src, dst = (2, 5) if name in ("k", "v", "rows") else (1, 2)
        for before, after in zip(getattr(cache, name), getattr(forked, name)):
            np.testing.assert_array_equal(after[dst], before[src])
            keep = np.arange(before.shape[0]) != dst
            np.testing.assert_array_equal(np.asarray(after)[keep], np.asarray(before)[keep])
            assert float(jnp.max(jnp.abs(before[dst] - before[src]))) >= 1.0


def _refused(family):
    """Argument sets that spell none of the six forms, for this family."""
    recurrent = MODELS[family].lane_state  # a recurrent layer, or a window alone
    z = jnp.zeros((2, 4), jnp.int32)
    lanes = jnp.zeros((2,), jnp.int32)
    paged = dict(page_ids=z, page_offsets=z)
    table = dict(page_table=jnp.zeros((2, 3), jnp.int32))
    state = dict(state_lanes=lanes) if recurrent else {}
    mask = dict(attn_mask=jnp.ones((2, 4, 4), bool))
    sets = {
        "a_page_table_with_no_cache": (False, dict(**table, attn_lengths=lanes)),
        "page_ids_with_no_cache": (False, dict(**paged, **mask)),
        "prefix_starts_with_no_page_table": (True, dict(**paged, **mask, **state, prefix_starts=lanes)),
        "segment_ids_on_a_cache": (True, dict(**paged, **mask, **state, segment_ids=z)),
        "a_cache_and_nowhere_to_write": (True, dict(**mask, **state)),
        "a_decode_with_no_lengths": (True, dict(**paged, **table)),
        "mtp_on_a_cache": (True, dict(**paged, **mask, **state, mtp=True)),
    }
    if recurrent:
        sets["a_tail_prefill_on_a_recurrent_model"] = (True, dict(**paged, **table, prefix_starts=lanes))
        sets["a_prefill_that_names_no_lanes"] = (True, dict(**paged, **mask))
        sets["state_lanes_in_a_decode"] = (True, dict(**paged, **table, attn_lengths=lanes, **state))
    else:
        sets["state_lanes_with_no_recurrent_layer"] = (True, dict(**paged, **mask, state_lanes=lanes))
    return sets


@pytest.mark.parametrize(
    "family,case", [(family, case) for family in FAMILIES for case in _refused(family)]
)
def test_an_argument_set_that_spells_no_form_is_refused_by_the_model(family, case):
    model = MODELS[family]
    on_cache, kw = _refused(family)[case]
    tokens = jnp.zeros((2, 4), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), tokens))
    if on_cache:
        kw = dict(kw, paged_cache=_cache(family))
    with pytest.raises(ValueError, match="call|carries lane state has no tail prefill"):
        jax.eval_shape(lambda: model.apply(params, tokens, positions=tokens, **kw))


_ARRAYS = dict.fromkeys(
    ("attn_mask", "segment_ids", "page_ids", "page_offsets", "page_table", "attn_lengths",
     "prefix_starts", "state_lanes")
)


def _of(lane_state=False, segment_kernel=False, mtp=False, paged_cache=None, **given):
    return Call.of(
        lane_state=lane_state, segment_kernel=segment_kernel, mtp=mtp, paged_cache=paged_cache,
        **{**_ARRAYS, **given},
    )


def test_the_six_forms_are_spelled_by_their_arguments_and_by_nothing_else():
    z = jnp.zeros((2, 4), jnp.int32)
    lanes = jnp.zeros((2,), jnp.int32)
    mask = jnp.tril(jnp.ones((4, 4), bool))[None].repeat(2, 0)
    table = jnp.zeros((2, 3), jnp.int32)
    cache = ModelCache()
    assert _of() == Call("causal") and not _of().paged
    assert _of(attn_mask=mask).mode == "masked"
    paged = dict(paged_cache=cache, page_ids=z, page_offsets=z)
    assert _of(attn_mask=mask, **paged).mode == "prefill"
    assert _of(page_table=table, attn_lengths=lanes, **paged).mode == "decode"
    tail = _of(page_table=table, prefix_starts=lanes, **paged)
    assert tail.mode == "tail" and tail.paged and tail.prefix_starts is lanes
    # packed rows: through the segment kernel where the model has one ...
    seg = jnp.asarray([[1, 1, 2, 0], [1, 2, 2, 2]], jnp.int32)
    kernel = _of(segment_kernel=True, segment_ids=seg, mtp=True)
    assert kernel.mode == "packed" and kernel.segment_ids is seg and kernel.attn_mask is None
    # ... and as a masked call under the dense segment mask where it has none
    dense = _of(segment_ids=seg)
    assert dense.mode == "masked" and dense.segment_ids is None
    want = (seg[:, :, None] == seg[:, None, :]) & (seg[:, :, None] > 0) & np.tril(np.ones((4, 4), bool))
    np.testing.assert_array_equal(dense.attn_mask, want)
    assert dense.runs is None and dense.real is None  # only a model whose lanes carry state reads them
    # a model whose lanes carry state is told which tokens are real and where runs start
    rec = _of(lane_state=True, segment_ids=seg)
    np.testing.assert_array_equal(rec.real, seg > 0)
    np.testing.assert_array_equal(rec.runs, run_ids(seg))
    prompt = mask & (jnp.arange(4)[None, None, :] < jnp.asarray([3, 4])[:, None, None])
    pre = _of(lane_state=True, attn_mask=prompt, state_lanes=lanes, **paged)
    assert pre.mode == "prefill" and pre.state_lanes is lanes
    np.testing.assert_array_equal(pre.real, [[1, 1, 1, 0], [1, 1, 1, 1]])
    np.testing.assert_array_equal(pre.runs, [[1, 1, 1, 1], [1, 1, 1, 1]])  # a pad rides the last run
    dec = _of(lane_state=True, page_table=table, attn_lengths=lanes, **paged)
    assert dec.mode == "decode" and dec.runs is None and dec.real is None
    with pytest.raises(dataclasses.FrozenInstanceError):
        dec.mode = "tail"  # decided once
