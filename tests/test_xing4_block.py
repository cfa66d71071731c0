"""The ``xing4`` block family of the token model (ISSUE 51): joyai's stack
(a leading dense layer, then latent attention and a sigmoid router beside a
shared expert, every expert held) on a residual STREAM OF ROWS, four a
token, mixed a sublayer by manifold-constrained hyper-connections
(:class:`_HyperMix`: a read mix, a write by a Sinkhorn-projected matrix and
a gate), with YaRN's rotary.

Every comparison is against ``benchmark/reference/xing4.py`` (plain
``jax.numpy``, float32 at ``highest``, a normalisation's sums by ``jnp.sum``
where the program takes a product with a constant matrix, a Python loop
over the iterations,
un-absorbed attention, a masked loop over the experts) and, for the
learner, ``reference/token_ppo.py``.  The model here is 1 dense + 5 routed
layers, hidden 48, 4 heads (q/k 8 + 8 rotary, v 8), ranks 24 and 16, a
dense FFN of 96, a router over 8 experts of width 32 with 3 a token, one
shared expert, 4 rows and 20 iterations, YaRN of factor 4 over 8 original
positions; float32 on both sides.  The hyper-connections' seeded draw is
the configuration's (gains in 0.5-1.5, a bias far from neutral), so that a
term left out fails: the planted faults at the end each miss by far more
than the tolerance.
"""

import dataclasses
import importlib.util
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from scalerl_tpu.agents.token_ppo import token_ppo_packed_loss
from scalerl_tpu.config import GenRLArguments, parse_args
from scalerl_tpu.genrl.continuous import ContinuousConfig, ContinuousEngine
from scalerl_tpu.genrl.rollout import pack_learner_batch
from scalerl_tpu.models.transformer import (
    Call,
    RopeScaling,
    TransformerPolicy,
    _Block,
    _HyperMix,
    _MixerBlock,
    _ShortcutBlock,
    block_spec,
    layer_specs,
    packed_attention_mask,
    prompt_attention_mask,
    rotary_fn,
    yarn_terms,
)
from scalerl_tpu.runtime import telemetry
from scalerl_tpu.trainer.sequence_rl import build_genrl_model

ROOT = Path(__file__).resolve().parents[1]
ATOL = 1e-4
V, D, H, L = 53, 48, 4, 6
E, K, F, N, ITERS = 8, 3, 32, 4, 20
YARN = dict(
    factor=4.0, original_max_position_embeddings=8, beta_fast=32.0, beta_slow=1.0,
    mscale=1.0, mscale_all_dim=1.0, type="yarn",
)
CFG = dict(
    vocab_size=V, hidden_size=D, num_hidden_layers=L, first_k_dense_replace=1,
    num_attention_heads=H, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
    qk_rope_head_dim=8, v_head_dim=8, intermediate_size=96, moe_intermediate_size=F,
    n_routed_experts=E, n_shared_experts=1, num_experts_per_tok=K, scoring_func="sigmoid",
    routed_scaling_factor=2.0, norm_topk_prob=True, rms_norm_eps=1e-6,
    rope_theta=10000.0, router_aux_loss_coef=0.0, hc_mult=N, hc_sinkhorn_iters=ITERS,
    hc_eps=1e-6, mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30, rope_scaling=YARN,
)
_SCALING = RopeScaling(4.0, 8, 32.0, 1.0, 1.0, 1.0)
_SIZES = dict(
    norm_eps=1e-6, rope_theta=10000.0, num_experts=E, experts_per_token=K,
    expert_width=F, norm_topk_prob=True, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8, ffn_hidden=96,
    routed_scaling=2.0, scoring="sigmoid", shared_experts=1, streams=N,
    hc_iters=ITERS, hc_eps=1e-6, hc_clamp=(-30.0, 30.0), rope_scaling=_SCALING,
)
# the planted faults a whole forward shows.  The fifth, rows before columns,
# shares the iteration's limit with the right order (a doubly stochastic
# matrix is both orders' fixed point), so 20 iterations in it differ by what
# is left of the convergence alone: it is held at the maps
# (``test_rows_before_columns_is_another_matrix``)
FAULTS = ("one_iteration", "identity_res", "plain_rope", "no_m2")


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"ref_{name}", ROOT / "benchmark" / "reference" / f"{name}.py"
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load("xing4")
ref_ppo = _load("token_ppo")
GEO = ref.geometry(CFG)


def _faulty(name):
    """The reference with one planted fault."""
    if name == "one_iteration":
        return ref.geometry(CFG, hc_iters=1)
    return ref.geometry(CFG, fault=name)


@pytest.fixture(autouse=True)
def _fresh_registry():
    telemetry.reset()
    yield
    telemetry.reset()


def _args(*extra, cfg=CFG):
    args = parse_args(
        GenRLArguments,
        ref.program_argv(cfg)
        + ["--prompt-len", "12", "--max-new-tokens", "12", "--logger-backend", "none",
           "--learner-packing", "true"]
        + list(extra),
    )
    args.validate()
    return args


def _thaw(tree):
    return {k: _thaw(v) if isinstance(v, dict) else v for k, v in tree.items()}


def _init(model, key=3):
    params = model.init(jax.random.PRNGKey(key), jnp.zeros((1, 2), jnp.int32))
    return _thaw(jax.tree_util.tree_map(np.asarray, jax.device_get(params)))


@pytest.fixture(scope="module")
def net():
    """The model as the program's arguments build it, and its seeded
    weights."""
    model = build_genrl_model(_args())
    return model, _init(model)


def _min_gap(routing):
    return min(float(jnp.min(gap)) for _s, _w, gap in routing)


# ---------------------------------------------------------------------------
# the arguments


def test_program_arguments_choose_the_family(net):
    model, params = net
    spec = block_spec("xing4", **_SIZES)
    assert model.block == spec and model.layers == layer_specs(spec, L, 1)
    assert spec.streams == N and spec.residual == "mhc4" and spec.rope_scaling == _SCALING
    assert spec.attention == "mla" and spec.scoring == "sigmoid" and spec.shared_experts == 1
    assert model.routed_layers == L - 1 and not model.lane_state and model.mtp_layers == 0
    p = params["params"]
    assert "ffn" in p["block_0"] and "experts" not in p["block_0"]
    for i in range(L):
        for name in ("attn_hc", "ffn_hc"):
            shapes = {k: v.shape for k, v in p[f"block_{i}"][name].items()}
            assert shapes == {
                "scale": (N * D,), "phi": (N * D, N * (N + 2)), "b": (N * (N + 2),), "alpha": (3,),
            }
            assert all(v.dtype == np.float32 for v in p[f"block_{i}"][name].values())
    # the seeded draw is far from neutral: gains in 0.5-1.5, a bias whose
    # matrix part leans on the diagonal
    hc = p["block_1"]["attn_hc"]
    assert np.all((hc["alpha"] >= 0.5) & (hc["alpha"] <= 1.5))
    mat = hc["b"][2 * N :].reshape(N, N)
    assert np.all(np.diag(mat) > 0.5) and float(np.std(hc["b"][: 2 * N])) > 0.3
    # the cache is joyai's: one latent pool a layer, nothing by lane
    cache = model.init_paged_cache(5, 4)
    assert len(cache.rows) == L and not (cache.k or cache.v or cache.ssm or cache.conv)


@pytest.mark.parametrize(
    "extra,match",
    [
        (("--block-family", "llama"), "eight families.*zaya \\| xing4"),
        (("--hc-mult", "1"), "hc_mult >= 2"),
        (("--hc-sinkhorn-iters", "0"), "hc_sinkhorn_iters"),
        (("--hc-clamp-min", "31"), "hc_clamp_min < hc_clamp_max"),
        (("--rope-factor", "0.5"), "rope_factor > 1"),
        (("--rope-beta-fast", "0.5"), "rope_beta_fast > rope_beta_slow"),
        (("--dense-layers", "7"), "dense_layers must lie in 0..n_layers"),
        (("--mtp-layers", "1"), "mtp_layers is the joyai family's"),
        (("--block-family", "joyai"), "xing4 family's"),
        (("--block-family", "gpt2"), "family's"),
    ],
)
def test_validate_refuses(extra, match):
    with pytest.raises(ValueError, match=match):
        _args(*extra)


@pytest.mark.parametrize(
    "extra",
    [
        ("--dense-layers", "2"),
        ("--dense-layers", "6"),  # up to the depth: a stack of dense layers alone
        ("--hc-mult", "2", "--hc-sinkhorn-iters", "3"),
        ("--rope-factor", "1.0"),  # plain rotary on a stream of rows
        ("--rope-factor", "64", "--rope-original-max", "4096"),
    ],
)
def test_validate_accepts(extra):
    args = _args(*extra)
    model = build_genrl_model(args)
    assert model.block.streams == args.hc_mult
    assert (model.block.rope_scaling is None) == (args.rope_factor == 1.0)
    assert sum(s.ffn == "swiglu" for s in model.layer_specs) == args.dense_layers


def test_other_families_take_neither_rows_nor_yarn():
    joyai = [a for a in ref.program_argv(CFG)]
    joyai[joyai.index("xing4")] = "joyai"
    with pytest.raises(ValueError, match="xing4 family's"):
        parse_args(GenRLArguments, joyai + ["--learner-packing", "true"]).validate()
    with pytest.raises(ValueError, match="residual stream of 2 rows"):
        block_spec("xing4", **{**_SIZES, "streams": 1})
    with pytest.raises(ValueError, match="YaRN needs"):
        block_spec("xing4", **{**_SIZES, "rope_scaling": RopeScaling(0.5, 8)})


@pytest.mark.parametrize("kind", ["mixer", "scmoe", "mtp"])
def test_what_knows_one_row_refuses_a_stream_by_name(kind):
    spec = block_spec("xing4", **_SIZES)
    x = jnp.zeros((1, 3, N, D))
    if kind == "mtp":
        model = TransformerPolicy(
            num_actions=V, vocab_size=V, d_model=D, num_heads=H, num_layers=2, block=spec, mtp_layers=1
        )
        with pytest.raises(ValueError, match="mtp_layers > 0 with a residual stream of more than one row"):
            model.init(jax.random.PRNGKey(0), jnp.zeros((1, 3), jnp.int32))
        return
    cls, layer = (_MixerBlock, "mixer") if kind == "mixer" else (_ShortcutBlock, "scmoe")
    block = cls(D, H, 4, None, spec=dataclasses.replace(spec, layer=layer, mixer="ffn"))
    with pytest.raises(ValueError, match=f"a {kind} layer takes a one-row residual stream"):
        block.init(jax.random.PRNGKey(0), x, Call("causal"))


# ---------------------------------------------------------------------------
# the hyper-connection alone


def _mix(dtype=jnp.float32, **kw):
    spec = block_spec("xing4", **{**_SIZES, **kw})
    return _HyperMix(spec, D, dtype=dtype), spec


def _stream(seed=0, B=2, T=5):
    return jax.random.normal(jax.random.PRNGKey(seed), (B, T, N, D), jnp.float32)


def test_hyper_mix_read_and_write_match_reference():
    mix, _spec = _mix()
    X = _stream()
    p = mix.init(jax.random.PRNGKey(1), X, method=mix.read)
    h, (post, m) = mix.apply(p, X, method=mix.read)
    pre_r, post_r, res_r = ref.hyper_maps(p["params"], X, GEO)
    np.testing.assert_allclose(h, ref.hyper_read(pre_r, X), atol=1e-5)
    np.testing.assert_allclose(post, post_r, atol=1e-6)
    np.testing.assert_allclose(m, res_r, atol=1e-6)
    y = jax.random.normal(jax.random.PRNGKey(2), (2, 5, D))
    out = mix.apply(p, X, y, (post, m), method=mix.write)
    np.testing.assert_allclose(out, ref.hyper_write(post_r, res_r, X, y), atol=1e-5)
    # doubly stochastic to the iteration's accuracy, and far from both the
    # identity and the uniform matrix
    m = np.asarray(m)
    # (the rows, normalised last, to rounding; the columns to what 20
    # iterations reach on this draw: the slowest token here is 3e-4 off)
    np.testing.assert_allclose(m.sum(axis=-1), 1.0, atol=1e-5)
    np.testing.assert_allclose(m.sum(axis=-2), 1.0, atol=1e-3)
    assert np.median(np.abs(m.sum(axis=-2) - 1.0)) < 1e-5
    assert np.all(m >= 0) and np.abs(m - np.eye(N)).max() > 0.2 and np.abs(m - 0.25).max() > 0.2
    # the maps depend on the input: another token, another matrix
    assert np.abs(m[0, 0] - m[1, 3]).max() > 0.02


def test_rows_before_columns_is_another_matrix():
    """The program normalises the columns first and the rows LAST: its
    rows sum to one to rounding and its columns to the iteration's
    accuracy.  The planted order (rows, then columns) has it the other way
    round, and few iterations tell the two apart by far."""
    X = _stream(4)
    for iters, least in ((ITERS, 1e-5), (3, 1e-3)):
        mix, _spec = _mix(hc_iters=iters)
        p = mix.init(jax.random.PRNGKey(1), X, method=mix.read)
        _h, (_post, m) = mix.apply(p, X, method=mix.read)
        got = np.asarray(m)
        geo = GEO._replace(hc_iters=iters)
        np.testing.assert_allclose(got, ref.hyper_maps(p["params"], X, geo)[2], atol=1e-6)
        wrong = np.asarray(ref.hyper_maps(p["params"], X, geo._replace(fault="rows_first"))[2])
        assert np.abs(wrong - got).max() > least
        rows, cols = np.abs(got.sum(-1) - 1).max(), np.abs(got.sum(-2) - 1).max()
        wrong_rows, wrong_cols = np.abs(wrong.sum(-1) - 1).max(), np.abs(wrong.sum(-2) - 1).max()
        assert rows < 3e-6 < cols and wrong_cols < 3e-6 < wrong_rows


def test_hyper_mix_maps_are_float32_under_a_bfloat16_stream():
    mix, _spec = _mix(jnp.bfloat16)
    X = _stream().astype(jnp.bfloat16)
    p = mix.init(jax.random.PRNGKey(1), X, method=mix.read)
    h, (post, m) = mix.apply(p, X, method=mix.read)
    assert h.dtype == jnp.bfloat16 and post.dtype == m.dtype == jnp.float32
    out = mix.apply(p, X, h, (post, m), method=mix.write)
    assert out.dtype == jnp.bfloat16 and out.shape == X.shape
    # float32 accumulation, rounded once: within a bfloat16 rounding of
    # the reference on the same (bfloat16) stream
    Xf = X.astype(jnp.float32)
    pre_r, post_r, res_r = ref.hyper_maps(p["params"], Xf, GEO)
    want = ref.hyper_write(post_r, res_r, Xf, h.astype(jnp.float32))
    np.testing.assert_allclose(out.astype(jnp.float32), want, rtol=2 ** -7, atol=2 ** -7)


def test_the_clamp_is_reached_by_a_planted_large_bias():
    """Four planted logits in a 2 x 2 block, 80 on its diagonal and 60
    off it.  The iteration forgets any scaling of a row or a column but
    not the block's cross-ratio: unclamped that is ``e^40`` and the block
    goes to the identity; under the clamp at 30 all four are ``e^30``, the
    ratio is 1 and the block is shared evenly.  The matrix is the
    reference's under the clamp at 30 and another under a clamp at 100."""
    mix, _spec = _mix()
    X = _stream(3)
    p = _thaw(jax.device_get(mix.init(jax.random.PRNGKey(1), X, method=mix.read)))
    b = np.asarray(p["params"]["b"]).copy().reshape(-1)
    res = b[2 * N :].reshape(N, N)
    res[0, 1] = res[1, 2] = 80.0
    res[0, 2] = res[1, 1] = 60.0
    p["params"]["b"] = jnp.asarray(np.concatenate([b[: 2 * N], res.reshape(-1)]))
    _h, (_post, m) = mix.apply(p, X, method=mix.read)
    got = np.asarray(m)
    np.testing.assert_allclose(got, ref.hyper_maps(p["params"], X, GEO)[2], atol=1e-6)
    loose = np.asarray(ref.hyper_maps(p["params"], X, GEO._replace(clamp=(-100.0, 100.0)))[2])
    assert np.isfinite(got).all() and np.isfinite(loose).all()
    np.testing.assert_allclose(got[..., :2, 1:3], 0.5, atol=0.05)
    np.testing.assert_allclose(loose[..., 0, 1], 1.0, atol=0.05)
    np.testing.assert_allclose(loose[..., 0, 2], 0.0, atol=0.05)


def test_the_iterations_are_unrolled_and_scoped():
    """No ``while`` for the 20 iterations, and the two named scopes in the
    lowered text (what ``benchmark/aot_xing4.py`` counts by)."""
    mix, _spec = _mix()
    X = _stream()
    p = mix.init(jax.random.PRNGKey(1), X, method=mix.read)
    text = jax.jit(lambda w, x: mix.apply(w, x, method=mix.read)).lower(p, X).as_text(debug_info=True)
    assert "while" not in text and "mhc_maps" in text and "mhc_mix" in text
    jaxpr = str(jax.make_jaxpr(lambda w, x: mix.apply(w, x, method=mix.read))(p, X))
    # a division and one product with a constant 0/1 matrix a normalisation
    # (and the norm's mean; the projection is the 41st product)
    assert jaxpr.count(" div ") in (2 * ITERS, 2 * ITERS + 1)
    assert jaxpr.count("dot_general") == 2 * ITERS + 1 and jaxpr.count("reduce_sum") <= 2


# ---------------------------------------------------------------------------
# YaRN


def test_yarn_numbers_at_the_published_sizes():
    """``low`` 10, ``high`` 23, the softmax scale 0.14468, and the blended
    frequencies against the formula by hand."""
    scaling = RopeScaling(64.0, 4096, 32.0, 1.0, 1.0, 1.0)
    terms = yarn_terms(scaling, 64, 10000.0)
    assert (terms.low, terms.high) == (10, 23) and terms.amplitude == 1.0
    m = 0.1 * math.log(64.0) + 1.0
    np.testing.assert_allclose(terms.softmax_factor / math.sqrt(192), 0.14468, atol=5e-6)
    np.testing.assert_allclose(terms.softmax_factor, m * m, rtol=1e-12)
    f = np.array([10000.0 ** (-2 * i / 64) for i in range(32)])
    np.testing.assert_allclose(terms.inv_freq[:11], f[:11], rtol=1e-12)  # fast pairs: kept
    np.testing.assert_allclose(terms.inv_freq[23:], f[23:] / 64, rtol=1e-12)  # slow: interpolated
    np.testing.assert_allclose(terms.inv_freq[15], f[15] * (1 - 5 / 13) + f[15] / 64 * (5 / 13), rtol=1e-12)
    inv_freq, low, high, amplitude, m_all = ref.yarn_numbers(64, 10000.0, ref.Yarn(64.0, 4096, 32.0, 1.0, 1.0, 1.0))
    assert (low, high, amplitude) == (10, 23, 1.0)
    np.testing.assert_allclose(inv_freq, terms.inv_freq, rtol=1e-6)
    np.testing.assert_allclose(m_all ** 2, terms.softmax_factor, rtol=1e-12)
    # an amplitude other than one where the two mscales differ
    other = yarn_terms(RopeScaling(64.0, 4096, 32.0, 1.0, 1.0, 0.5), 64, 10000.0)
    np.testing.assert_allclose(other.amplitude, m / (0.05 * math.log(64.0) + 1.0), rtol=1e-12)


def test_rotary_fn_without_scaling_is_the_plain_rotary():
    pos = jnp.arange(40)[None]
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 40, 2, 8))
    plain = rotary_fn(pos, 8, 10000.0, "interleaved")(x)
    same = rotary_fn(pos, 8, 10000.0, "interleaved", None)(x)
    np.testing.assert_array_equal(plain, same)
    scaled = rotary_fn(pos, 8, 10000.0, "interleaved", _SCALING)(x)
    # position 0 turns nothing; late positions turn differently
    np.testing.assert_allclose(scaled[:, 0], plain[:, 0], atol=1e-6)
    assert float(jnp.abs(scaled[:, 30:] - plain[:, 30:]).max()) > 0.1


def test_attention_scores_past_the_original_context_match_reference(net):
    """The stack at positions far past ``L0`` = 8 and ``L0 x s`` = 32 and
    over distances past both: the scaled frequencies and ``m^2`` on the
    scale, against the reference; the plain rotary and the plain scale are
    other numbers there."""
    model, params = net
    tokens = jnp.asarray(np.random.default_rng(2).integers(0, V, (1, 48)), jnp.int32)
    positions = jnp.arange(100, 148)[None]
    mask = jnp.tril(jnp.ones((48, 48), bool))[None]
    out = model.apply(params, tokens, positions=positions, attn_mask=mask)
    logits, _values, _routing = ref.forward(params, tokens, GEO, positions, mask)
    np.testing.assert_allclose(out.policy_logits, logits, atol=ATOL)
    for fault in ("plain_rope", "no_m2"):
        other = ref.forward(params, tokens, _faulty(fault), positions, mask)[0]
        assert float(jnp.abs(other - logits).max()) > 100 * ATOL, fault


# ---------------------------------------------------------------------------
# layers and the stack, in every form without a cache


def test_one_layer_matches_reference(net):
    """A routed layer alone on a stream of distinct rows (the stack's
    first layer sees four equal ones)."""
    model, params = net
    spec = model.block
    X = _stream(5, B=1, T=9)
    pos = jnp.arange(9)[None]
    block = _Block(
        D, H, 4, lambda q, k, v: None, spec=spec,
        rotary=rotary_fn(pos, 8, 10000.0, "interleaved", _SCALING),
    )
    mask = jnp.tril(jnp.ones((9, 9), bool))[None]
    call = Call("masked", attn_mask=mask)
    p = {"params": params["params"]["block_2"]}
    out, _cache, _r = block.apply(p, X, call)
    want, _s, _w, gap = ref.layer(params["params"]["block_2"], X, pos, mask, GEO)
    assert float(jnp.min(gap)) > 1e-5
    np.testing.assert_allclose(out, want, atol=ATOL)
    dense, _c, _r = block.clone(spec=model.layers[0]).apply({"params": params["params"]["block_0"]}, X, call)
    np.testing.assert_allclose(
        dense, ref.layer(params["params"]["block_0"], X, pos, mask, GEO)[0], atol=ATOL
    )


def test_full_forward_matches_reference(net):
    model, params = net
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, V, (2, 300)), jnp.int32)
    out = model.apply(params, tokens)  # 600 tokens: the sorted form
    logits, values, routing = ref.forward(params, tokens, GEO)
    assert _min_gap(routing) > 1e-6 and len(routing) == L - 1
    np.testing.assert_allclose(out.policy_logits, logits, atol=ATOL)
    np.testing.assert_allclose(out.baseline, values, atol=ATOL)
    short = tokens[:, :40]  # 80 tokens: the streamed form
    np.testing.assert_allclose(
        model.apply(params, short).policy_logits, ref.forward(params, short, GEO)[0], atol=ATOL
    )
    assert 0.3 < float(jnp.std(out.policy_logits)) < 3.0
    # what the tolerance refuses: the reference itself at float8 operands,
    # and at bfloat16 maps it is another number too
    low = ref.forward(params, tokens, ref.geometry(CFG, round_to="float8_e4m3fn"))
    assert float(jnp.median(jnp.abs(low[0] - logits))) > 100 * ATOL
    maps = ref.forward(params, short, ref.geometry(CFG, map_round_to="bfloat16"))
    assert float(jnp.median(jnp.abs(maps[0] - logits[:, :40]))) > ATOL


def test_right_padded_rows_match_reference(net):
    """The prefill's mask over right-padded prompts: a real token's logits
    are those of the prompt alone."""
    model, params = net
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, V, (3, 16)), jnp.int32)
    lengths = jnp.asarray([16, 9, 3])
    mask = prompt_attention_mask(lengths, 16)
    out = model.apply(params, tokens, attn_mask=mask)
    for b, n in enumerate([16, 9, 3]):
        alone = ref.forward(params, tokens[b : b + 1, :n], GEO)
        np.testing.assert_allclose(out.policy_logits[b, :n], alone[0][0], atol=ATOL)
        np.testing.assert_allclose(out.baseline[b, :n], alone[1][0], atol=ATOL)


def _rows(seed, lengths, S):
    rng = np.random.default_rng(seed)
    tok = np.zeros((1, S), np.int32)
    seg = np.zeros((1, S), np.int32)
    pos = np.zeros((1, S), np.int32)
    off = 0
    for i, n in enumerate(lengths, start=1):
        tok[0, off : off + n] = rng.integers(0, V, n)
        seg[0, off : off + n] = i
        pos[0, off : off + n] = np.arange(n)
        off += n
    return jnp.asarray(tok), jnp.asarray(seg), jnp.asarray(pos)


@pytest.mark.parametrize("kernel", ["dense", "segment_flash"])
def test_packed_rows_match_reference(net, kernel):
    """Packed rows with several runs a row and per-segment positions: the
    dense packed mask, and the flash segment kernel in interpret mode,
    which is handed YaRN's scale.  A segment's logits are those of the
    sequence alone."""
    model, params = net
    tok, seg, pos = _rows(4, [9, 14, 6], 32)
    if kernel == "segment_flash":
        from scalerl_tpu.ops.pallas_attention import segment_flash_attention

        seen = []

        def kern(q, k, v, s, scale=None):
            seen.append(scale)
            return segment_flash_attention(q, k, v, s, scale=scale)

        model = model.clone(segment_attn_fn=kern)
    out = model.apply(params, tok, positions=pos, segment_ids=seg)
    logits, values, _routing = ref.forward(
        params, tok, GEO, positions=pos, mask=packed_attention_mask(seg)
    )
    real = np.asarray(seg)[0] > 0
    np.testing.assert_allclose(out.policy_logits[0][real], logits[0][real], atol=ATOL)
    np.testing.assert_allclose(out.baseline[0][real], values[0][real], atol=ATOL)
    alone = ref.forward(params, tok[:, 9:23], GEO)  # the middle segment
    np.testing.assert_allclose(out.policy_logits[0, 9:23], alone[0][0], atol=ATOL)
    if kernel == "segment_flash":
        want = ref.softmax_scale(GEO)
        assert len(seen) == L and all(abs(s - want) < 1e-9 for s in seen)


def test_causal_form_matches_reference(net):
    """The whole-trajectory form, whose ``attn_fn`` takes no scale: YaRN's
    factor rides in q."""
    model, params = net
    tokens = jnp.asarray(np.random.default_rng(7).integers(0, V, (2, 20)), jnp.int32)
    out = model.apply(params, tokens)
    masked = model.apply(
        params, tokens, attn_mask=jnp.broadcast_to(jnp.tril(jnp.ones((20, 20), bool)), (2, 20, 20))
    )
    np.testing.assert_allclose(out.policy_logits, masked.policy_logits, atol=ATOL)


def test_the_cut_is_the_deeper_stack_without_its_second_dense_layer():
    """The stack test that stands where a share test would (every expert
    is held): the 1 + 5 cut equals the first six layers of a deeper
    reference stack whose first TWO layers are dense, as published, WITH
    THE SECOND DENSE LAYER REMOVED: the cut's weights are the deeper
    stack's ``block_0, block_2 .. block_6`` renumbered, and the program on
    them gives what the reference gives running those blocks of the deeper
    tree."""
    deep_cfg = {**CFG, "num_hidden_layers": 8, "first_k_dense_replace": 2}
    deep = build_genrl_model(_args(cfg=deep_cfg))
    deep_params = _init(deep, key=9)
    assert [s.ffn for s in deep.layer_specs] == ["swiglu"] * 2 + ["experts"] * 6
    kept = [0, 2, 3, 4, 5, 6]
    cut = {k: v for k, v in deep_params["params"].items() if not k.startswith("block_")}
    for new, old in enumerate(kept):
        cut[f"block_{new}"] = deep_params["params"][f"block_{old}"]
    model = build_genrl_model(_args())
    tokens = jnp.asarray(np.random.default_rng(3).integers(0, V, (2, 30)), jnp.int32)
    out = model.apply({"params": cut}, tokens)
    logits, values, routing = ref.forward(deep_params, tokens, GEO, blocks=kept)
    assert len(routing) == 5 and _min_gap(routing) > 1e-6
    np.testing.assert_allclose(out.policy_logits, logits, atol=ATOL)
    np.testing.assert_allclose(out.baseline, values, atol=ATOL)
    # and it is not the deeper stack's first six layers as they stand
    as_published = ref.forward(deep_params, tokens, GEO, blocks=range(6))[0]
    assert float(jnp.abs(as_published - logits).max()) > 100 * ATOL


# ---------------------------------------------------------------------------
# the engine


def _engine(model, params, **kw):
    cfg = dict(
        vocab_size=V, max_prompt_len=12, max_new_tokens=12, temperature=1.0,
        seed=5, lanes=8, page_size=4, steps_per_macro=3, steps_in_flight=2,
        prefix_cache=True,
    )
    cfg.update(kw)
    return ContinuousEngine(model, params, ContinuousConfig(**cfg))


def _check_against_reference(params, completions, geo=GEO, atol=ATOL):
    for c in completions:
        m, r = int(c.prompt_len), len(c.response_tokens)
        toks = np.concatenate([c.prompt[:m], c.response_tokens])[None]
        logp, values, gaps = ref.token_logprobs(params, toks, geo)
        assert float(jnp.min(gaps)) > 1e-6
        np.testing.assert_allclose(
            c.behavior_logp, np.asarray(logp)[0, m - 1 : m + r - 1], atol=atol
        )
        np.testing.assert_allclose(
            c.values, np.asarray(values)[0, m - 1 : m + r - 1], atol=atol
        )


@pytest.fixture(scope="module", params=["xla", "pallas"])
def rollout(request, net):
    """Un-absorbed local prefill, then ABSORBED decode through the latent
    cache (the XLA twin, and the kernel in interpret mode), a forked group
    sharing its prompt's pages, and a second admission over a cached
    prefix (the tail prefill): the completions and the engine's stats."""
    telemetry.reset()
    model, params = net
    engine = _engine(model, params, paged_attn=request.param)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, V, 10).astype(np.int32)  # 2 full pages + a partial one
    assert engine.submit_group(prompt, 4, 10, tag=0)
    assert engine.submit(rng.integers(0, V, 7).astype(np.int32), 7, tag=1)
    done = engine.run_until(5)
    again = np.concatenate([prompt[:8], rng.integers(0, V, 3)]).astype(np.int32)
    assert engine.submit(again, len(again), tag=2)
    done += engine.run_until(1)
    return done, engine.stats(), engine.prefix_tokens_saved


def test_engine_prefill_decode_fork_and_prefix_hit_match_reference(net, rollout):
    _model, params = net
    done, _stats, saved = rollout
    assert len(done) == 6 and all(len(c.response_tokens) == 12 for c in done)
    assert saved >= 8  # the tail prefill over cached latent rows ran
    assert len({c.tag for c in done}) == 3 and sum(c.tag == 0 for c in done) == 4  # the fork
    _check_against_reference(params, done)


def test_engine_counters(rollout):
    """Every expert is held: ``held = k x tokens x routed layers``, absent
    and zero-compute picks none; a stream of rows is no lane state, so the
    prefix cache stayed on."""
    done, stats, _saved = rollout
    decoded = sum(len(c.response_tokens) for c in done)
    assert stats["expert_tokens"].shape == (L - 1, E)
    np.testing.assert_array_equal(stats["expert_tokens"].sum(axis=1), [K * decoded] * (L - 1))
    assert stats["held_expert_tokens"] == K * decoded * (L - 1)
    assert stats["absent_expert_tokens"] == 0 and stats["zero_expert_tokens"] == 0
    assert stats.get("state_bytes_per_lane", 0) == 0
    assert stats.get("prefix_skipped_recurrent", 0) == 0


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_faults_fail_through_the_engine(net, rollout, fault):
    """Each planted fault in the reference misses the engine's recorded
    log-probabilities by far more than the tolerance."""
    _model, params = net
    done, _stats, _saved = rollout
    c = max(done, key=lambda c: c.prompt_len)
    m, r = int(c.prompt_len), len(c.response_tokens)
    toks = np.concatenate([c.prompt[:m], c.response_tokens])[None]
    logp, _values, _gaps = ref.token_logprobs(params, toks, _faulty(fault))
    err = np.abs(c.behavior_logp - np.asarray(logp)[0, m - 1 : m + r - 1])
    assert float(err.max()) > 20 * ATOL, (fault, float(err.max()))


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_faults_fail_on_a_whole_forward(net, fault):
    model, params = net
    tokens = jnp.asarray(np.random.default_rng(8).integers(0, V, (2, 40)), jnp.int32)
    out = model.apply(params, tokens)
    sound = ref.forward(params, tokens, GEO)[0]
    faulty = ref.forward(params, tokens, _faulty(fault))[0]
    np.testing.assert_allclose(out.policy_logits, sound, atol=ATOL)
    assert float(jnp.abs(faulty - out.policy_logits).max()) > 50 * ATOL, fault
    assert float(jnp.median(jnp.abs(faulty - out.policy_logits))) > 5 * ATOL, fault


def test_two_iterations_are_not_twenty(net):
    """The chip cell's control: a reference with 2 Sinkhorn iterations in
    place of 20 is another model by far more than float32 rounding."""
    _model, params = net
    tokens = jnp.asarray(np.random.default_rng(8).integers(0, V, (2, 40)), jnp.int32)
    sound = ref.forward(params, tokens, GEO)[0]
    two = ref.forward(params, tokens, ref.geometry(CFG, hc_iters=2))[0]
    assert float(jnp.median(jnp.abs(two - sound))) > 5 * ATOL


# ---------------------------------------------------------------------------
# the learner


_HYPER = dict(clip_range=0.2, value_cost=0.5, entropy_cost=0.01, kl_cost=0.0, adv_norm=True)
_REF_HYPER = {**_HYPER, "router_aux_loss_coef": 0.0}


def _sequences(seed, n, P=8, R=8):
    rng = np.random.default_rng(seed)
    plens, rlens = rng.integers(2, P + 1, n), rng.integers(3, R + 1, n)
    return dict(
        prompts=[rng.integers(0, V, a).astype(np.int32) for a in plens],
        resps=[rng.integers(0, V, b).astype(np.int32) for b in rlens],
        logps=[np.log(rng.uniform(0.05, 0.5, b)).astype(np.float32) for b in rlens],
        vals=[rng.normal(0, 0.1, b).astype(np.float32) for b in rlens],
        rewards=rng.uniform(0, 1, n).astype(np.float32),
        gens=np.zeros(n, np.int32),
    )


def _packed(seqs, S=16):
    pk = pack_learner_batch(
        seqs["prompts"], seqs["resps"], seqs["logps"], seqs["vals"],
        seqs["rewards"], seqs["gens"], pack_len=S,
    )
    fields, _prios = pk.fields()
    return {k: jnp.asarray(v) for k, v in fields.items()}, pk


def _one(seqs, i=0):
    a, b = len(seqs["prompts"][i]), len(seqs["resps"][i])
    return {
        "tokens": jnp.asarray(np.concatenate([seqs["prompts"][i], seqs["resps"][i]])),
        "mask": jnp.asarray(np.r_[np.zeros(a), np.ones(b)], jnp.float32),
        "behavior_logp": jnp.asarray(np.r_[np.zeros(a), seqs["logps"][i]], jnp.float32),
        "value": jnp.asarray(np.r_[np.zeros(a), seqs["vals"][i]], jnp.float32),
        "reward": jnp.full((a + b,), seqs["rewards"][i], jnp.float32),
    }


def test_packed_learner_loss_and_gradients_match_reference(net):
    """The PACKED learner on one sequence against the reference's loss and
    ``jax.grad`` of it, through the 20 iterations; the gradient reaches
    every hyper-connection parameter."""
    model, params = net
    seqs = _sequences(6, 1)
    packed, _pk = _packed(seqs, S=16)
    seq = _one(seqs)
    assert _min_gap(ref.forward(params, seq["tokens"][None], GEO)[2]) > 1e-6
    (total, metrics), grads = jax.jit(jax.value_and_grad(
        lambda w: token_ppo_packed_loss(w, w, model, packed, **_HYPER), has_aux=True
    ))(params)
    (want, parts), want_grads = jax.jit(jax.value_and_grad(
        lambda w: ref.ppo_loss(ref_ppo, w, w, seq, GEO, _REF_HYPER), has_aux=True
    ))(params)
    np.testing.assert_allclose(float(total), float(want), atol=1e-5)
    for key in ("pg_loss", "value_loss", "entropy"):
        np.testing.assert_allclose(float(metrics[key]), float(parts[key]), atol=1e-5)
    got, _ = ravel_pytree(grads)
    exp, _ = ravel_pytree(want_grads)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), atol=2e-5, rtol=1e-3)
    for i in (0, 3, L - 1):
        for name in ("attn_hc", "ffn_hc"):
            for leaf in ("phi", "b", "alpha", "scale"):
                g = np.asarray(grads["params"][f"block_{i}"][name][leaf])
                assert np.abs(g).max() > 1e-7, (i, name, leaf)
    # a fault in the iteration is a fault in the gradient
    _v, one = jax.jit(jax.value_and_grad(
        lambda w: ref.ppo_loss(ref_ppo, w, w, seq, _faulty("one_iteration"), _REF_HYPER), has_aux=True
    ))(params)
    flat, _ = ravel_pytree(one)
    assert float(jnp.max(jnp.abs(flat - got))) > 1e-3
    # all picks are of held experts
    real = float(jnp.sum(packed["segment_ids"] > 0))
    assert float(metrics["moe_held_picks"]) == K * (L - 1) * real
    assert float(metrics["moe_absent_picks"]) == 0


def test_normal_entry_point_generates_and_learns(tmp_path):
    """``--block-family xing4`` through ``SequenceRLTrainer``'s normal
    entry point at a small size: generation rounds on the continuous
    engine (prefix cache and fork on) and packed learn steps, finite
    losses."""
    from scalerl_tpu.trainer.sequence_rl import SequenceRLTrainer

    small = {**CFG, "num_hidden_layers": 3}
    args = _args(
        "--samples-per-prompt", "4", "--genrl-lanes", "8",
        "--work-dir", str(tmp_path), "--platform", "cpu", cfg=small,
    )
    trainer = SequenceRLTrainer(args)
    m1 = trainer.train_round()
    m2 = trainer.train_round()
    assert np.isfinite(m1["total_loss"]) and np.isfinite(m2["total_loss"])
    assert m2["moe_held_picks"] > 0 and m2["moe_absent_picks"] == 0
    stats = trainer.engine.stats()
    assert stats["completed"] > 0 and stats["held_expert_tokens"] > 0
    assert stats["absent_expert_tokens"] == 0


def test_token_ppo_agent_takes_the_family():
    """``TokenPPOAgent`` builds and learns on the family by the same entry
    point as the other seven, without the MTP module."""
    from scalerl_tpu.agents.token_ppo import TokenPPOAgent

    small = {**CFG, "num_hidden_layers": 2}
    args = _args("--learner-pack-len", "32", cfg=small)
    model = build_genrl_model(args)
    agent = TokenPPOAgent(args, model=model)
    packed, _pk = _packed(_sequences(5, 3), S=32)
    before = np.asarray(agent.state.params["params"]["block_1"]["attn_hc"]["phi"]).copy()
    metrics = agent.learn(packed)
    assert np.isfinite(float(metrics["total_loss"]))
    after = np.asarray(agent.state.params["params"]["block_1"]["attn_hc"]["phi"])
    assert np.abs(after - before).max() > 0  # the step trains the hyper-connections
    assert "mtp" not in agent.state.params["params"]


# ---------------------------------------------------------------------------
# what a trace says


def test_the_stack_says_what_it_is_once_a_traced_shape(net):
    """``model.layers`` with its ``residual`` attr, ``mhc.form`` and
    ``rope.form``: zero-length program spans once a traced shape."""
    from scalerl_tpu.models import transformer
    from scalerl_tpu.runtime import tracing

    model, params = net
    for note in (transformer._note_layers, transformer._note_mhc_form, transformer._note_rope_form):
        note.cache_clear()
    seen = []
    real = tracing.span

    def spy(name, **attrs):
        seen.append((name, attrs))
        return real(name, **attrs)

    tracing.span, keep = spy, tracing.span
    try:
        tokens = jnp.zeros((1, 6), jnp.int32)
        model.apply(params, tokens)
        model.apply(params, tokens)
        tok, seg, pos = _rows(4, [3, 2], 6)
        model.apply(params, tok, positions=pos, segment_ids=seg)
    finally:
        tracing.span = keep
    by = lambda name: [attrs for n, attrs in seen if n == name]  # noqa: E731
    layers, mhc, rope = by("model.layers"), by("mhc.form"), by("rope.form")
    assert len(layers) == 1 and len(rope) == 1 and len(mhc) == 2
    assert layers[0]["layers"] == ["plain/swiglu"] + ["plain/experts"] * (L - 1)
    assert layers[0]["residual"] == "mhc4" and layers[0]["mtp_layers"] == 0
    assert (layers[0]["attention"], layers[0]["held"], layers[0]["num_experts"]) == ("mla", E, E)
    assert mhc[0] == dict(
        kind="model", shape=[1, 6, N, D], streams=N, iters=ITERS, eps=1e-6,
        clamp=[-30.0, 30.0], map_dtype="float32", stream_dtype="float32",
        sublayers=2 * L, path="whole",
    )
    assert mhc[1]["path"] == "packed"
    terms = yarn_terms(_SCALING, 8, 10000.0)
    assert rope[0]["scaling"] == "yarn" and rope[0]["factor"] == 4.0
    assert (rope[0]["low"], rope[0]["high"]) == (terms.low, terms.high) == (0, 1)
    np.testing.assert_allclose(rope[0]["softmax_scale"], ref.softmax_scale(GEO), rtol=1e-9)


def test_a_one_row_stack_notes_a_plain_residual():
    from scalerl_tpu.models import transformer
    from scalerl_tpu.runtime import tracing
    from tests.tiny_families import MODELS

    seen = []
    real = tracing.span

    def spy(name, **attrs):
        seen.append((name, attrs))
        return real(name, **attrs)

    transformer._note_layers.cache_clear()
    tracing.span, keep = spy, tracing.span
    try:
        for name in ("gpt2", "zaya"):
            model = MODELS[name]
            tokens = jnp.zeros((1, 5), jnp.int32)
            model.apply(model.init(jax.random.PRNGKey(0), tokens), tokens)
    finally:
        tracing.span = keep
    notes = [attrs["residual"] for n, attrs in seen if n == "model.layers"]
    assert notes == ["add", "scaled"]
    assert not [n for n, _a in seen if n in ("mhc.form", "rope.form")]
