"""The ``qwen3_next`` block family of the token model (ISSUE 42): plain
layers (mixer, then routed FFN) whose mixer is a Gated DeltaNet at three
layers in four and gated attention at the fourth, the delta rule's matrix
state kept by lane beside the KV pages as a Mamba layer's is, attention
with an output gate carried by the query projection, an RMSNorm over each
head of q and k and a rotary over a head's first quarter, zero-centred
RMSNorm scales, and a softmax router over a held share of the experts
beside a shared expert behind a sigmoid scalar.

Every comparison is against ``benchmark/reference/qwen3_next.py`` (plain
``jax.numpy``, float32 at ``highest``, the delta rule ONE TOKEN AT A TIME
in its literal order, key/value heads repeated, a masked loop over the held
experts) and, for the learner, ``reference/token_ppo.py``.  The model here
is ``L L L F L`` (an interval of 4 over 5 layers): hidden 32, 4 value heads
of 8 over 2 key heads of 8 and a chunk of 8 (so that every sequence below
crosses chunk boundaries at lengths that are no multiple of it), 4 query
heads over 2 key/value heads of 16 of which the first 4 features rotate, a
router over 8 experts of width 16 with 3 a token, of which experts 0-3 are
held, a shared expert of width 16; float32 on both sides, every
zero-centred scale moved off zero by a seeded tenth.  At that size and
precision the two sides agree to about 3e-5 (the chunked form solves a
chunk's triangular system where the recurrence writes token by token),
while the smallest gap between a kept and a left-out router probability
is about 1e-3, so a routing flip cannot happen and the tolerance is 1e-4;
each routed case asserts that gap rather than trust it.  A reference whose
matmul operands are rounded to float8 misses these by three orders of
magnitude, a state taken at a prompt bucket's end instead of the prompt's
true length by as much.
"""

import importlib.util
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
from scalerl_tpu.models.routed_ffn import RoutedExperts
from scalerl_tpu.models.transformer import (
    ModelCache,
    TransformerPolicy,
    block_spec,
    fork_cache,
    gated_delta_chunked,
    gdn_decode_update,
    interval_specs,
    prompt_attention_mask,
    run_ids,
)
from scalerl_tpu.ops.pallas_paged_attention import (
    paged_attention_reference,
    paged_decode_attention,
)
from scalerl_tpu.runtime import telemetry
from scalerl_tpu.trainer.sequence_rl import build_genrl_model

ROOT = Path(__file__).resolve().parents[1]
ATOL = 1e-4  # see the module docstring
LAYERS, INTERVAL = 5, 4
V, D, H, KV, DH, ROT = 53, 32, 4, 2, 16, 4
GH, GP, GN, GG, CHUNK = 4, 8, 8, 2, 8  # value heads, value size, key size, key heads
E, HELD, K, F = 8, 4, 3, 16
CFG = dict(
    vocab_size=V, hidden_size=D, num_hidden_layers=LAYERS, full_attention_interval=INTERVAL,
    num_attention_heads=H, num_key_value_heads=KV, head_dim=DH, partial_rotary_factor=ROT / DH,
    rope_theta=1e7, rms_norm_eps=1e-6, linear_num_value_heads=GH, linear_value_head_dim=GP,
    linear_key_head_dim=GN, linear_num_key_heads=GG, linear_conv_kernel_dim=4,
    gdn_chunk_size=CHUNK, moe_intermediate_size=F, shared_expert_intermediate_size=F,
    num_experts_published=E, num_experts=HELD, first_expert=0, num_experts_per_tok=K,
    norm_topk_prob=True, router_aux_loss_coef=0.0,
)
GDN_LAYERS = [i for i in range(LAYERS) if (i + 1) % INTERVAL]


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"ref_{name}", ROOT / "benchmark" / "reference" / f"{name}.py"
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load("qwen3_next")
ref_ppo = _load("token_ppo")
GEO = ref.geometry(CFG)


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


@pytest.fixture(scope="module")
def net():
    """The model as the program's arguments build it, and its weights with
    every norm scale moved by a seeded tenth (a zero-centred scale that
    stayed at its zero would not show a ``1 + w`` read as ``w``)."""
    model = build_genrl_model(_args())
    params = model.init(jax.random.PRNGKey(3), jnp.zeros((1, 2), jnp.int32))
    params = _thaw(jax.tree_util.tree_map(np.asarray, jax.device_get(params)))
    rng = np.random.default_rng(11)

    def move(tree):
        for name, leaf in tree.items():
            if isinstance(leaf, dict):
                move(leaf)
            elif name in ("scale", "norm_scale"):
                tree[name] = np.asarray(leaf + 0.1 * rng.normal(size=leaf.shape), np.float32)

    move(params)
    return model, params


def _min_gap(routing):
    return min(float(jnp.min(gap)) for _s, _w, gap in routing)


def test_program_arguments_choose_the_family(net):
    model, params = net
    spec = model.block
    assert spec == block_spec(
        "qwen3_next", head_dim=DH, norm_eps=1e-6, rope_theta=1e7, num_experts=E,
        experts_per_token=K, expert_width=F, norm_topk_prob=True, experts_held=HELD,
        shared_experts=1, shared_width=F, kv_heads=KV, ssm_heads=GH, ssm_head_dim=GP,
        ssm_state=GN, ssm_groups=GG, ssm_conv=4, ssm_chunk=CHUNK, rotary_dim=ROT,
    )
    assert (spec.qk_norm, spec.attn_gate, spec.shared_gate, spec.norm_zero_centered) == (
        "head", True, True, True,
    )
    assert model.layers == interval_specs(spec, LAYERS, INTERVAL)
    assert [s.mixer for s in model.layers] == ["gdn", "gdn", "gdn", "attention", "gdn"]
    assert model.recurrent and model.routed_layers == LAYERS
    assert spec.state_shape == (GH, GN, GP) and spec.conv_channels == 2 * GG * GN + GH * GP
    p = params["params"]
    mixer = p["block_0"]["mixer"]
    assert {k: np.shape(v) for k, v in mixer.items() if not isinstance(v, dict)} == {
        "A_log": (GH,), "dt_bias": (GH,), "conv_w": (4, 2 * GG * GN + GH * GP), "norm_scale": (GP,),
    }
    assert mixer["in_proj"]["kernel"].shape == (D, 2 * GG * GN + 2 * GH * GP)
    assert mixer["ba_proj"]["kernel"].shape == (D, 2 * GH)
    assert mixer["out_proj"]["kernel"].shape == (GH * GP, D)
    attn = p["block_3"]
    assert attn["q"]["kernel"].shape == (D, 2 * H * DH)  # a head [q | gate]
    assert attn["kv"]["kernel"].shape == (D, 2 * KV * DH)
    assert attn["q_norm"]["scale"].shape == attn["k_norm"]["scale"].shape == (DH,)
    assert p["block_0"]["shared_gate"]["kernel"].shape == (D, 1)
    assert p["block_0"]["experts"]["router"].shape == (D, E)
    assert p["block_0"]["experts"]["w_gate"].shape == (HELD, D, F)
    # the zero-centred scales start at zero, the gated norm's at one
    fresh = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32))["params"]
    assert not np.any(np.asarray(fresh["final_norm"]["scale"]))
    assert not np.any(np.asarray(fresh["block_3"]["q_norm"]["scale"]))
    assert np.all(np.asarray(fresh["block_0"]["mixer"]["norm_scale"]) == 1.0)
    cache = model.init_paged_cache(9, 4, lanes=3)
    assert isinstance(cache, ModelCache) and len(cache.k) == 1 and len(cache.ssm) == 4
    assert cache.k[0].shape == (9, 4, KV * DH)
    assert cache.ssm[0].shape == (3, GH, GN, GP) and cache.conv[0].shape == (3, 3, 2 * GG * GN + GH * GP)


def test_arguments_the_family_refuses():
    with pytest.raises(ValueError, match="full_attention_interval in"):
        _args(cfg={**CFG, "full_attention_interval": 0})
    with pytest.raises(ValueError, match="carries lane state .* no cursor to rewind"):
        _args("--spec-enable", "true")
    with pytest.raises(ValueError, match="qwen3_next family's"):
        parse_args(GenRLArguments, ["--full-attention-interval", "4"]).validate()
    with pytest.raises(ValueError, match="qwen3_next family's"):
        parse_args(GenRLArguments, ["--block-family", "olmoe", "--rotary-dim", "4"]).validate()
    with pytest.raises(ValueError, match="one of the eight families"):
        parse_args(GenRLArguments, ["--block-family", "qwen4"]).validate()
    with pytest.raises(ValueError, match="gpt2 \\| olmoe \\| longcat \\| joyai \\| nemotron_h \\| qwen3_next"):
        block_spec("qwen4")
    with pytest.raises(ValueError, match="value heads a multiple of the key heads"):
        block_spec("qwen3_next", head_dim=8, num_experts=4, experts_per_token=2, expert_width=8,
                   ssm_heads=3, ssm_head_dim=4, ssm_state=4, ssm_groups=2)
    with pytest.raises(ValueError, match="an even rotary_dim"):
        block_spec("qwen3_next", head_dim=8, num_experts=4, experts_per_token=2, expert_width=8,
                   ssm_heads=2, ssm_head_dim=4, ssm_state=4, ssm_groups=2, rotary_dim=3)
    with pytest.raises(ValueError, match="over a plain-layer spec"):
        interval_specs(block_spec("gpt2"), 4, 4)
    # an interval of 1 is all attention: nothing recurrent, speculation allowed
    _args("--spec-enable", "true", cfg={**CFG, "full_attention_interval": 1})


def test_full_forward_matches_reference(net):
    """37 tokens a row: four chunks of 8 and a tail of 5, against the
    recurrence one token at a time."""
    model, params = net
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, V, (2, 37)), jnp.int32)
    apply = jax.jit(model.apply)
    out = apply(params, tokens)
    logits, values, routing = ref.forward(params, tokens, GEO)
    assert _min_gap(routing) > 1e-5 and len(routing) == LAYERS
    np.testing.assert_allclose(out.policy_logits, logits, atol=ATOL)
    np.testing.assert_allclose(out.baseline, values, atol=ATOL)
    assert 0.3 < float(jnp.std(out.policy_logits)) < 3.0
    # 600 tokens take the sorted expert form on a share (held 4 > 3 picks)
    long = jnp.asarray(np.random.default_rng(1).integers(0, V, (2, 300)), jnp.int32)
    want = ref.forward(params, long, GEO)
    assert _min_gap(want[2]) > 1e-6
    np.testing.assert_allclose(apply(params, long).policy_logits, want[0], atol=ATOL)
    # what the tolerance refuses: the reference itself at float8 operands
    low = ref.forward(params, tokens, ref.geometry(CFG, round_to="float8_e4m3fn"))
    assert float(jnp.median(jnp.abs(low[0] - logits))) > 100 * ATOL
    # and a reference that reads a zero-centred scale as the scale itself
    wrong = _thaw(params)
    wrong["params"] = dict(wrong["params"], final_norm={"scale": params["params"]["final_norm"]["scale"] - 1.0})
    assert float(jnp.median(jnp.abs(ref.forward(wrong, tokens, GEO)[0] - logits))) > 100 * ATOL


@pytest.mark.parametrize("lengths,tail", [((37,), 0), ((9, 8), 4), ((5, 14, 6), 7), ((21,), 11)])
def test_chunked_rule_is_the_recurrence_and_cuts_at_runs(lengths, tail):
    """:func:`gated_delta_chunked` alone against the rule a token at a
    time (the reference's literal order), at lengths that are no multiple
    of the chunk, with several runs a row and a pad tail: the state is
    zero at a run's start, passes through pad tokens unchanged (``beta =
    0``, ``g = 0``) and leaves at the last real token's value."""
    rng = np.random.default_rng(sum(lengths))
    T = sum(lengths) + tail
    l2 = lambda a: a / np.sqrt(np.sum(a * a, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    q = jnp.asarray(l2(rng.normal(size=(1, T, GG, GN))) * GN ** -0.5, jnp.float32)
    k = jnp.asarray(l2(rng.normal(size=(1, T, GG, GN))), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, T, GH, GP)), jnp.float32)
    g = -jnp.asarray(rng.uniform(0.01, 1.5, size=(1, T, GH)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.05, 0.95, size=(1, T, GH)), jnp.float32)
    seg = jnp.asarray([sum(([i + 1] * n for i, n in enumerate(lengths)), []) + [0] * tail])
    real = seg > 0
    g, beta = jnp.where(real[..., None], g, 0.0), jnp.where(real[..., None], beta, 0.0)
    o, last = jax.jit(gated_delta_chunked, static_argnums=6)(q, k, v, g, beta, run_ids(seg), CHUNK)
    assert np.all(np.isfinite(np.asarray(o)))
    per = GH // GG
    lo = 0
    for n in lengths:
        S = jnp.zeros((1, GH, GN, GP))
        for t in range(lo, lo + n):
            want, S = ref.delta_rule_token(
                S, jnp.repeat(q[:, t], per, axis=1), jnp.repeat(k[:, t], per, axis=1),
                v[:, t], g[:, t], beta[:, t],
            )
            np.testing.assert_allclose(o[:, t], want, atol=2e-5)
        lo += n
    np.testing.assert_allclose(last, S, atol=2e-5)  # at the true length, not the row's end
    assert float(jnp.max(jnp.abs(last))) > 1e-2
    if tail:
        # bit for bit: pads of beta = 0 and g = 0 change nothing, whatever
        # their q, k and v hold
        noisy_v = v.at[:, lo:].set(7.0)
        _o, again = gated_delta_chunked(q, k, noisy_v, g, beta, run_ids(seg), CHUNK)
        np.testing.assert_array_equal(again, last)


@pytest.mark.parametrize("shape", [(3, 4, 8, 8, 2), (2, 8, 128, 128, 4), (2, 32, 128, 128, 16)])
def test_the_one_read_update_is_the_literal_one(shape):
    """``gdn_decode_update`` (the kernel in interpret mode: the state read
    once, a block of heads a step) against the rule in its literal order
    (decay, ``S^T k``, rank-one write, ``S^T q``), at the rehearsal's
    sizes and at the configuration's own 32 x 128 x 128 (two lanes: two
    blocks of 16 heads a lane).  A pad's ``beta = 0, g = 0`` leaves the
    state bit for bit."""
    L, Hh, N, P, G = shape
    key = jax.random.split(jax.random.PRNGKey(L + Hh), 6)
    l2 = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    state = jax.random.normal(key[0], (L, Hh, N, P))
    q = l2(jax.random.normal(key[1], (L, G, N))) * N ** -0.5
    k = l2(jax.random.normal(key[2], (L, G, N)))
    v = jax.random.normal(key[3], (L, Hh, P))
    g = -jax.nn.softplus(jax.random.normal(key[4], (L, Hh)))
    beta = jax.nn.sigmoid(jax.random.normal(key[5], (L, Hh)))
    per = Hh // G
    with jax.default_matmul_precision("highest"):
        want_o, want = ref.delta_rule_token(
            state, jnp.repeat(q, per, axis=1), jnp.repeat(k, per, axis=1), v, g, beta
        )
    o, new = jax.jit(gdn_decode_update)(state, q, k, v, g, beta)
    assert new.shape == state.shape and new.dtype == jnp.float32
    np.testing.assert_allclose(o, want_o, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(new, want, atol=2e-5, rtol=1e-5)
    _o, same = gdn_decode_update(state, q, k, v, jnp.zeros_like(g), jnp.zeros_like(beta))
    np.testing.assert_array_equal(same, state)
    # a value head reads ITS key head: with the key heads swapped the
    # answer is another one
    swapped = gdn_decode_update(state, q[:, ::-1], k[:, ::-1], v, g, beta)[0]
    assert float(jnp.max(jnp.abs(swapped - want_o))) > 1e-2


def test_gated_attention_matches_reference_through_the_paged_reference():
    """The attention layer alone at 16 query heads over 2 key/value heads
    of 256 with 64 rotating features (the published head): a prefill of 9
    tokens writes K, normed a head and rotated, into pages; one decode
    token reads them through ``paged_attention_reference`` and through the
    kernel in interpret mode; both are the reference's full forward."""
    Hq, KVh, Dh, rot, d = 16, 2, 256, 64, 64
    spec = block_spec(
        "qwen3_next", head_dim=Dh, norm_eps=1e-6, rope_theta=1e7, num_experts=4,
        experts_per_token=2, expert_width=8, norm_topk_prob=True, shared_experts=1, kv_heads=KVh,
        ssm_heads=2, ssm_head_dim=4, ssm_state=4, ssm_groups=1, ssm_chunk=4, rotary_dim=rot,
    )
    geo = GEO._replace(
        layers=1, interval=1, n_head=Hq, kv_heads=KVh, head_dim=Dh, rotary_dim=rot,
        n_routed=4, held=4, top_k=2,
    )
    rng = np.random.default_rng(5)
    tokens = jnp.asarray(rng.integers(0, V, (1, 10)), jnp.int32)
    for paged in (paged_attention_reference, lambda *a: paged_decode_attention(*a, interpret=True)):
        model = TransformerPolicy(
            num_actions=V, vocab_size=V, d_model=d, num_heads=Hq, num_layers=1, block=spec,
            paged_attn_fn=paged,
        )
        params = model.init(jax.random.PRNGKey(2), tokens)
        params = _thaw(jax.tree_util.tree_map(np.asarray, params))
        for name in ("q_norm", "k_norm"):
            params["params"]["block_0"][name]["scale"] = np.asarray(0.2 * rng.normal(size=Dh), np.float32)
        want, _values, routing = ref.forward(params, tokens, geo)
        assert _min_gap(routing) > 1e-5
        np.testing.assert_allclose(model.apply(params, tokens).policy_logits, want, atol=ATOL)
        ps, n = 4, 9
        cache = model.init_paged_cache(5, ps)
        assert cache.k[0].shape == (5, ps, KVh * Dh)
        pos = np.arange(n)
        table = np.asarray([[1, 2, 3]], np.int32)
        _out, cache = model.apply(
            params, tokens[:, :n], positions=pos[None], attn_mask=prompt_attention_mask(jnp.asarray([n]), n),
            paged_cache=cache, page_ids=jnp.asarray(table[:, pos // ps]), page_offsets=jnp.asarray(pos[None] % ps),
        )
        out, _cache = model.apply(
            params, tokens[:, n:], positions=jnp.asarray([[n]]), paged_cache=cache,
            page_ids=jnp.asarray([[3]]), page_offsets=jnp.asarray([[n % ps]]),
            page_table=jnp.asarray(table), attn_lengths=jnp.asarray([n + 1]),
        )
        np.testing.assert_allclose(out.policy_logits[0, 0], want[0, n], atol=ATOL)
    # only the first 64 features rotate: a key cached at another position
    # differs there and nowhere else
    moved = model.apply(
        params, tokens[:, :n], positions=pos[None] + 3, attn_mask=prompt_attention_mask(jnp.asarray([n]), n),
        paged_cache=model.init_paged_cache(5, ps), page_ids=jnp.asarray(table[:, pos // ps]),
        page_offsets=jnp.asarray(pos[None] % ps),
    )[1]
    k0, k1 = (np.asarray(c.k[0]).reshape(-1, KVh, Dh) for c in (cache, moved))
    assert np.max(np.abs(k0[..., :rot] - k1[..., :rot])) > 1e-3
    np.testing.assert_array_equal(k0[..., rot:], k1[..., rot:])


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
def test_packed_rows_match_reference_and_nothing_crosses_a_boundary(net, kernel):
    """Three sequences a row (9, 14 and 6 tokens: every boundary inside a
    chunk of 8) and a pad tail: each segment's logits are those of the
    reference on that sequence ALONE, through the delta rule, the
    convolution and the attention (the dense packed mask, and the flash
    segment kernel in interpret mode on keys and values repeated to 4
    heads); and changing a neighbour's tokens changes no logit of this
    one, bit for bit."""
    model, params = net
    tok, seg, pos = _rows(4, [9, 14, 6], 32)
    if kernel == "segment_flash":
        from scalerl_tpu.ops.pallas_attention import segment_flash_attention

        seen = []

        def kern(q, k, v, s):
            seen.append((q.shape[2], k.shape[2]))
            return segment_flash_attention(q, k, v, s)

        model = model.clone(segment_attn_fn=kern)
    packed = jax.jit(lambda t: model.apply(params, t, positions=pos, segment_ids=seg))
    out = packed(tok)
    for lo, hi in ((0, 9), (9, 23), (23, 29)):
        logits, values, routing = ref.forward(params, tok[:, lo:hi], GEO)
        assert _min_gap(routing) > 1e-5
        np.testing.assert_allclose(out.policy_logits[0, lo:hi], logits[0], atol=ATOL)
        np.testing.assert_allclose(out.baseline[0, lo:hi], values[0], atol=ATOL)
    if kernel == "segment_flash":
        assert seen == [(H, H)]  # one attention layer, keys repeated under their query heads
    other = np.asarray(tok).copy()
    other[0, :9] = (other[0, :9] + 17) % V  # both neighbours of the middle segment change
    other[0, 23:29] = (other[0, 23:29] + 5) % V
    moved = packed(jnp.asarray(other))
    np.testing.assert_array_equal(out.policy_logits[0, 9:23], moved.policy_logits[0, 9:23])
    assert float(jnp.max(jnp.abs(out.policy_logits[0, :9] - moved.policy_logits[0, :9]))) > 1e-3
    # what a boundary that leaks would read: the row as ONE sequence
    leaky = ref.forward(params, tok[:, :23], GEO)[0]
    assert float(jnp.max(jnp.abs(leaky[0, 9:23] - out.policy_logits[0, 9:23]))) > 100 * ATOL


def test_prefill_takes_the_state_at_the_true_length(net):
    """The paged prefill over prompts right-padded to a bucket of 16 (true
    lengths 11 and 5, neither a multiple of the chunk): the last real
    position's logits are the full forward's, the state written to the
    named lanes is the recurrence's after exactly that many tokens, and
    the convolution window holds the last three REAL inputs (zeros before
    a prompt shorter than the window).  A state taken at the bucket's end
    is measured to be three orders of magnitude away."""
    model, params = net
    rng = np.random.default_rng(5)
    P, lanes, ps = 16, 4, 4
    lengths = jnp.asarray([11, 5, 2])
    toks = np.zeros((3, P), np.int32)
    for r, n in enumerate(np.asarray(lengths)):
        toks[r, :n] = rng.integers(1, V, n)
    cache = model.init_paged_cache(16, ps, lanes=lanes)
    table = np.arange(1, 13, dtype=np.int32).reshape(3, 4)
    pos = np.arange(P)
    page_ids = np.where(pos[None] < np.asarray(lengths)[:, None], table[:, pos // ps], 0)
    offsets = np.where(pos[None] < np.asarray(lengths)[:, None], pos % ps, 0)
    lane_ids = jnp.asarray([2, 0, lanes])  # the third row is a pad row: it drops

    @jax.jit
    def prefill(toks, positions, mask, cache, page_ids, offsets, lane_ids):
        return model.apply(
            params, toks, positions=positions, attn_mask=mask, paged_cache=cache,
            page_ids=page_ids, page_offsets=offsets, state_lanes=lane_ids,
        )

    out, written = prefill(
        jnp.asarray(toks), jnp.broadcast_to(pos, (3, P)), prompt_attention_mask(lengths, P),
        cache, jnp.asarray(page_ids), jnp.asarray(offsets), lane_ids,
    )
    layers = range(len(GDN_LAYERS))
    for r, lane in ((0, 2), (1, 0)):
        n = int(lengths[r])
        # the state a prefill of the prompt ALONE, unpadded, leaves, and
        # its logits (a prefill's are the full forward's)
        alone, own = prefill(
            jnp.asarray(toks[r : r + 1, :n]), jnp.arange(n)[None],
            prompt_attention_mask(jnp.asarray([n]), n), model.init_paged_cache(16, ps, lanes=1),
            jnp.asarray(page_ids[r : r + 1, :n]), jnp.asarray(offsets[r : r + 1, :n]), jnp.asarray([0]),
        )
        np.testing.assert_allclose(out.policy_logits[r, n - 1], alone.policy_logits[0, n - 1], atol=1e-5)
        for layer in layers:
            np.testing.assert_allclose(written.ssm[layer][lane], own.ssm[layer][0], atol=1e-5)
            np.testing.assert_allclose(written.conv[layer][lane], own.conv[layer][0], atol=1e-5)
            assert float(jnp.max(jnp.abs(written.ssm[layer][lane]))) > 1e-3
    # the first layer's state is the reference's rule after exactly 11 tokens
    p0 = params["params"]["block_0"]
    x = jnp.asarray(params["params"]["token_embed"]["embedding"])[toks[:1, :11]]
    u = ref._rms_norm(x, p0["attn_norm"]["scale"], GEO.eps)
    want = jax.jit(_reference_state)(p0["mixer"], u)
    np.testing.assert_allclose(written.ssm[0][2], want[0], atol=1e-5)
    # a prompt of 2 in a window of 3 taps: the pad row dropped, lanes no row
    # named untouched
    for layer in layers:
        assert not np.any(np.asarray(written.ssm[layer][jnp.asarray([1, 3])]))
    # a state at the bucket's end (the prompt taken as 16 real tokens)
    _o, wrong = prefill(
        jnp.asarray(toks[:1]), jnp.asarray(pos[None]), prompt_attention_mask(jnp.asarray([P]), P),
        model.init_paged_cache(16, ps, lanes=1), jnp.asarray(table[:1, pos // ps]),
        jnp.asarray(pos[None] % ps), jnp.asarray([0]),
    )
    assert float(jnp.max(jnp.abs(wrong.ssm[0][0] - written.ssm[0][2]))) > 1000 * 1e-5


def _reference_state(p, u):
    """The state the reference's rule leaves after ``u [B, T, d]``."""
    B, T, _ = u.shape
    keys, per = GG * GN, GH // GG
    conv_w = jnp.asarray(p["conv_w"])
    qkvz = u @ p["in_proj"]["kernel"]
    ba = u @ p["ba_proj"]["kernel"]
    qkv = jnp.pad(qkvz[..., : conv_w.shape[1]], ((0, 0), (3, 0), (0, 0)))
    beta = jax.nn.sigmoid(ba[..., :GH])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., GH:] + p["dt_bias"])
    l2 = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    S = jnp.zeros((B, GH, GN, GP))
    for t in range(T):
        act = jax.nn.silu(jnp.sum(qkv[:, t : t + 4] * conv_w, axis=1))
        q = jnp.repeat(l2(act[:, :keys].reshape(B, GG, GN)) * GN ** -0.5, per, axis=1)
        k = jnp.repeat(l2(act[:, keys : 2 * keys].reshape(B, GG, GN)), per, axis=1)
        _o, S = ref.delta_rule_token(S, q, k, act[:, 2 * keys :].reshape(B, GH, GP), g[:, t], beta[:, t])
    return S


def _engine(model, params, **kw):
    cfg = dict(
        vocab_size=V, max_prompt_len=12, max_new_tokens=12, temperature=1.0,
        seed=5, lanes=8, page_size=4, steps_per_macro=3, steps_in_flight=2,
        prefix_cache=True,
    )
    cfg.update(kw)
    return ContinuousEngine(model, params, ContinuousConfig(**cfg))


def _check_against_reference(params, completions):
    for c in completions:
        m, r = int(c.prompt_len), len(c.response_tokens)
        toks = np.concatenate([c.prompt[:m], c.response_tokens])[None]
        logp, values, gaps = ref.token_logprobs(params, toks, GEO)
        assert float(jnp.min(gaps)) > 1e-5
        np.testing.assert_allclose(
            c.behavior_logp, np.asarray(logp)[0, m - 1 : m + r - 1], atol=ATOL
        )
        np.testing.assert_allclose(
            c.values, np.asarray(values)[0, m - 1 : m + r - 1], atol=ATOL
        )


@pytest.mark.parametrize("paged_attn", ["xla", "pallas"])
def test_engine_prefill_decode_and_fork_match_reference(net, paged_attn):
    """Local prefill under right-padding (prompts of 10 and 7 in a bucket
    of 16), then decode through pages AND state (the XLA twin, and the
    grouped-head kernel in interpret mode), and a forked group whose
    members got their state from the leader; then a second admission over
    the same prefix, which must NOT be served from the prefix cache."""
    model, params = net
    engine = _engine(model, params, paged_attn=paged_attn)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, V, 10).astype(np.int32)  # 2 full pages + a partial one
    assert engine.submit_group(prompt, 4, 10, tag=0)
    assert engine.submit(rng.integers(0, V, 7).astype(np.int32), 7, tag=1)
    done = engine.run_until(5)
    again = np.concatenate([prompt[:8], rng.integers(0, V, 3)]).astype(np.int32)
    assert engine.submit(again, len(again), tag=2)
    done += engine.run_until(1)
    assert len(done) == 6 and all(len(c.response_tokens) == 12 for c in done)
    _check_against_reference(params, done)
    stats = engine.stats()
    # no prefix hit is ever served: every admission skipped the cache,
    # which holds nothing, and only local prefills were built; what was
    # saved is the group's copy-on-write share alone
    assert stats["prefix_skipped_recurrent"] == 3
    assert engine._prefix_cache.stats() == {"cached_pages": 0, "hits": 0, "misses": 0, "evictions": 0}
    assert {key[0] for key in engine._prefill_fns} == {"local"}
    assert engine.prefix_tokens_saved == 3 * 8
    # the state: 4 delta-rule layers x (4 x 8 x 8 + 3 x 64) float32 a lane
    assert stats["state_bytes_per_lane"] == 4 * 4 * (GH * GN * GP + 3 * (2 * GG * GN + GH * GP))
    assert stats["state_forks"] == 3
    # every layer has a router
    assert stats["expert_tokens"].shape == (LAYERS, E)
    decoded = sum(len(c.response_tokens) for c in done)
    np.testing.assert_array_equal(stats["expert_tokens"].sum(axis=1), [K * decoded] * LAYERS)
    assert stats["held_expert_tokens"] + stats["absent_expert_tokens"] == K * decoded * LAYERS
    assert stats["zero_expert_tokens"] == 0 and stats["held_expert_tokens"] > 0


def test_a_forked_member_continues_bit_for_bit_as_its_leader(net):
    """Greedy sampling: every member of a group decodes the leader's
    tokens, so its recorded log-probabilities and values must be the
    leader's bit for bit, which they are only if the fork gave it the
    leader's state (the lanes held another prompt's state before).  Then
    :func:`fork_cache` alone on this cache: the members' state rows are
    the leader's, the source untouched, pad rows dropped."""
    model, params = net
    engine = _engine(model, params, temperature=0.0, lanes=4)
    rng = np.random.default_rng(3)
    assert engine.submit_group(rng.integers(0, V, 9).astype(np.int32), 4, 9, tag="warm")
    engine.run_until(4)  # every lane now holds a finished sequence's state
    prompt = rng.integers(0, V, 11).astype(np.int32)
    assert engine.submit_group(prompt, 4, 11, tag="group")
    done = engine.run_until(4)
    leader = done[0]
    for member in done[1:]:
        np.testing.assert_array_equal(member.response_tokens, leader.response_tokens)
        np.testing.assert_array_equal(member.behavior_logp, leader.behavior_logp)
        np.testing.assert_array_equal(member.values, leader.values)
    _check_against_reference(params, done[:1])
    cache = model.init_paged_cache(6, 4, lanes=4)
    cache = cache._replace(
        ssm=tuple(s + jnp.arange(4.0)[:, None, None, None] + 1 for s in cache.ssm),
        conv=tuple(c + jnp.arange(4.0)[:, None, None] + 1 for c in cache.conv),
        k=tuple(k.at[2].set(7.0) for k in cache.k),
    )
    forked = fork_cache(
        cache, jnp.asarray([2, 0]), jnp.asarray([5, 0]), jnp.asarray([1, 0]), jnp.asarray([3, 4])
    )
    for before, after in zip(cache.ssm + cache.conv, forked.ssm + forked.conv):
        np.testing.assert_array_equal(after[3], before[1])  # the member has the leader's rows
        np.testing.assert_array_equal(after[:3], before[:3])  # lane 4 is out of range: dropped
        assert float(jnp.max(jnp.abs(before[3] - before[1]))) >= 2.0  # what sharing would leave
    np.testing.assert_array_equal(forked.k[0][5], cache.k[0][2])
    assert not np.any(np.asarray(forked.v[0]))


def test_speculation_is_refused_for_a_recurrent_model(net):
    model, params = net
    with pytest.raises(ValueError, match="a layer that carries lane state: .* no cursor to rewind"):
        _engine(model, params, spec_k=2)
    # and the model refuses the tail prefill a hit or a verify would ride
    cache = model.init_paged_cache(6, 4, lanes=2)
    z = jnp.zeros((2, 2), jnp.int32)
    with pytest.raises(ValueError, match="cannot be entered at a page boundary"):
        model.apply(
            params, z, positions=z, paged_cache=cache, page_ids=z, page_offsets=z,
            page_table=jnp.zeros((2, 3), jnp.int32), prefix_starts=jnp.zeros((2,), jnp.int32),
        )


_HYPER = dict(
    clip_range=0.2, value_cost=0.5, entropy_cost=0.01, kl_cost=0.0, adv_norm=True,
    router_aux_loss_coef=0.01,
)
_KW = {("router_aux_coef" if k == "router_aux_loss_coef" else k): v for k, v in _HYPER.items()}


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
    """The PACKED learner on one sequence (a row of 16 with a pad tail)
    against the reference's loss and ``jax.grad`` of each: autodiff
    through the chunked form (its triangular solve among it) against
    autodiff through the rule a token at a time."""
    model, params = net
    seqs = _sequences(6, 1)
    packed, _pk = _packed(seqs, S=16)
    seq = _one(seqs)
    assert _min_gap(ref.forward(params, seq["tokens"][None], GEO)[2]) > 1e-5
    (total, metrics), grads = jax.jit(jax.value_and_grad(
        lambda w: token_ppo_packed_loss(w, w, model, packed, **_KW), has_aux=True
    ))(params)
    (want, parts), want_grads = jax.jit(jax.value_and_grad(
        lambda w: ref.ppo_loss(ref_ppo, w, w, seq, GEO, _HYPER), has_aux=True
    ))(params)
    np.testing.assert_allclose(float(total), float(want), atol=1e-5)
    for key in ("pg_loss", "value_loss", "entropy", "moe_aux_loss", "moe_max_load"):
        np.testing.assert_allclose(float(metrics[key]), float(parts[key]), atol=1e-5)
    got, _ = ravel_pytree(grads)
    exp, _ = ravel_pytree(want_grads)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), atol=2e-5, rtol=1e-4)
    assert np.all(np.isfinite(np.asarray(got)))
    # every kind of mixer takes a gradient, the rule's own leaves too
    mixer = grads["params"]["block_0"]["mixer"]
    for name in ("A_log", "dt_bias", "conv_w", "norm_scale"):
        assert float(jnp.max(jnp.abs(mixer[name]))) > 1e-7, name
    for name in ("in_proj", "ba_proj", "out_proj"):
        assert float(jnp.max(jnp.abs(mixer[name]["kernel"]))) > 1e-7, name
    block = grads["params"]["block_3"]
    for name in ("q", "kv", "shared_gate"):
        assert float(jnp.max(jnp.abs(block[name]["kernel"]))) > 1e-7, name
    assert float(jnp.max(jnp.abs(block["q_norm"]["scale"]))) > 1e-7
    real = float(jnp.sum(packed["segment_ids"] > 0))
    assert float(metrics["moe_held_picks"] + metrics["moe_absent_picks"]) == K * LAYERS * real


def test_packed_rows_of_several_sequences_match_the_reference_on_each(net):
    """Two and more sequences a row: the state and the taps are cut at
    every segment's start, so the loss term is the token-weighted mean of
    the reference's per-sequence terms (no advantage norm, so that the
    terms separate)."""
    model, params = net
    seqs = _sequences(7, 5)
    packed, pk = _packed(seqs, S=32)
    assert pk.rows < 5
    kw = {**_KW, "adv_norm": False}
    _total, metrics = jax.jit(lambda w: token_ppo_packed_loss(w, w, model, packed, **kw))(params)
    hyper = {**_HYPER, "adv_norm": False}
    term = count = 0.0
    for i in range(5):
        seq = _one(seqs, i)
        _t, parts = ref.ppo_loss(ref_ppo, params, params, seq, GEO, hyper)
        n = float(seq["mask"][1:].sum())
        term += n * float(parts["pg_loss"])
        count += n
    np.testing.assert_allclose(float(metrics["pg_loss"]), term / count, atol=1e-5)


def test_the_trainer_and_the_agent_take_the_family():
    """``SequenceRLTrainer`` and ``TokenPPOAgent`` by the entry point the
    other families use: two rounds of generate, pack, learn and push at
    test size, finite and counted."""
    from scalerl_tpu.trainer.sequence_rl import SequenceRLTrainer

    args = _args(
        "--vocab-size", "16", "--prompt-len", "4", "--max-new-tokens", "4", "--genrl-engine",
        "continuous", "--genrl-lanes", "8", "--samples-per-prompt", "4", "--genrl-batch", "8",
        "--genrl-sample-batch", "8", "--genrl-buffer-sequences", "16", "--learner-pack-len", "32",
        "--genrl-page-size", "4", "--platform", "cpu", "--seed", "1",
    )
    trainer = SequenceRLTrainer(args)
    for _ in range(2):
        metrics = trainer.train_round()
    stats = trainer.engine.stats()
    assert np.isfinite(metrics["total_loss"]) and metrics["decode_tokens"] > 0
    assert stats["state_forks"] > 0 and stats["prefix_skipped_recurrent"] > 0
    assert stats["state_bytes_per_lane"] == 4 * 4 * (GH * GN * GP + 3 * 64)


# ---------------------------------------------------------------------------
# the share


@pytest.mark.parametrize("n_tokens", [7, 600])
def test_the_shares_add_up(net, n_tokens):
    """Over ALL the shares of a layer (the deployment's way at test size:
    8 experts as 4 ranks of 2, and as 2 ranks of 4), the held parts
    summed, with the gated shared expert and the residual counted once,
    equal the UNCUT reference layer: what a rank leaves out is exactly what
    the other ranks add.  7 tokens take the streamed form; of 600, a share
    of 4 (> 3 picks) takes the sorted one."""
    model, params = net
    block = dict(params["params"]["block_3"])
    rng = np.random.default_rng(n_tokens)
    x = jnp.asarray(rng.normal(size=(1, n_tokens, D)), jnp.float32)
    full_banks = {
        "w_gate": jnp.asarray(rng.normal(size=(E, D, F)) / np.sqrt(D), jnp.float32),
        "w_up": jnp.asarray(rng.normal(size=(E, D, F)) / np.sqrt(D), jnp.float32),
        "w_down": jnp.asarray(rng.normal(size=(E, F, D)) / np.sqrt(F), jnp.float32),
    }
    whole = dict(block, experts={**block["experts"], **full_banks})
    uncut = GEO._replace(first_expert=0, held=E)
    u = ref._rms_norm(x, block["ffn_norm"]["scale"], GEO.eps)
    want, _probs, _weights, gap = ref._experts(whole, u, uncut)
    assert float(jnp.min(gap)) > 1e-6
    shared = jax.nn.sigmoid(u @ block["shared_gate"]["kernel"]) * ref._swiglu(block["shared"], u, None)
    for held in (2, 4):
        total = jnp.zeros_like(x)
        for first in range(0, E, held):
            ffn = RoutedExperts(E, K, F, norm_topk_prob=True, held=held, first_expert=first)
            share = {
                **block["experts"],
                **{k: v[first : first + held] for k, v in full_banks.items()},
            }
            part = ffn.apply({"params": share}, u)
            total = total + part
            # and each share alone is the reference's share
            alone = ref._experts(
                dict(block, experts=share), u, GEO._replace(first_expert=first, held=held)
            )[0]
            np.testing.assert_allclose(part + shared, alone, atol=1e-5)
        np.testing.assert_allclose(total + shared, want, atol=1e-5)


def test_the_stack_says_what_it_is_once_a_traced_shape(net):
    """The ``model.layers`` note carries each plain layer's mixer kind, so
    the tuple is the pattern; ``gdn.form`` says once a traced shape which
    form of the rule ran."""
    from scalerl_tpu.models import transformer
    from scalerl_tpu.runtime import tracing

    model, params = net
    transformer._note_layers.cache_clear()
    transformer._note_gdn_form.cache_clear()
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
        engine = _engine(model, params, lanes=4)
        assert engine.submit_group(np.arange(5, dtype=np.int32), 4, 5, tag=0)
        engine.run_until(4)
    finally:
        tracing.span = keep
    notes = [attrs for name, attrs in seen if name == "model.layers" and attrs["shape"] == [1, 6]]
    assert len(notes) == 1
    assert notes[0]["layers"] == [
        "plain/gdn/experts", "plain/gdn/experts", "plain/gdn/experts",
        "plain/attention/experts", "plain/gdn/experts",
    ]
    assert (notes[0]["held"], notes[0]["num_experts"]) == (HELD, E)
    forms = [attrs for name, attrs in seen if name == "gdn.form"]
    whole = [f for f in forms if f["shape"] == [1, 6, D]]
    assert len(whole) == 1 and whole[0]["chunk"] == CHUNK and "kernel" not in whole[0]
    assert whole[0]["heads"] == (GH, GN, GP) and whole[0]["state_dtype"] == "float32"
    decode = [f for f in forms if "kernel" in f]
    assert len(decode) == 1 and decode[0]["shape"] == [4, 1, D]
    assert decode[0]["kernel"] == "pallas" and decode[0]["tile"] == [GH, GN, GP]
    dispatch = [attrs for name, attrs in seen if name == "genrl.dispatch"]
    assert dispatch and all(a["state_bytes"] == 4 * engine.stats()["state_bytes_per_lane"] for a in dispatch)
