"""The ``zaya`` block family of the token model (ISSUE 46): plain layers of
compressed convolutional attention (CCA: query and key latents convolved
over the two tokens before them by a depthwise and then a grouped
convolution, a q-k mean, L2 norms with a temperature a key head, a half
rotary, half of the values the previous token's) whose two-token window
rides by lane (a ring of two rows, side by side) beside the layer's OWN K and V pages, and routed experts
behind a router that is an MLP on a state handed up from the layer before
(top-1 of 4 here), with a learned scale and bias on both sides of every
residual add.

Every comparison is against ``benchmark/reference/zaya.py`` (plain
``jax.numpy``, float32 at ``highest``, shifted arrays for the taps and the
value shift, a loop over a head's grouped taps, key/value heads repeated, a
masked scan over the experts) and, for the learner,
``reference/token_ppo.py``.  The model here is three layers: hidden 32, 4
query heads over 2 key/value heads of 8 of which the first 4 features
rotate, both convolutions at 2 taps, a router of width 8 over 4 experts of
width 16 with one a token; float32 on both sides.  EVERY vector the
configuration lists under ``assumed`` with a neutral start (the residual
merges' scales and biases, the router's carry scale, norm scale and
biases, the key temperature, the router's choice bias, every norm scale) is
moved off its start by a seeded fifth, so that leaving one out fails.  At
that size and precision the two sides agree to about 2e-6, while the
smallest gap between the picked probability and the runner-up is about
1e-3, so a routing flip cannot happen and the tolerance is 1e-4; each case
asserts that gap rather than trust it.
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
from scalerl_tpu.models.routed_ffn import MLPRouter, RoutedExperts
from scalerl_tpu.models.transformer import (
    BlockSpec,
    ModelCache,
    TransformerPolicy,
    _join,
    _layer_entries,
    block_spec,
    cca_window_shape,
    fork_cache,
    prompt_attention_mask,
)
from scalerl_tpu.runtime import telemetry, tracing
from scalerl_tpu.trainer.sequence_rl import build_genrl_model

ROOT = Path(__file__).resolve().parents[1]
ATOL = 1e-4  # see the module docstring
LAYERS = 3
V, D, H, KV, DH, ROT = 53, 32, 4, 2, 8, 4
E, K, F, RW = 4, 1, 16, 8
C = (H + KV) * DH  # the convolutions' channels
ROW = C + KV * DH // 2  # a window row: [u | h W_v2]
CFG = dict(
    vocab_size=V, hidden_size=D, num_hidden_layers=LAYERS, num_attention_heads=H,
    num_key_value_heads=KV, head_dim=DH, partial_rotary_factor=ROT / DH,
    rope_parameters={"hybrid": {"rope_theta": 5e6}}, rms_norm_eps=1e-5, cca_time0=2,
    cca_time1=2, router_hidden_size=RW, moe_intermediate_size=F, num_experts=E,
    num_experts_per_tok=K, router_aux_loss_coef=0.0,
)


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"ref_{name}", ROOT / "benchmark" / "reference" / f"{name}.py"
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load("zaya")
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


_MOVED = ("scale", "norm_scale", "carry_scale", "k_temp", "router_bias", "bias")


def _seeded(model, key, seed):
    """The model's weights with every vector that starts neutral moved by
    a seeded fifth (the policy head's bias among them)."""
    params = jax.device_get(model.init(jax.random.PRNGKey(key), jnp.zeros((1, 2), jnp.int32)))
    rng = np.random.default_rng(seed)

    def move(tree):
        out = {}
        for name, leaf in tree.items():
            if isinstance(leaf, dict):
                out[name] = move(leaf)
            elif name in _MOVED or name.endswith(("_res_scale", "_res_bias")):
                out[name] = np.asarray(leaf + 0.2 * rng.normal(size=leaf.shape), np.float32)
            else:
                out[name] = np.asarray(leaf)
        return out

    return move(params)


@pytest.fixture(scope="module")
def net():
    model = build_genrl_model(_args())
    return model, _seeded(model, 3, 11)


def _min_gap(routing):
    return min(float(jnp.min(gap)) for _s, _w, gap in routing)


def test_program_arguments_choose_the_family(net):
    model, params = net
    spec = model.block
    assert spec.attention == "cca" and spec.router == "mlp" and spec.residual_scale
    assert (spec.cca_time0, spec.cca_time1, spec.router_width, spec.rotary_dim) == (2, 2, RW, ROT)
    assert spec.owns == {"k": 1, "v": 1, "conv": 1}
    # a lane carries rows of every layer though no layer is a recurrence
    assert spec.lane_state and not spec.recurrent
    assert model.lane_state and not model.recurrent and model.routed_layers == LAYERS
    assert spec.kind == "plain/cca/experts"
    assert cca_window_shape(spec, H, DH) == (2, ROW)
    cache = model.init_paged_cache(5, 4, lanes=3)
    assert [a.shape for a in cache.conv] == [(3, 2 * ROW)] * LAYERS and cache.ssm == ()
    assert [a.shape for a in cache.k] == [(5, 4, KV * DH)] * LAYERS and cache.rows == ()
    assert all(a.dtype == jnp.float32 for a in cache.conv)
    with pytest.raises(ValueError, match="sized by its lanes"):
        model.init_paged_cache(5, 4)
    block = params["params"]["block_1"]
    assert set(block) == {
        "attn", "attn_norm", "attn_res_scale", "attn_res_bias", "router", "experts",
        "ffn_norm", "ffn_res_scale", "ffn_res_bias",
    }
    assert set(block["attn"]) == {"q", "k", "v1", "v2", "conv_w", "conv_b", "mix_w", "mix_b", "k_temp", "proj"}
    assert block["attn"]["mix_w"].shape == (2, H + KV, DH, DH)
    # the router is no matrix of the experts' module, and the first layer
    # has no state to scale
    assert "router" not in block["experts"] and set(block["experts"]) == {"router_bias", "w_gate", "w_up", "w_down"}
    assert "carry_scale" in block["router"] and "carry_scale" not in params["params"]["block_0"]["router"]


def test_arguments_the_family_refuses():
    with pytest.raises(ValueError, match="eight families"):
        _args("--block-family", "zeya")
    for extra, match in (
        (("--spec-enable", "true"), "carries lane state"),
        (("--ssm-heads", "2"), "ssm sizes"),
        (("--layer-pattern", "M*E"), "nemotron_h family's"),
        (("--full-attention-interval", "2"), "qwen3_next family's"),
    ):
        with pytest.raises(ValueError, match=match):
            _args(*extra)
    for family, extra in (("olmoe", ("--cca-time0", "2")), ("gpt2", ("--router-hidden", "8"))):
        argv = ["--block-family", family, "--logger-backend", "none", *extra]
        with pytest.raises(ValueError, match="zaya family's"):
            parse_args(GenRLArguments, argv).validate()
    with pytest.raises(ValueError, match="2 taps or more"):
        block_spec("zaya", head_dim=8, expert_width=16, router_width=8, num_experts=4,
                   experts_per_token=1, kv_heads=2, cca_time0=1, cca_time1=2)
    with pytest.raises(ValueError, match="even number of kv_heads"):
        block_spec("zaya", head_dim=8, expert_width=16, router_width=8, num_experts=4,
                   experts_per_token=1, kv_heads=1, cca_time0=2, cca_time1=2)


@pytest.mark.parametrize("length", [1, 2, 3, 11])
def test_full_forward_matches_reference_at_every_length(net, length):
    """The whole-sequence path at 1, 2 and 3 tokens (where the two
    convolutions and the value shift read the zeros before the sequence)
    and longer: logits and values are the reference's."""
    model, params = net
    tok = jnp.asarray(np.random.default_rng(length).integers(0, V, (2, length)))
    out = jax.jit(lambda t: model.apply(params, t))(tok)
    logits, values, routing = ref.forward(params, tok, GEO)
    assert _min_gap(routing) > 1e-5
    np.testing.assert_allclose(out.policy_logits, logits, atol=ATOL)
    np.testing.assert_allclose(out.baseline, values, atol=ATOL)


def test_a_vector_left_at_its_start_is_seen(net):
    """Each of the moved vectors matters: with any one kind of them put
    back to its neutral start on the program's side alone, the logits
    leave the reference's by orders of magnitude more than the tolerance."""
    model, params = net
    tok = jnp.asarray(np.random.default_rng(2).integers(0, V, (1, 9)))
    logits = ref.forward(params, tok, GEO)[0]
    fresh = jax.device_get(model.init(jax.random.PRNGKey(3), jnp.zeros((1, 2), jnp.int32)))

    def with_neutral(tree, start, hit):
        return {
            name: with_neutral(leaf, start[name], hit) if isinstance(leaf, dict)
            else (np.asarray(start[name]) if hit(name) else leaf)
            for name, leaf in tree.items()
        }

    for hit in (
        lambda n: n.endswith("_res_scale"), lambda n: n.endswith("_res_bias"),
        lambda n: n == "carry_scale", lambda n: n == "k_temp", lambda n: n == "norm_scale",
    ):
        block = with_neutral(params["params"]["block_1"], fresh["params"]["block_1"], hit)
        changed = {"params": {**params["params"], "block_1": block}}
        got = model.apply(changed, tok).policy_logits
        assert float(jnp.max(jnp.abs(got - logits))) > 100 * ATOL


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
    """Four sequences a row (9, 1, 14 and 2 tokens, so that a run of one
    token and one of two are among them) and a pad tail: each segment's
    logits are those of the reference on that sequence ALONE, through both
    convolutions, the value shift and the attention (the dense packed mask,
    and the flash segment kernel in interpret mode on keys and values
    repeated to 4 heads): the first two tokens of a run see zeros, not the
    run before.  Changing a neighbour's tokens changes no logit of this
    one, bit for bit."""
    model, params = net
    spans = [(0, 9), (9, 10), (10, 24), (24, 26)]
    tok, seg, pos = _rows(4, [hi - lo for lo, hi in spans], 32)
    if kernel == "segment_flash":
        from scalerl_tpu.ops.pallas_attention import segment_flash_attention

        seen = []

        def kern(q, k, v, s):
            seen.append((q.shape[2], k.shape[2]))
            return segment_flash_attention(q, k, v, s)

        model = model.clone(segment_attn_fn=kern)
    packed = jax.jit(lambda t: model.apply(params, t, positions=pos, segment_ids=seg))
    out = packed(tok)
    for lo, hi in spans:
        logits, values, routing = ref.forward(params, tok[:, lo:hi], GEO)
        assert _min_gap(routing) > 1e-5
        np.testing.assert_allclose(out.policy_logits[0, lo:hi], logits[0], atol=ATOL)
        np.testing.assert_allclose(out.baseline[0, lo:hi], values[0], atol=ATOL)
    if kernel == "segment_flash":
        assert seen == [(H, H)] * LAYERS  # keys repeated under their query heads
    other = np.asarray(tok).copy()
    other[0, :10] = (other[0, :10] + 17) % V  # both neighbours of the third segment change
    other[0, 24:26] = (other[0, 24:26] + 5) % V
    moved = packed(jnp.asarray(other))
    np.testing.assert_array_equal(out.policy_logits[0, 10:24], moved.policy_logits[0, 10:24])
    assert float(jnp.max(jnp.abs(out.policy_logits[0, :9] - moved.policy_logits[0, :9]))) > 1e-3
    # what a boundary that leaks would read: the row as ONE sequence
    leaky = ref.forward(params, tok[:, :24], GEO)[0]
    assert float(jnp.max(jnp.abs(leaky[0, 10:12] - out.policy_logits[0, 10:12]))) > 100 * ATOL


def _reference_rows(params, toks):
    """A window's rows ``[u | h W_v2]`` of every token in the first layer,
    by the reference."""
    p0 = params["params"]["block_0"]
    x = jnp.asarray(params["params"]["token_embed"]["embedding"])[toks]
    h = ref._rms_norm(x, p0["attn_norm"]["scale"], GEO.eps)
    positions = jnp.broadcast_to(jnp.arange(toks.shape[1]), toks.shape)
    with jax.default_matmul_precision("highest"):
        return ref.cca_parts(p0["attn"], h, positions, GEO)[3]


def test_prefill_leaves_the_window_at_the_true_length(net):
    """The paged prefill over prompts right-padded to a bucket of 16 (true
    lengths 11, 1 and 2): the last real position's logits are the full
    forward's, and the window written to the named lanes holds the last
    two REAL tokens' rows (zeros before a prompt of one token), which in
    the first layer are the reference's, the token at position ``p`` in
    row ``p mod 2`` (the window is a ring).  A window taken at the
    bucket's end is measured to be far away."""
    model, params = net
    rng = np.random.default_rng(5)
    P, lanes, ps = 16, 5, 4
    lengths = jnp.asarray([11, 1, 2, 7])
    toks = np.zeros((4, P), np.int32)
    for r, n in enumerate(np.asarray(lengths)):
        toks[r, :n] = rng.integers(1, V, n)
    cache = model.init_paged_cache(20, ps, lanes=lanes)
    cache = cache._replace(conv=tuple(c + 9.0 for c in cache.conv))  # what a dead lane left
    table = np.arange(1, 17, dtype=np.int32).reshape(4, 4)
    pos = np.arange(P)
    inside = pos[None] < np.asarray(lengths)[:, None]
    page_ids = np.where(inside, table[:, pos // ps], 0)
    offsets = np.where(inside, pos % ps, 0)
    lane_ids = jnp.asarray([2, 0, 4, lanes])  # the fourth row is a pad row: it drops
    out, written = jax.jit(
        lambda t, c: model.apply(
            params, t, positions=jnp.broadcast_to(pos, (4, P)),
            attn_mask=prompt_attention_mask(lengths, P), paged_cache=c,
            page_ids=jnp.asarray(page_ids), page_offsets=jnp.asarray(offsets),
            state_lanes=lane_ids,
        )
    )(jnp.asarray(toks), cache)
    assert written.ssm == () and len(written.conv) == LAYERS
    for r, lane in ((0, 2), (1, 0), (2, 4)):
        n = int(lengths[r])
        logits, _values, routing = ref.forward(params, toks[r : r + 1, :n], GEO)
        assert _min_gap(routing) > 1e-5
        np.testing.assert_allclose(out.policy_logits[r, n - 1], logits[0, n - 1], atol=ATOL)
        rows = np.asarray(_reference_rows(params, toks[r : r + 1, :n]))[0]
        want = np.concatenate([np.zeros((2, ROW), np.float32), rows])[-2:]  # positions n - 2, n - 1
        want = want[[n % 2, (n + 1) % 2]]  # row p mod 2 holds position p
        np.testing.assert_allclose(written.conv[0][lane].reshape(2, ROW), want, atol=1e-5)
        # what the bucket's end holds instead: two pad tokens' rows
        wrong = np.asarray(_reference_rows(params, toks[r : r + 1]))[0, -2:]  # positions 14, 15
        assert np.max(np.abs(wrong - want)) > 0.05
    # a prompt of one token (position 0, in row 0): the row of the
    # position before it is zero, in every layer
    for layer in range(LAYERS):
        assert not np.any(np.asarray(written.conv[layer][0, ROW:]))
        assert np.any(np.asarray(written.conv[layer][0, :ROW]))
        # lanes no row named keep what they held; the pad row dropped
        np.testing.assert_array_equal(written.conv[layer][jnp.asarray([1, 3])], 9.0)


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
    """Local prefill under right-padding (prompts of 10, 7 and 1 in a
    bucket of 16), then decode through pages AND the carried window (the
    XLA twin, and the grouped-head kernel in interpret mode), and a forked
    group whose members got their window from the leader; then a second
    admission over the same prefix, which must NOT be served from the
    prefix cache.  Recorded log-probabilities and values are the
    reference's full forward's."""
    model, params = net
    engine = _engine(model, params, paged_attn=paged_attn)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, V, 10).astype(np.int32)  # 2 full pages + a partial one
    assert engine.submit_group(prompt, 4, 10, tag=0)
    assert engine.submit(rng.integers(0, V, 7).astype(np.int32), 7, tag=1)
    assert engine.submit(rng.integers(0, V, 1).astype(np.int32), 1, tag=2)
    done = engine.run_until(6)
    again = np.concatenate([prompt[:8], rng.integers(0, V, 3)]).astype(np.int32)
    assert engine.submit(again, len(again), tag=3)
    done += engine.run_until(1)
    assert len(done) == 7 and all(len(c.response_tokens) == 12 for c in done)
    _check_against_reference(params, done)
    stats = engine.stats()
    # no prefix hit is ever served: every admission skipped the cache,
    # which holds nothing, and only local prefills were built; what was
    # saved is the group's copy-on-write share alone
    assert stats["prefix_skipped_recurrent"] == 4
    assert engine._prefix_cache.stats() == {"cached_pages": 0, "hits": 0, "misses": 0, "evictions": 0}
    assert {key[0] for key in engine._prefill_fns} == {"local"}
    assert engine.prefix_tokens_saved == 3 * 8
    # the window alone: 3 layers x 2 rows x (48 + 8) float32 a lane
    assert stats["state_bytes_per_lane"] == LAYERS * 2 * ROW * 4
    assert stats["state_forks"] == 3
    # every layer has a router, every expert is held, one pick a token
    assert stats["expert_tokens"].shape == (LAYERS, E)
    decoded = sum(len(c.response_tokens) for c in done)
    np.testing.assert_array_equal(stats["expert_tokens"].sum(axis=1), [K * decoded] * LAYERS)
    assert stats["held_expert_tokens"] == K * decoded * LAYERS
    assert stats["absent_expert_tokens"] == 0 and stats["zero_expert_tokens"] == 0


def test_a_forked_member_continues_bit_for_bit_as_its_leader(net):
    """Greedy sampling: every member of a group decodes the leader's
    tokens, so its recorded log-probabilities and values must be the
    leader's bit for bit, which they are only if the fork gave it the
    leader's window (the lanes held another prompt's before).  Then
    :func:`fork_cache` alone on this cache: the members' window rows are
    the leader's, the source untouched, pad rows dropped."""
    model, params = net
    engine = _engine(model, params, temperature=0.0, lanes=4)
    rng = np.random.default_rng(3)
    assert engine.submit_group(rng.integers(0, V, 9).astype(np.int32), 4, 9, tag="warm")
    engine.run_until(4)  # every lane now holds a finished sequence's window
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
        conv=tuple(c + jnp.arange(4.0)[:, None] + 1 for c in cache.conv),
        k=tuple(k.at[2].set(7.0) for k in cache.k),
    )
    forked = fork_cache(
        cache, jnp.asarray([2, 0]), jnp.asarray([5, 0]), jnp.asarray([1, 0]), jnp.asarray([3, 4])
    )
    assert forked.ssm == ()
    for before, after in zip(cache.conv, forked.conv):
        np.testing.assert_array_equal(after[3], before[1])  # the member has the leader's rows
        np.testing.assert_array_equal(after[:3], before[:3])  # lane 4 is out of range: dropped
        assert float(jnp.max(jnp.abs(before[3] - before[1]))) >= 2.0  # what sharing would leave
    np.testing.assert_array_equal(forked.k[0][5], cache.k[0][2])
    assert not np.any(np.asarray(forked.v[0]))


def test_speculation_and_the_tail_prefill_are_refused_for_a_window_alone(net):
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


@pytest.mark.parametrize("carries", [False, True])
def test_the_router_matches_reference_with_and_without_a_layer_before_it(net, carries):
    """:class:`MLPRouter` alone on a seeded input: logits and the state it
    hands on are the reference's, with a state coming in (scaled by the
    learned vector) and without (the first layer: no such vector)."""
    _model, params = net
    p = params["params"]["block_1" if carries else "block_0"]["router"]
    rng = np.random.default_rng(8)
    g = jnp.asarray(rng.normal(size=(2, 5, D)), jnp.float32)
    r = jnp.asarray(rng.normal(size=(2, 5, RW)), jnp.float32) if carries else None
    logits, state = MLPRouter(RW, E, K, 1e-5).apply({"params": p}, g, r)
    with jax.default_matmul_precision("highest"):
        want_logits, want_state = ref.router(p, g, r, GEO)
    np.testing.assert_allclose(logits, want_logits, atol=1e-5)
    np.testing.assert_allclose(state, want_state, atol=1e-5)
    assert state.shape == (2, 5, RW) and logits.dtype == jnp.float32
    if carries:  # the state that came in matters
        other, _ = MLPRouter(RW, E, K, 1e-5).apply({"params": p}, g, 0 * r)
        assert float(jnp.max(jnp.abs(other - logits))) > 1e-3


def test_the_routers_stream_crosses_three_layers(net):
    """The second stream end to end: the third layer's router
    probabilities are the reference's, whose third router reads what the
    second handed on, which read the first's; and a model whose routers
    each start from nothing (the stream cut between layers, on the
    reference's side) picks differently."""
    model, params = net
    tok = jnp.asarray(np.random.default_rng(12).integers(0, V, (2, 9)))
    _out, sown = model.apply(params, tok, mutable=["intermediates"])
    _x, routing = ref.trunk(params, tok, GEO)
    for i in range(LAYERS):
        probs = sown["intermediates"][f"block_{i}"]["experts"]["router_probs"][0]
        np.testing.assert_allclose(probs, routing[i][0], atol=1e-5)
        ids = sown["intermediates"][f"block_{i}"]["experts"]["expert_ids"][0]
        np.testing.assert_array_equal(ids[..., 0], jnp.argmax(routing[i][1], axis=-1))
    # the same third layer with no state coming in
    p2 = params["params"]["block_2"]
    x2, _ = ref.trunk(params, tok, GEO, upto=2)
    g = ref._rms_norm(
        ref._merge(
            p2["attn_res_scale"], p2["attn_res_bias"], x2,
            ref._attention(
                p2["attn"], ref._rms_norm(x2, p2["attn_norm"]["scale"], GEO.eps),
                jnp.broadcast_to(jnp.tril(jnp.ones((9, 9), bool)), (2, 9, 9)),
                jnp.broadcast_to(jnp.arange(9), (2, 9)), GEO,
            ),
        ),
        p2["ffn_norm"]["scale"], GEO.eps,
    )
    cut = jax.nn.softmax(ref.router(p2["router"], g, None, GEO)[0], axis=-1)
    assert float(jnp.max(jnp.abs(cut - routing[2][0]))) > 1e-3


def test_top_one_picks_and_weights():
    """``RoutedExperts`` handed logits: one pick a token, the largest of
    ``p + bias`` (the bias only chooses), weighing its own probability
    with no renormalisation; in the streamed and in the sorted form."""
    rng = np.random.default_rng(4)
    module = RoutedExperts(E, 1, F, False, choice_bias=True)
    h = jnp.asarray(rng.normal(size=(1, 6, D)), jnp.float32)
    logits = jnp.asarray(rng.normal(size=(1, 6, E)), jnp.float32)
    params = jax.device_get(module.init(jax.random.PRNGKey(0), h, logits))
    assert "router" not in params["params"]
    bias = np.asarray([0.0, 0.9, 0.0, 0.0], np.float32)
    params = {"params": {**params["params"], "router_bias": bias}}
    y, sown = module.apply(params, h, logits, mutable=["intermediates"])
    probs = np.asarray(jax.nn.softmax(logits, axis=-1))[0]
    picks = np.argmax(probs + bias, axis=-1)
    np.testing.assert_array_equal(sown["intermediates"]["expert_ids"][0][0, :, 0], picks)
    assert np.any(picks != np.argmax(probs, axis=-1))  # the bias chose somewhere
    bank = params["params"]
    want = np.stack([
        probs[t, e] * (
            (jax.nn.silu(h[0, t] @ bank["w_gate"][e]) * (h[0, t] @ bank["w_up"][e])) @ bank["w_down"][e]
        )
        for t, e in enumerate(picks)
    ])
    np.testing.assert_allclose(y[0], want, atol=1e-5)
    many = jnp.tile(h, (1, 100, 1))  # 600 tokens: the sorted form
    y_sorted = module.apply(params, many, jnp.tile(logits, (1, 100, 1)))
    np.testing.assert_allclose(y_sorted[0, :6], want, atol=1e-5)


def test_the_cut_is_the_first_layers_of_the_uncut_stack():
    """The stack test that stands where a share test would (every expert
    is held, so nothing is left out of a layer): the program's 3-layer cut,
    given the first three layers' weights of a 5-layer model, gives the
    hidden state the UNCUT reference stack has after its third layer, and
    the logits its heads give there.  (The cell's cut is 20 of 40.)"""
    uncut = build_genrl_model(_args(cfg={**CFG, "num_hidden_layers": 5}))
    full = _seeded(uncut, 7, 13)
    cut = build_genrl_model(_args())
    p = full["params"]
    held = {"params": {k: v for k, v in p.items() if k not in ("block_3", "block_4")}}
    tok = jnp.asarray(np.random.default_rng(6).integers(0, V, (2, 10)))
    out = cut.apply(held, tok)
    geo5 = ref.geometry({**CFG, "num_hidden_layers": 5})
    x3, routing = ref.trunk(full, tok, geo5, upto=3)
    assert _min_gap(routing) > 1e-5
    logits, values = ref.heads(p["final_norm"], p["policy_head"], p["value_head"], x3, geo5)
    np.testing.assert_allclose(out.policy_logits, logits, atol=ATOL)
    np.testing.assert_allclose(out.baseline, values, atol=ATOL)
    # and the layers cut off do matter
    deep = ref.forward(full, tok, geo5)[0]
    assert float(jnp.max(jnp.abs(deep - logits))) > 100 * ATOL


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
    through the shifted, run-cut convolutions and the router's stream
    against autodiff through the reference's."""
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
    # every new leaf takes a gradient
    block = grads["params"]["block_1"]
    for name in ("conv_w", "conv_b", "mix_w", "mix_b", "k_temp"):
        assert float(jnp.max(jnp.abs(block["attn"][name]))) > 1e-8, name
    for name in ("q", "k", "v1", "v2", "proj"):
        assert float(jnp.max(jnp.abs(block["attn"][name]["kernel"]))) > 1e-8, name
    for name in ("carry_scale", "norm_scale"):
        assert float(jnp.max(jnp.abs(block["router"][name]))) > 1e-8, name
    for name in ("attn_res_scale", "attn_res_bias", "ffn_res_scale", "ffn_res_bias"):
        assert float(jnp.max(jnp.abs(block[name]))) > 1e-8, name
    real = float(jnp.sum(packed["segment_ids"] > 0))
    assert float(metrics["moe_held_picks"]) == K * LAYERS * real
    assert float(metrics["moe_absent_picks"]) == 0


def test_packed_rows_of_several_sequences_match_the_reference_on_each(net):
    """Two and more sequences a row: the window and the value shift are
    cut at every segment's start, so the loss term is the token-weighted
    mean of the reference's per-sequence terms (no advantage norm, so that
    the terms separate)."""
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
    assert stats["state_bytes_per_lane"] == LAYERS * 2 * ROW * 4


def test_the_stack_and_its_two_forms_say_what_they_are_once_a_traced_shape(net):
    """``model.layers`` names the layer kind, ``cca.form`` the path a
    traced shape took and the window it carries, ``router.form`` the
    scorer and whether a state came in: one zero-length span each a
    traced shape, none at the weights' making."""
    from scalerl_tpu.models import routed_ffn, transformer

    model, params = net
    for note in (transformer._note_layers, transformer._note_cca_form, routed_ffn._note_router_form):
        note.cache_clear()
    before = tracing.span_totals()
    tok = jnp.zeros((2, 7), jnp.int32)
    jax.jit(lambda t: model.apply(params, t)).lower(tok)
    jax.jit(lambda t: model.apply(params, t)).lower(tok)  # a shape is noted once
    cache = model.init_paged_cache(9, 4, lanes=3)
    z = jnp.zeros((3, 1), jnp.int32)
    jax.jit(lambda c: model.apply(
        params, z, positions=z, paged_cache=c, page_ids=z, page_offsets=z,
        page_table=jnp.zeros((3, 6), jnp.int32), attn_lengths=jnp.ones((3,), jnp.int32),
    )).lower(cache)
    after = tracing.span_totals()
    count = lambda name: after[name]["count"] - before.get(name, {"count": 0})["count"]  # noqa: E731
    assert count("model.layers") == 2
    assert count("cca.form") == 2  # whole [2, 7] and decode [3, 1]: the layers share a shape
    assert count("router.form") == 4  # each shape with and without a state coming in


def test_a_cache_is_cut_and_joined_for_a_stack_that_mixes_page_and_window_layers():
    """:func:`_layer_entries` and :func:`_join` on a stack that mixes a
    layer owning ``{k, v, conv}`` with one owning ``{k, v}``: each layer
    gets its own arrays in layer order, the window goes to the layer that
    owns one, and joining the entries gives the cache back."""
    cca = block_spec(
        "zaya", head_dim=DH, expert_width=F, router_width=RW, num_experts=E,
        experts_per_token=1, kv_heads=KV, cca_time0=2, cca_time1=2,
    )
    mha = BlockSpec()
    specs = (cca, mha, cca)
    assert [sorted(s.owns) for s in specs] == [["conv", "k", "v"], ["k", "v"], ["conv", "k", "v"]]
    cache = ModelCache(
        k=tuple(jnp.full((2, 2, 4), i) for i in (10, 11, 12)),
        v=tuple(jnp.full((2, 2, 4), i) for i in (20, 21, 22)),
        conv=tuple(jnp.full((3, 2, 5), i) for i in (30, 32)),
    )
    entries = _layer_entries(cache, specs)
    assert [int(e.k[0][0, 0, 0]) for e in entries] == [10, 11, 12]
    assert [int(e.v[0][0, 0, 0]) for e in entries] == [20, 21, 22]
    assert [len(e.conv) for e in entries] == [1, 0, 1] and all(e.ssm == () and e.rows == () for e in entries)
    assert int(entries[2].conv[0][0, 0, 0]) == 32
    joined = _join(entries)
    for got, want in zip(jax.tree_util.tree_leaves(joined), jax.tree_util.tree_leaves(cache)):
        np.testing.assert_array_equal(got, want)
    assert jax.tree_util.tree_structure(joined) == jax.tree_util.tree_structure(cache)
    # a model may not mix attention kinds, but the cache would serve one
    with pytest.raises(ValueError, match="attention kind is not the model's"):
        TransformerPolicy(
            num_actions=V, vocab_size=V, d_model=D, num_heads=H, num_layers=2,
            block=cca, layers=(cca, mha),
        ).layer_specs
