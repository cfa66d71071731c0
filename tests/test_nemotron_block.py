"""The ``nemotron_h`` block family of the token model (ISSUE 40): a stack
of single-mixer layers laid out by a pattern string, Mamba-2 layers whose
recurrent state the generation engine keeps by lane beside the KV pages,
attention with fewer key/value heads than query heads and no position
signal, and relu^2 experts (two matrices, no gate) beside a shared expert
of its own width, over a held share of the experts.

Every comparison is against ``benchmark/reference/nemotron_h.py`` (plain
``jax.numpy``, float32 at ``highest``, the recurrence ONE TOKEN AT A TIME,
key/value heads repeated, a masked loop over the held experts) and, for
the learner, ``reference/token_ppo.py``.  The model here is the pattern
``MEM*E``: hidden 32, 4 Mamba heads of 8 in 2 groups with state 16 and a
chunk of 8 (so that every sequence below crosses chunk boundaries at
lengths that are no multiple of it), 4 query heads over 2 key/value heads
of 8, a router over 8 experts of width 16 with 3 a token, of which experts
0-3 are held, a shared expert of width 32; float32 on both sides.  At that
size and precision the two sides agree to about 1e-6 (the chunked form
sums a chunk's products in another order than the recurrence does), while
the smallest gap between a kept and a left-out router score is about
1e-4, so a routing flip cannot happen and the tolerance is 1e-4 or
tighter; each routed case asserts that gap rather than trust it.  A
reference whose matmul operands are rounded to float8 misses these by two
orders of magnitude, a state taken at a prompt bucket's end instead of the
prompt's true length by three, and a fork that leaves a member the state
its lane had before by as much
(``test_full_forward_matches_reference``,
``test_prefill_takes_the_state_at_the_true_length``,
``test_a_forked_member_continues_bit_for_bit_as_its_leader`` measure them).
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
    pattern_specs,
    prompt_attention_mask,
    run_ids,
    ssd_chunked,
    ssm_decode_update,
)
from scalerl_tpu.runtime import telemetry
from scalerl_tpu.trainer.sequence_rl import build_genrl_model

ROOT = Path(__file__).resolve().parents[1]
ATOL = 1e-4  # see the module docstring
PATTERN = "MEM*E"
V, D, H, KV, DH = 53, 32, 4, 2, 8
SH, SP, SN, SG, CHUNK = 4, 8, 16, 2, 8
E, HELD, K, F, FS = 8, 4, 3, 16, 32
CFG = dict(
    vocab_size=V, hidden_size=D, num_hidden_layers=len(PATTERN),
    hybrid_override_pattern=PATTERN, num_attention_heads=H, num_key_value_heads=KV,
    head_dim=DH, norm_eps=1e-5, mamba_num_heads=SH, mamba_head_dim=SP, ssm_state_size=SN,
    n_groups=SG, conv_kernel=4, chunk_size=CHUNK, intermediate_size=F,
    moe_intermediate_size=F, moe_shared_expert_intermediate_size=FS,
    n_routed_experts_published=E, n_routed_experts=HELD, first_expert=0, n_shared_experts=1,
    num_experts_per_tok=K, scoring_func="sigmoid", routed_scaling_factor=2.5,
    norm_topk_prob=True, mlp_hidden_act="relu2", router_aux_loss_coef=0.0,
)


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"ref_{name}", ROOT / "benchmark" / "reference" / f"{name}.py"
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load("nemotron_h")
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
    a small seeded router bias (so that the bias is not a silent zero)."""
    model = build_genrl_model(_args())
    params = model.init(jax.random.PRNGKey(3), jnp.zeros((1, 2), jnp.int32))
    params = _thaw(jax.tree_util.tree_map(np.asarray, jax.device_get(params)))
    rng = np.random.default_rng(11)
    for i, ch in enumerate(PATTERN):
        if ch == "E":
            params["params"][f"block_{i}"]["experts"]["router_bias"] = np.asarray(
                0.01 * rng.normal(size=E), np.float32
            )
    return model, params


def _min_gap(routing):
    return min(float(jnp.min(gap)) for _s, _w, gap in routing)


def test_program_arguments_choose_the_family(net):
    model, params = net
    spec = model.block
    assert spec == block_spec(
        "nemotron_h", head_dim=DH, norm_eps=1e-5, num_experts=E, experts_per_token=K,
        expert_width=F, norm_topk_prob=True, ffn_hidden=F, experts_held=HELD,
        routed_scaling=2.5, scoring="sigmoid", shared_experts=1, kv_heads=KV,
        expert_act="relu2", shared_width=FS, ssm_heads=SH, ssm_head_dim=SP, ssm_state=SN,
        ssm_groups=SG, ssm_conv=4, ssm_chunk=CHUNK,
    )
    # a per-layer list by the pattern: one mixer a layer, no positions
    assert model.layers == pattern_specs(spec, PATTERN)
    assert [s.mixer for s in model.layer_specs] == ["mamba", "experts", "mamba", "attention", "experts"]
    assert {s.layer for s in model.layer_specs} == {"mixer"} and spec.positions == "none"
    assert model.routed_layers == 2 and model.recurrent
    assert not TransformerPolicy(num_actions=V, vocab_size=V, d_model=D, num_heads=H, num_layers=1).recurrent
    p = params["params"]
    assert set(p) == {
        "token_embed", *(f"block_{i}" for i in range(5)), "final_norm", "policy_head", "value_head",
    }  # no position table
    assert set(p["block_0"]) == {"norm", "mixer"}
    channels = SH * SP + 2 * SG * SN
    assert {k: np.shape(v) for k, v in p["block_0"]["mixer"].items() if k not in ("in_proj", "out_proj")} == {
        "conv_w": (4, channels), "conv_b": (channels,), "dt_bias": (SH,), "A_log": (SH,),
        "D": (SH,), "norm_scale": (SH * SP,),
    }
    assert p["block_0"]["mixer"]["in_proj"]["kernel"].shape == (D, SH * SP + channels + SH)
    assert p["block_0"]["mixer"]["out_proj"]["kernel"].shape == (SH * SP, D)
    # grouped heads project q apart from the fused k and v of the few
    assert set(p["block_3"]) == {"norm", "q", "kv", "proj"}
    assert p["block_3"]["q"]["kernel"].shape == (D, H * DH)
    assert p["block_3"]["kv"]["kernel"].shape == (D, 2 * KV * DH)
    # relu^2 experts: two banks and no gate; the shared expert's own width
    assert set(p["block_1"]) == {"norm", "experts", "shared"}
    assert set(p["block_1"]["experts"]) == {"router", "router_bias", "w_up", "w_down"}
    assert p["block_1"]["experts"]["w_up"].shape == (HELD, D, F)
    assert p["block_1"]["experts"]["router"].shape == (D, E)
    assert {k: v["kernel"].shape for k, v in p["block_1"]["shared"].items()} == {
        "up": (D, FS), "down": (FS, D),
    }
    # the cache the model describes: pools for the attention layer alone,
    # a lane-indexed float32 state a Mamba layer, nothing for the experts
    cache = model.init_paged_cache(5, 4, lanes=3)
    assert isinstance(cache, ModelCache)
    assert [x.shape for x in cache.k + cache.v] == [(5, 4, KV * DH)] * 2
    assert [x.shape for x in cache.ssm] == [(3, SH, SP, SN)] * 2
    assert [x.shape for x in cache.conv] == [(3, 3, channels)] * 2
    assert {x.dtype for x in cache.ssm + cache.conv} == {jnp.dtype("float32")}
    with pytest.raises(ValueError, match="sized by its lanes"):
        model.init_paged_cache(5, 4)
    # the seeded decays at a zero input lie in (0.2, 1): dt in [0.001,
    # 0.1] a head, A in [1, 16]
    big = block_spec("nemotron_h", **{**_sizes(), "ssm_heads": 64, "ssm_groups": 8})
    wide = TransformerPolicy(
        num_actions=V, vocab_size=V, d_model=D, num_heads=H, num_layers=1, block=big,
        layers=pattern_specs(big, "M"),
    ).init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32))["params"]["block_0"]["mixer"]
    decay = np.exp(-np.asarray(jax.nn.softplus(wide["dt_bias"]) * jnp.exp(wide["A_log"])))
    step = np.asarray(jax.nn.softplus(wide["dt_bias"]))
    assert 0.2 < decay.min() and decay.max() < 1.0
    assert 0.001 <= step.min() and step.max() <= 0.1 + 1e-6 and step.max() > 10 * step.min()


def _sizes():
    return dict(
        head_dim=DH, norm_eps=1e-5, num_experts=E, experts_per_token=K, expert_width=F,
        norm_topk_prob=True, ffn_hidden=F, experts_held=HELD, routed_scaling=2.5,
        scoring="sigmoid", shared_experts=1, kv_heads=KV, expert_act="relu2", shared_width=FS,
        ssm_heads=SH, ssm_head_dim=SP, ssm_state=SN, ssm_groups=SG, ssm_conv=4, ssm_chunk=CHUNK,
    )


def test_arguments_the_family_refuses():
    with pytest.raises(ValueError, match="gpt2 \\| olmoe \\| longcat \\| joyai \\| nemotron_h"):
        _args("--block-family", "llama")
    with pytest.raises(ValueError, match="one character for each of n_layers"):
        _args("--n-layers", "4")
    with pytest.raises(ValueError, match="one character for each of n_layers"):
        _args("--layer-pattern", "MEMXE")
    with pytest.raises(ValueError, match="nemotron_h family's"):
        _args("--block-family", "gpt2")
    with pytest.raises(ValueError, match="kv_heads must divide"):
        _args("--kv-heads", "3")
    # a state cannot be rewound by a page cursor: the arguments say why
    with pytest.raises(ValueError, match="no cursor to rewind"):
        _args("--spec-enable", "true")
    with pytest.raises(ValueError, match="heads a multiple of the groups"):
        block_spec("nemotron_h", **{**_sizes(), "ssm_groups": 3})
    with pytest.raises(ValueError, match="M \\| E \\| \\* \\| -"):
        pattern_specs(block_spec("nemotron_h", **_sizes()), "MQ")
    with pytest.raises(ValueError, match="mixer-layer spec"):
        pattern_specs(block_spec("gpt2"), "M")
    # a dense FFN alone is a layer kind of the family too
    dense = TransformerPolicy(
        num_actions=V, vocab_size=V, d_model=D, num_heads=H, num_layers=2,
        block=block_spec("nemotron_h", **_sizes()),
        layers=pattern_specs(block_spec("nemotron_h", **_sizes()), "-*"),
    )
    tree = dense.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32))["params"]
    assert set(tree["block_0"]) == {"norm", "ffn"} and set(tree["block_0"]["ffn"]) == {"up", "down"}
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, V, (1, 9)), jnp.int32)
    want = ref.forward({"params": tree}, tokens, GEO._replace(pattern="-*"))[0]
    np.testing.assert_allclose(dense.apply({"params": tree}, tokens).policy_logits, want, atol=ATOL)
    assert not dense.recurrent


def test_full_forward_matches_reference(net):
    """37 tokens a row: four chunks of 8 and a tail of 5, against the
    recurrence one token at a time."""
    model, params = net
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, V, (2, 37)), jnp.int32)
    out = model.apply(params, tokens)
    logits, values, routing = ref.forward(params, tokens, GEO)
    assert _min_gap(routing) > 1e-5 and len(routing) == 2  # the two expert layers
    np.testing.assert_allclose(out.policy_logits, logits, atol=ATOL)
    np.testing.assert_allclose(out.baseline, values, atol=ATOL)
    assert 0.3 < float(jnp.std(out.policy_logits)) < 3.0
    # 600 tokens take the sorted expert form on a share (held 4 > 3 picks)
    long = jnp.asarray(np.random.default_rng(1).integers(0, V, (2, 300)), jnp.int32)
    want = ref.forward(params, long, GEO)
    assert _min_gap(want[2]) > 1e-6
    np.testing.assert_allclose(model.apply(params, long).policy_logits, want[0], atol=ATOL)
    # what the tolerance refuses: the reference itself at float8 operands
    low = ref.forward(params, tokens, ref.geometry(CFG, round_to="float8_e4m3fn"))
    assert float(jnp.median(jnp.abs(low[0] - logits))) > 100 * ATOL
    # and the order matters to the model: no position signal, but the
    # Mamba layers carry it
    swapped = tokens.at[:, [3, 4]].set(tokens[:, [4, 3]])
    assert float(jnp.max(jnp.abs(model.apply(params, swapped).policy_logits[:, -1] - out.policy_logits[:, -1]))) > 1e-3


def test_chunked_scan_is_the_recurrence_and_cuts_at_runs():
    """:func:`ssd_chunked` alone against the recurrence a token at a time,
    with two runs and a pad tail in a row: the state is zero at a run's
    start, passes through pad tokens unchanged and leaves at the last real
    token's value."""
    rng = np.random.default_rng(2)
    T, G = 21, SG
    x = jnp.asarray(rng.normal(size=(1, T, SH, SP)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(1, T, G, SN)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(1, T, G, SN)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, size=(1, T, SH)), jnp.float32)
    A = -jnp.asarray(rng.uniform(1, 4, size=(SH,)), jnp.float32)
    seg = jnp.asarray([[1] * 9 + [2] * 8 + [0] * 4])
    real = seg > 0
    x, dt = jnp.where(real[..., None, None], x, 0), jnp.where(real[..., None], dt, 0)
    y, last = ssd_chunked(x, dt, A, B, C, run_ids(seg), CHUNK)
    np.testing.assert_array_equal(run_ids(seg)[0], [1] * 9 + [2] * 12)

    def alone(lo, hi):
        S = jnp.zeros((1, SH, SP, SN))
        ys = []
        for t in range(lo, hi):
            yt, S = ssm_decode_update(S, x[:, t], dt[:, t], A, B[:, t], C[:, t], jnp.zeros((SH,)))
            ys.append(yt)
        return jnp.stack(ys, axis=1), S

    y1, _ = alone(0, 9)
    y2, S2 = alone(9, 17)
    np.testing.assert_allclose(y[:, :9], y1, atol=1e-5)
    np.testing.assert_allclose(y[:, 9:17], y2, atol=1e-5)
    np.testing.assert_allclose(last, S2, atol=1e-5)  # at the true length, not the row's end
    assert np.all(np.isfinite(np.asarray(y)))


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
    reference on that sequence ALONE, through the scan, the convolution
    and the attention (the dense packed mask, and the flash segment kernel
    in interpret mode on keys and values repeated to 4 heads); and
    changing a neighbour's tokens changes no logit of this one, bit for
    bit."""
    model, params = net
    tok, seg, pos = _rows(4, [9, 14, 6], 32)
    if kernel == "segment_flash":
        from scalerl_tpu.ops.pallas_attention import segment_flash_attention

        seen = []

        def kern(q, k, v, s):
            seen.append((q.shape[2], k.shape[2]))
            return segment_flash_attention(q, k, v, s)

        model = model.clone(segment_attn_fn=kern)
    out = model.apply(params, tok, positions=pos, segment_ids=seg)
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
    moved = model.apply(params, jnp.asarray(other), positions=pos, segment_ids=seg)
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
    out, written = model.apply(
        params, jnp.asarray(toks), positions=jnp.broadcast_to(pos, (3, P)),
        attn_mask=prompt_attention_mask(lengths, P), paged_cache=cache,
        page_ids=jnp.asarray(page_ids), page_offsets=jnp.asarray(offsets), state_lanes=lane_ids,
    )
    for r, lane in ((0, 2), (1, 0)):
        n = int(lengths[r])
        alone = model.apply(params, jnp.asarray(toks[r : r + 1, :n]))
        np.testing.assert_allclose(out.policy_logits[r, n - 1], alone.policy_logits[0, n - 1], atol=1e-5)
        # the state a prefill of the prompt ALONE, unpadded, leaves
        _o, own = model.apply(
            params, jnp.asarray(toks[r : r + 1, :n]), positions=jnp.arange(n)[None],
            attn_mask=prompt_attention_mask(jnp.asarray([n]), n),
            paged_cache=model.init_paged_cache(16, ps, lanes=1),
            page_ids=jnp.asarray(page_ids[r : r + 1, :n]), page_offsets=jnp.asarray(offsets[r : r + 1, :n]),
            state_lanes=jnp.asarray([0]),
        )
        for layer in range(2):
            np.testing.assert_allclose(written.ssm[layer][lane], own.ssm[layer][0], atol=1e-5)
            np.testing.assert_allclose(written.conv[layer][lane], own.conv[layer][0], atol=1e-6)
            assert float(jnp.max(jnp.abs(written.ssm[layer][lane]))) > 1e-3
    # lanes no row named are untouched, the pad row among them
    for layer in range(2):
        assert not np.any(np.asarray(written.ssm[layer][jnp.asarray([1, 3])]))
    # a state at the bucket's end (the prompt taken as 16 real tokens)
    _o, wrong = model.apply(
        params, jnp.asarray(toks[:1]), positions=pos[None],
        attn_mask=prompt_attention_mask(jnp.asarray([P]), P),
        paged_cache=model.init_paged_cache(16, ps, lanes=1),
        page_ids=jnp.asarray(table[:1, pos // ps]), page_offsets=jnp.asarray(pos[None] % ps),
        state_lanes=jnp.asarray([0]),
    )
    assert float(jnp.max(jnp.abs(wrong.ssm[0][0] - written.ssm[0][2]))) > 1000 * 1e-5


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
    # the state: 2 Mamba layers x (4 x 8 x 16 + 3 x 64) float32 a lane
    assert stats["state_bytes_per_lane"] == 2 * 4 * (SH * SP * SN + 3 * (SH * SP + 2 * SG * SN))
    assert stats["state_forks"] == 3
    # counts of the two expert layers only
    assert stats["expert_tokens"].shape == (2, E)
    decoded = sum(len(c.response_tokens) for c in done)
    np.testing.assert_array_equal(stats["expert_tokens"].sum(axis=1), [K * decoded] * 2)
    assert stats["held_expert_tokens"] + stats["absent_expert_tokens"] == K * decoded * 2
    assert stats["zero_expert_tokens"] == 0 and stats["held_expert_tokens"] > 0


def test_a_forked_member_continues_bit_for_bit_as_its_leader(net):
    """Greedy sampling: every member of a group decodes the leader's
    tokens, so its recorded log-probabilities and values must be the
    leader's bit for bit, which they are only if the fork gave it the
    leader's state (the lanes held another prompt's state before).  Then
    :func:`fork_cache` alone: rows copied, the source untouched, pad rows
    dropped; a member left with what its lane held is far away."""
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
    with pytest.raises(ValueError, match="no cursor to rewind"):
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
    through the chunked scan against autodiff through the recurrence."""
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
    # float32 sums in another order; a float8 reference misses by 1e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), atol=1e-5, rtol=1e-4)
    assert np.all(np.isfinite(np.asarray(got)))
    # every kind of mixer takes a gradient, the recurrence's own leaves too
    mixer = grads["params"]["block_0"]["mixer"]
    for name in ("A_log", "dt_bias", "D", "conv_w", "conv_b", "norm_scale"):
        assert float(jnp.max(jnp.abs(mixer[name]))) > 1e-7, name
    assert float(jnp.max(jnp.abs(grads["params"]["block_3"]["kv"]["kernel"]))) > 1e-7
    # the bias chooses and does not weigh: no gradient reaches it
    assert not np.any(np.asarray(grads["params"]["block_1"]["experts"]["router_bias"]))
    real = float(jnp.sum(packed["segment_ids"] > 0))
    assert float(metrics["moe_held_picks"] + metrics["moe_absent_picks"]) == K * 2 * real


def test_packed_rows_of_several_sequences_match_the_reference_on_each(net):
    """Two and more sequences a row: the loss term is the token-weighted
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


# ---------------------------------------------------------------------------
# the share


@pytest.mark.parametrize("n_tokens", [7, 600])
def test_the_shares_add_up(net, n_tokens):
    """Over ALL the shares of a layer (8 experts as 4 ranks of 2), the
    held parts summed, with the shared expert and the residual counted
    once, equal the UNCUT reference layer: what a rank leaves out is
    exactly what the other ranks add.  7 tokens take the streamed form,
    600 the sorted one on a share (held 2 < 3 picks keeps the streamed:
    so 2 ranks of 4 are summed too, where 600 tokens sort)."""
    model, params = net
    block = {k: v for k, v in params["params"]["block_1"].items()}
    rng = np.random.default_rng(n_tokens)
    x = jnp.asarray(rng.normal(size=(1, n_tokens, D)), jnp.float32)
    full_banks = {
        "w_up": jnp.asarray(rng.normal(size=(E, D, F)) / np.sqrt(D), jnp.float32),
        "w_down": jnp.asarray(rng.normal(size=(E, F, D)) / np.sqrt(F), jnp.float32),
    }
    whole = dict(block, experts={**block["experts"], **full_banks})
    uncut = GEO._replace(first_expert=0, held=E)
    want, routing = ref.layer(whole, x, None, "experts", uncut)
    assert float(jnp.min(routing[2])) > 1e-6
    u = ref._rms_norm(x, block["norm"]["scale"], GEO.eps)
    shared = ref._relu2(block["shared"]["up"]["kernel"], block["shared"]["down"]["kernel"], u, None)
    for held in (2, 4):
        total = jnp.zeros_like(x)
        for first in range(0, E, held):
            ffn = RoutedExperts(
                E, K, F, norm_topk_prob=True, held=held, first_expert=first, choice_bias=True,
                routed_scaling=2.5, scoring="sigmoid", act="relu2",
            )
            share = {
                **block["experts"],
                **{k: v[first : first + held] for k, v in full_banks.items()},
            }
            total = total + ffn.apply({"params": share}, u)
            # and each share alone is the reference's share
            alone, _r = ref.layer(
                dict(block, experts=share), x, None, "experts",
                GEO._replace(first_expert=first, held=held),
            )
            np.testing.assert_allclose(x + ffn.apply({"params": share}, u) + shared, alone, atol=1e-5)
        np.testing.assert_allclose(x + total + shared, want, atol=1e-5)


def test_relu2_experts_have_no_gate_and_swiglu_is_unchanged():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, D))
    plain = RoutedExperts(E, K, F)
    assert set(plain.init(jax.random.PRNGKey(1), x)["params"]) == {"router", "w_gate", "w_up", "w_down"}
    relu2 = RoutedExperts(E, K, F, act="relu2")
    p = relu2.init(jax.random.PRNGKey(1), x)["params"]
    assert set(p) == {"router", "w_up", "w_down"}
    probs = jax.nn.softmax(x[0] @ p["router"], axis=-1)
    top_p, top_i = jax.lax.top_k(probs, K)
    want = sum(
        top_p[:, j, None] * (jnp.square(jax.nn.relu(jnp.einsum("nd,ndf->nf", x[0], p["w_up"][top_i[:, j]])))[:, None, :] @ p["w_down"][top_i[:, j]])[:, 0]
        for j in range(K)
    )
    np.testing.assert_allclose(relu2.apply({"params": p}, x)[0], want, atol=1e-5)


# ---------------------------------------------------------------------------
# the decode update, alone


@pytest.mark.parametrize("shape", [(3, 4, 8, 16, 2), (2, 8, 8, 128, 8), (2, 64, 64, 128, 8)])
def test_ssm_decode_update_is_the_recurrence_a_head(shape):
    """``ssm_decode_update`` against the recurrence written out a head with
    ``einsum`` at ``highest``, at the rehearsal's sizes, with 128 states,
    and at the configuration's own 64 x 64 x 128 (two lanes): the state is
    the same products in the same order (1e-6), ``y`` a float32 sum of 128
    products in another order (1e-4 on values of order ten)."""
    L, Hh, P, N, G = shape
    k = jax.random.split(jax.random.PRNGKey(L + Hh), 6)
    state = jax.random.normal(k[0], (L, Hh, P, N))
    x, dt, A, B, C, D = args = (
        jax.random.normal(k[1], (L, Hh, P)), jax.nn.softplus(jax.random.normal(k[2], (L, Hh)) - 3),
        -jnp.exp(jax.random.uniform(k[3], (Hh,), minval=0.0, maxval=2.7)),
        jax.random.normal(k[4], (L, G, N)), jax.random.normal(k[5], (L, G, N)), jnp.ones((Hh,)),
    )
    Bh, Ch = jnp.repeat(B, Hh // G, axis=1), jnp.repeat(C, Hh // G, axis=1)  # [L, H, N]
    want = jnp.exp(dt * A)[..., None, None] * state + jnp.einsum("lh,lhp,lhn->lhpn", dt, x, Bh)
    want_y = jnp.einsum("lhpn,lhn->lhp", want, Ch, precision="highest") + D[None, :, None] * x
    y, new = jax.jit(ssm_decode_update)(state, *args)
    assert new.shape == state.shape and new.dtype == jnp.float32
    np.testing.assert_allclose(y, want_y, atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(new, want, atol=1e-6, rtol=1e-6)
    # a head reads ITS group's B and C: with the groups' rows swapped the
    # answer is another one
    swapped = args[:3] + (B[:, ::-1], C[:, ::-1], D)
    assert float(jnp.max(jnp.abs(ssm_decode_update(state, *swapped)[0] - want_y))) > 1e-2


def test_the_stack_says_what_it_is_once_a_traced_shape(net):
    """The ``model.layers`` note carries the pattern's layer kinds."""
    from scalerl_tpu.models import transformer
    from scalerl_tpu.runtime import tracing

    model, params = net
    transformer._note_layers.cache_clear()
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
    finally:
        tracing.span = keep
    notes = [attrs for name, attrs in seen if name == "model.layers"]
    assert len(notes) == 1
    assert notes[0]["layers"] == [
        "mixer/mamba", "mixer/experts", "mixer/mamba", "mixer/attention", "mixer/experts",
    ]
    assert (notes[0]["held"], notes[0]["num_experts"]) == (HELD, E)
