"""The ``olmoe`` block family of the token model (ISSUE 25): RMSNorm,
rotary positions, RMSNorm on q and k, and dropless top-k routed SwiGLU
experts, through the same engine, cache and learner as the GPT-2 block.

Every comparison is against ``benchmark/reference/olmoe.py`` (plain
``jax.numpy``, float32 at ``highest``, a masked loop over the experts).
The model here is 2 layers, hidden 64, 4 heads of 16, 8 experts of width
32 with 3 a token, float32 on both sides.  At that size and precision the
two sides see the same router probabilities to about 1e-7 while the
smallest gap between a kept and a left-out probability over a few hundred
tokens is about 1e-4, so a routing flip (the two sides picking different
experts) cannot happen and the tolerance is 1e-4 or tighter; each routed
case asserts that gap rather than trust it.
"""

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from scalerl_tpu.agents.token_ppo import (
    TokenPPOAgent,
    token_ppo_loss,
    token_ppo_packed_loss,
)
from scalerl_tpu.config import GenRLArguments, parse_args
from scalerl_tpu.genrl.continuous import ContinuousConfig, ContinuousEngine
from scalerl_tpu.genrl.rollout import pack_learner_batch
from scalerl_tpu.models import routed_ffn
from scalerl_tpu.models.routed_ffn import RoutedExperts, router_balance
from scalerl_tpu.models.transformer import (
    BlockSpec,
    TransformerPolicy,
    block_spec,
    packed_attention_mask,
)
from scalerl_tpu.runtime import telemetry
from scalerl_tpu.trainer.sequence_rl import build_genrl_model

ROOT = Path(__file__).resolve().parents[1]
ATOL = 1e-4  # see the module docstring
V, D, H, HD, E, K, F = 53, 64, 4, 16, 8, 3, 32
CFG = dict(
    vocab_size=V, hidden_size=D, num_hidden_layers=2, num_attention_heads=H,
    num_experts=E, num_experts_per_tok=K, intermediate_size=F,
    rms_norm_eps=1e-5, rope_theta=10000.0, norm_topk_prob=False,
    router_aux_loss_coef=0.01,
)


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"ref_{name}", ROOT / "benchmark" / "reference" / f"{name}.py"
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load("olmoe")
ref_ppo = _load("token_ppo")
GEO = ref.geometry(CFG)


@pytest.fixture(autouse=True)
def _fresh_registry():
    telemetry.reset()
    yield
    telemetry.reset()


def _args(*extra):
    args = parse_args(
        GenRLArguments,
        ref.program_argv(CFG)
        + ["--prompt-len", "12", "--max-new-tokens", "12", "--logger-backend", "none"]
        + list(extra),
    )
    args.validate()
    return args


@pytest.fixture(scope="module")
def net():
    """The model as the program's arguments build it, and its weights."""
    model = build_genrl_model(_args())
    params = model.init(jax.random.PRNGKey(3), jnp.zeros((1, 2), jnp.int32))
    return model, params


def _min_gap(tokens, params):
    _logits, _values, routing = ref.forward(params, tokens, GEO)
    return min(float(jnp.min(gap)) for _p, _w, gap in routing)


def test_program_arguments_choose_the_family(net):
    model, params = net
    assert model.block == block_spec(
        "olmoe", head_dim=HD, norm_eps=1e-5, rope_theta=10000.0,
        num_experts=E, experts_per_token=K, expert_width=F,
    )
    assert model.head_dim == HD
    block = params["params"]["block_0"]
    assert set(block) == {
        "attn_norm", "q_norm", "k_norm", "qkv", "proj", "ffn_norm", "experts",
    }
    assert set(block["experts"]) == {"router", "w_gate", "w_up", "w_down"}
    assert block["experts"]["w_gate"].shape == (E, D, F)
    assert block["experts"]["w_down"].shape == (E, F, D)
    assert "pos_embed" not in params["params"]  # rotary: no position table
    # a bank's fan-in is its middle axis: unit-variance inputs stay of order one
    assert 0.8 < float(jnp.std(block["experts"]["w_gate"])) * D**0.5 < 1.2
    with pytest.raises(ValueError, match="block_family"):
        _args("--block-family", "llama")


def test_full_forward_matches_reference(net):
    model, params = net
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, V, (2, 300)), jnp.int32)
    out = model.apply(params, tokens)  # 600 tokens: the sorted form
    logits, values, _routing = ref.forward(params, tokens, GEO)
    assert _min_gap(tokens, params) > 1e-5
    np.testing.assert_allclose(out.policy_logits, logits, atol=ATOL)
    np.testing.assert_allclose(out.baseline, values, atol=ATOL)
    # activations stay of order one through the layers with seeded weights
    assert 0.3 < float(jnp.std(out.policy_logits)) < 3.0


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


def test_engine_prefill_decode_and_fork_match_reference(net):
    """Prefill, then paged decode, through ``ContinuousEngine``: positions
    run well past the first page of 4 (RoPE through the cache), and a
    forked group of 4 shares its prompt's pages copy-on-write (the
    partial last page is copied: rotated keys with it)."""
    model, params = net
    engine = _engine(model, params)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, V, 10).astype(np.int32)  # 2 full pages + a partial one
    assert engine.submit_group(prompt, 4, 10, tag=0)
    single = rng.integers(0, V, 7).astype(np.int32)
    assert engine.submit(single, 7, tag=1)
    done = engine.run_until(5)
    assert len(done) == 5 and all(len(c.response_tokens) == 12 for c in done)
    group = [c for c in done if c.tag == 0]
    assert len({tuple(c.response_tokens.tolist()) for c in group}) > 1  # sampled apart
    _check_against_reference(params, done)
    stats = engine.stats()
    # every decoded token of every layer was routed to K experts, none dropped
    assert stats["expert_tokens"].shape == (2, E)
    decoded = sum(len(c.response_tokens) for c in done)
    np.testing.assert_array_equal(stats["expert_tokens"].sum(axis=1), [K * decoded] * 2)
    assert 0 < stats["expert_hits"] <= stats["expert_substeps"] * E


def test_engine_counts_ride_the_one_batched_read(net, monkeypatch):
    import scalerl_tpu.genrl.continuous as cont

    model, params = net
    engine = _engine(model, params, steps_in_flight=1)
    engine.submit(np.arange(5, dtype=np.int32), 5)
    engine.step()  # admission and the first macro-step: compiles
    gets = []
    real = cont._device_get
    monkeypatch.setattr(cont, "_device_get", lambda x: (gets.append(1), real(x))[1])
    before = engine.stats()["expert_tokens"].sum()
    engine.step()
    assert len(gets) == 1  # the counts came back inside the macro-step's read
    assert engine.stats()["expert_tokens"].sum() == before + 2 * K * 3


def test_tail_prefill_over_a_cached_prefix_matches_reference(net):
    """A second admission of a prompt whose first pages are cached takes
    the shared-table tail prefill: its queries attend cached keys that
    were rotated when they were written."""
    model, params = net
    engine = _engine(model, params)
    rng = np.random.default_rng(2)
    shared = rng.integers(0, V, 8).astype(np.int32)  # two full pages
    first = np.concatenate([shared, rng.integers(0, V, 3)]).astype(np.int32)
    second = np.concatenate([shared, rng.integers(0, V, 4)]).astype(np.int32)
    assert engine.submit(first, len(first), tag=0)
    done = engine.run_until(1)
    assert engine.submit(second, len(second), tag=1)
    done += engine.run_until(1)
    assert engine.prefix_tokens_saved >= 8  # the tail path ran
    _check_against_reference(params, done)


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
    """Packed rows with per-segment positions through the segment path
    (the dense packed mask, and the flash segment kernel in interpret
    mode): each segment's outputs equal the reference's on that sequence
    alone."""
    model, params = net
    if kernel == "segment_flash":
        from scalerl_tpu.ops.pallas_attention import segment_flash_attention

        model = model.clone(segment_attn_fn=segment_flash_attention)
    tok, seg, pos = _rows(4, [9, 14, 6], 32)
    out = model.apply(params, tok, positions=pos, segment_ids=seg)
    logits, values, _routing = ref.forward(
        params, tok, GEO, positions=pos, mask=packed_attention_mask(seg)
    )
    real = np.asarray(seg)[0] > 0
    np.testing.assert_allclose(out.policy_logits[0][real], logits[0][real], atol=ATOL)
    np.testing.assert_allclose(out.baseline[0][real], values[0][real], atol=ATOL)
    alone, _v, _r = ref.forward(params, tok[:, 9:23], GEO)  # the middle segment
    np.testing.assert_allclose(out.policy_logits[0, 9:23], alone[0], atol=ATOL)


_HYPER = dict(
    clip_range=0.2, value_cost=0.5, entropy_cost=0.01, kl_cost=0.0, adv_norm=True,
    router_aux_loss_coef=0.01,
)
_KW = {("router_aux_coef" if k == "router_aux_loss_coef" else k): v for k, v in _HYPER.items()}


def _sequences(seed, n, P=8, R=8):
    rng = np.random.default_rng(seed)
    plens, rlens = rng.integers(2, P + 1, n), rng.integers(2, R + 1, n)
    return dict(
        prompts=[rng.integers(0, V, a).astype(np.int32) for a in plens],
        resps=[rng.integers(0, V, b).astype(np.int32) for b in rlens],
        logps=[np.log(rng.uniform(0.05, 0.5, b)).astype(np.float32) for b in rlens],
        vals=[rng.normal(0, 0.1, b).astype(np.float32) for b in rlens],
        rewards=rng.uniform(0, 1, n).astype(np.float32),
        gens=np.zeros(n, np.int32),
    )


def _padded(seqs, P=8, R=8):
    n = len(seqs["prompts"])
    tokens = np.zeros((n, P + R), np.int32)
    logp, val, mask = (np.zeros((n, R), np.float32) for _ in range(3))
    for i in range(n):
        a, b = len(seqs["prompts"][i]), len(seqs["resps"][i])
        tokens[i, P - a : P] = seqs["prompts"][i]
        tokens[i, P : P + b] = seqs["resps"][i]
        logp[i, :b], val[i, :b], mask[i, :b] = seqs["logps"][i], seqs["vals"][i], 1.0
    return {
        "tokens": jnp.asarray(tokens), "behavior_logp": jnp.asarray(logp),
        "value": jnp.asarray(val), "mask": jnp.asarray(mask),
        "reward": jnp.asarray(seqs["rewards"]), "generation": jnp.asarray(seqs["gens"]),
        "prompt_len": jnp.asarray([len(p) for p in seqs["prompts"]], jnp.int32),
    }


def _packed(seqs, S=16):
    pk = pack_learner_batch(
        seqs["prompts"], seqs["resps"], seqs["logps"], seqs["vals"],
        seqs["rewards"], seqs["gens"], pack_len=S,
    )
    fields, _prios = pk.fields()
    return {k: jnp.asarray(v) for k, v in fields.items()}, pk


def test_learner_loss_aux_and_gradients_match_reference(net):
    """The padded learner on one sequence against the reference's loss
    with the load-balancing term, and ``jax.grad`` of each."""
    model, params = net
    seqs = _sequences(6, 1)
    batch = _padded(seqs)
    a, b = len(seqs["prompts"][0]), len(seqs["resps"][0])
    seq = {
        "tokens": jnp.asarray(np.concatenate([seqs["prompts"][0], seqs["resps"][0]])),
        "mask": jnp.asarray(np.r_[np.zeros(a), np.ones(b)], jnp.float32),
        "behavior_logp": jnp.asarray(np.r_[np.zeros(a), seqs["logps"][0]], jnp.float32),
        "value": jnp.asarray(np.r_[np.zeros(a), seqs["vals"][0]], jnp.float32),
        "reward": jnp.full((a + b,), seqs["rewards"][0], jnp.float32),
    }
    assert _min_gap(seq["tokens"][None], params) > 1e-5
    (total, metrics), grads = jax.value_and_grad(
        lambda w: token_ppo_loss(w, w, model, batch, **_KW), has_aux=True
    )(params)
    (want, parts), want_grads = jax.value_and_grad(
        lambda w: ref.ppo_loss(ref_ppo, w, w, seq, GEO, _HYPER), has_aux=True
    )(params)
    np.testing.assert_allclose(float(total), float(want), atol=1e-5)
    np.testing.assert_allclose(float(metrics["moe_aux_loss"]), float(parts["moe_aux_loss"]), atol=1e-6)
    np.testing.assert_allclose(float(metrics["moe_max_load"]), float(parts["moe_max_load"]), atol=1e-6)
    assert float(metrics["moe_aux_loss"]) >= 1.0 - 1e-6  # 1 is perfect balance
    got, _ = ravel_pytree(grads)
    exp, _ = ravel_pytree(want_grads)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), atol=1e-5, rtol=1e-4)
    # the router learns from the term: its gradient moves with the coefficient
    no_aux = jax.grad(
        lambda w: token_ppo_loss(w, w, model, batch, **{**_KW, "router_aux_coef": 0.0})[0]
    )(params)
    delta = np.asarray(grads["params"]["block_0"]["experts"]["router"]) - np.asarray(
        no_aux["params"]["block_0"]["experts"]["router"]
    )
    assert np.abs(delta).max() > 1e-6


def test_packed_learner_equals_padded_learner(net):
    """The same ragged sequences packed into rows (several segments a
    row, positions reset per segment) and padded: loss, aux term and
    gradients agree, so the segment path carries RoPE and the router's
    real-token mask as the padded path does."""
    model, params = net
    seqs = _sequences(7, 5)
    padded = _padded(seqs)
    packed, pk = _packed(seqs, S=32)
    assert pk.rows < 5
    (l1, m1), g1 = jax.value_and_grad(
        lambda w: token_ppo_loss(w, w, model, padded, **_KW), has_aux=True
    )(params)
    (l2, m2), g2 = jax.value_and_grad(
        lambda w: token_ppo_packed_loss(w, w, model, packed, **_KW), has_aux=True
    )(params)
    np.testing.assert_allclose(float(l1), float(l2), atol=1e-5)
    for key in ("pg_loss", "value_loss", "moe_aux_loss", "moe_max_load"):
        np.testing.assert_allclose(float(m1[key]), float(m2[key]), atol=1e-5)
    f1, _ = ravel_pytree(g1)
    f2, _ = ravel_pytree(g2)
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f2), atol=1e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# the routed FFN alone


def _ffn_reference(p, x, k):
    """Every token through every expert, masked: no sort, nothing dropped."""
    with jax.default_matmul_precision("highest"):
        probs = jax.nn.softmax(x @ p["router"], axis=-1)
        weights, gap = ref.router_choice(probs, k, False)
        y = jnp.zeros_like(x)
        for e in range(p["router"].shape[1]):
            gate = jax.nn.silu(x @ p["w_gate"][e]) * (x @ p["w_up"][e])
            y = y + weights[:, e, None] * (gate @ p["w_down"][e])
    return y, weights, gap


def _ffn_case(n_tokens, router):
    ffn = RoutedExperts(E, K, F)
    x = jax.random.normal(jax.random.PRNGKey(n_tokens), (1, n_tokens, D))
    p = dict(ffn.init(jax.random.PRNGKey(1), x)["params"])
    if router != "seeded":
        x = x.at[..., 0].set(4.0)  # a feature every token carries
    if router == "skewed":
        # every token's first pick is expert 0: 8/3 of the mean load, all kept
        p["router"] = p["router"].at[0, 0].set(2.0)
    elif router == "empty":
        # experts 5..7 score far below the rest for every token: never picked
        p["router"] = p["router"].at[:, 5:].set(0.0).at[0, 5:].set(-5.0)
    return ffn, {"params": p}, x


@pytest.mark.parametrize("router", ["seeded", "skewed", "empty"])
@pytest.mark.parametrize("n_tokens", [1, 7, 64, 1000])
def test_routed_ffn_is_exact_and_dropless(n_tokens, router):
    """1 to 1,000 tokens (the streamed form up to 512, the sorted form
    beyond), under a router that overloads one expert and one that leaves
    experts empty: the output equals the masked loop's, and every token
    is counted at exactly K experts."""
    ffn, params, x = _ffn_case(n_tokens, router)
    y, sown = ffn.apply(params, x, mutable=["intermediates"])
    want, weights, gap = _ffn_reference(params["params"], x[0], K)
    assert float(jnp.min(gap)) > 1e-6
    np.testing.assert_allclose(y[0], want, atol=1e-5)
    balance = router_balance(
        {"block_0": {"experts": sown["intermediates"]}}, jnp.ones((1, n_tokens))
    )
    counts = np.asarray(balance.counts[0])
    assert counts.sum() == K * n_tokens  # nothing dropped
    np.testing.assert_array_equal(counts, np.asarray((weights > 0).sum(axis=0)))
    if router == "empty":
        assert counts[5:].sum() == 0 and counts[:5].sum() == K * n_tokens
    if router == "skewed":
        assert counts[0] == n_tokens  # 8/3 of the mean load, above any capacity factor


@pytest.mark.parametrize("n_tokens", [5, 300])
def test_the_two_forms_agree_in_value_and_gradient(n_tokens):
    ffn, params, x = _ffn_case(n_tokens, "seeded")
    p = params["params"]
    probs = jax.nn.softmax(x[0] @ p["router"], axis=-1)
    top_p, top_i = jax.lax.top_k(probs, K)

    def run(form, banks):
        return form(x[0], top_p, top_i, *banks)

    banks = (p["w_gate"], p["w_up"], p["w_down"])
    np.testing.assert_allclose(
        run(routed_ffn._streamed, banks), run(routed_ffn._sorted, banks), atol=1e-5
    )
    g1 = jax.grad(lambda b: jnp.sum(run(routed_ffn._streamed, b) ** 2))(banks)
    g2 = jax.grad(lambda b: jnp.sum(run(routed_ffn._sorted, b) ** 2))(banks)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# the sharded learner, and the GPT-2 default


@pytest.mark.parametrize("form", ["streamed", "sorted"])
def test_dp2_mp2_learn_step_equals_the_one_device_step(form, monkeypatch):
    """Four virtual devices as dp=2 x mp=2: the expert banks shard over
    ``mp`` on their leading axis (never replicated), and one learn step
    gives the one-device step's loss and weights, in either form of the
    routed FFN (the sorted one is what a learner's row count takes)."""
    from jax.sharding import PartitionSpec as P

    if form == "sorted":
        monkeypatch.setattr(routed_ffn, "STREAMED_MAX_TOKENS", 0)

    from scalerl_tpu.parallel import make_mesh

    args = _args("--learner-packing", "true", "--learner-pack-len", "24", "--learning-rate", "1e-3")
    seqs = _sequences(8, 6)
    batch, _pk = _packed(seqs, S=24)
    batch = {k: v[:2] for k, v in batch.items()}
    assert batch["tokens"].shape[0] == 2
    plain = TokenPPOAgent(args, build_genrl_model(args))
    meshed = TokenPPOAgent(args, build_genrl_model(args))
    meshed.enable_mesh(make_mesh("dp=2,mp=2", devices=jax.devices()[:4]))
    bank = meshed.state.params["params"]["block_0"]["experts"]
    assert bank["w_gate"].sharding.spec == P("mp", None, None)
    assert bank["w_down"].sharding.spec == P("mp", None, None)
    assert bank["router"].sharding.spec in (P(), P(None, None))
    assert meshed.state.params["params"]["block_0"]["qkv"]["kernel"].sharding.spec == P(None, "mp")
    m1, m2 = plain.learn(dict(batch)), meshed.learn(dict(batch))
    for key in ("total_loss", "pg_loss", "moe_aux_loss", "moe_max_load", "grad_norm"):
        np.testing.assert_allclose(m1[key], m2[key], atol=2e-5, rtol=1e-4)
    w1, _ = ravel_pytree(jax.device_get(plain.state.params))
    w2, _ = ravel_pytree(jax.device_get(meshed.state.params))
    np.testing.assert_allclose(w1, w2, atol=2e-5)


def _parent_gpt2_forward(params, tokens, num_heads, num_layers):
    """The GPT-2 block's plain forward as the parent commit built it
    (``_Block`` and ``TransformerPolicy.__call__`` of PR 24, the causal
    full-attention path), written out with the same flax calls."""
    import flax.linen as nn

    from scalerl_tpu.ops.ring_attention import full_attention

    class ParentBlock(nn.Module):
        @nn.compact
        def __call__(self, x):
            B, T, d = x.shape
            h = nn.LayerNorm(use_bias=False, dtype=jnp.float32)(x)
            qkv = nn.Dense(3 * d, use_bias=False, name="qkv")(h)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            shape = (B, T, num_heads, d // num_heads)
            out = full_attention(q.reshape(shape), k.reshape(shape), v.reshape(shape), causal=True)
            x = x + nn.Dense(d, use_bias=False, name="proj")(out.reshape(B, T, d))
            h = nn.LayerNorm(use_bias=False, dtype=jnp.float32)(x)
            h = nn.Dense(4 * d, name="mlp_in")(h)
            h = nn.gelu(h)
            return x + nn.Dense(d, name="mlp_out")(h)

    class Parent(nn.Module):
        @nn.compact
        def __call__(self, obs):
            B, T = obs.shape
            x = nn.Embed(V, D, name="token_embed")(obs.astype(jnp.int32))
            pos_tab = self.param("pos_embed", nn.initializers.normal(0.02), (32, D), jnp.float32)
            x = x + pos_tab[jnp.broadcast_to(jnp.arange(T), (B, T))]
            for i in range(num_layers):
                x = ParentBlock(name=f"block_{i}")(x)
            x = nn.LayerNorm(use_bias=False, name="final_norm", dtype=jnp.float32)(x)
            return (
                nn.Dense(V, name="policy_head")(x),
                nn.Dense(1, name="value_head")(x).squeeze(-1),
            )

    parent = Parent()
    fresh = parent.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32))
    return fresh, jax.jit(parent.apply)(params, tokens)


def test_gpt2_default_tree_and_outputs_are_bit_identical_to_the_parent():
    """The defaults are the parent's GPT-2 block: the same parameter
    names in the same tree with the same seeded values, and the same
    outputs to the bit."""
    model = TransformerPolicy(
        num_actions=V, vocab_size=V, d_model=D, num_heads=H, num_layers=2, max_len=32
    )
    assert model.block == BlockSpec() == block_spec("gpt2")
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32))
    tokens = jnp.asarray(np.random.default_rng(9).integers(0, V, (3, 20)), jnp.int32)
    out = jax.jit(model.apply)(params, tokens)
    fresh, (logits, values) = _parent_gpt2_forward(params, tokens, H, 2)
    assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(fresh)
    for a, b in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(fresh)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(out.policy_logits, logits)
    np.testing.assert_array_equal(out.baseline, values)
    # and what the family adds is absent: no sown routing, no engine counters
    _out, sown = model.apply(params, tokens, mutable=["intermediates"])
    assert not sown.get("intermediates")
