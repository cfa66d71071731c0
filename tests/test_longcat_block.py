"""The ``longcat`` block family of the token model (ISSUE 30): latent (MLA)
attention with its one-row-a-token paged cache, the shortcut-connected
double layer, and a router over computed and zero-compute experts of which
the program holds a share, through the same engine, cache and learner as
the GPT-2 and OLMoE blocks.

Every comparison is against ``benchmark/reference/longcat_flash.py`` (plain
``jax.numpy``, float32 at ``highest``, un-absorbed attention, a masked loop
over the held experts).  The model here is 2 double layers, hidden 48, 4
heads (q/k 8 + 4 rotary, v 8), ranks 24 and 16, dense FFNs of 96, a router
over 8 computed and 4 identity experts with 3 a token, of which experts
0-3 are held; float32 on both sides.  At that size and precision the two
sides see the same router probabilities to about 1e-7 while the smallest
gap between a kept and a left-out score over a few hundred tokens is about
1e-4, so a routing flip cannot happen and the tolerance is 1e-4 or
tighter; each routed case asserts that gap rather than trust it.  A
reference whose matmul operands are rounded to float8 misses every one of
these by two orders of magnitude (``test_full_forward_matches_reference``
measures it).
"""

import dataclasses
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from scalerl_tpu.agents.token_ppo import token_ppo_loss, token_ppo_packed_loss
from scalerl_tpu.config import GenRLArguments, parse_args
from scalerl_tpu.genrl.continuous import ContinuousConfig, ContinuousEngine
from scalerl_tpu.genrl.rollout import pack_learner_batch
from scalerl_tpu.models.routed_ffn import RoutedExperts, router_balance
from scalerl_tpu.models.transformer import (
    Call,
    ModelCache,
    TransformerPolicy,
    _ShortcutBlock,
    block_spec,
    packed_attention_mask,
    rotary_fn,
)
from scalerl_tpu.ops.ring_attention import full_attention
from scalerl_tpu.runtime import telemetry
from scalerl_tpu.trainer.sequence_rl import build_genrl_model

ROOT = Path(__file__).resolve().parents[1]
ATOL = 1e-4  # see the module docstring
V, D, H, L = 53, 48, 4, 2
E, HELD, Z, K, F = 8, 4, 4, 3, 32
CFG = dict(
    vocab_size=V, hidden_size=D, num_layers=L, num_attention_heads=H,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
    v_head_dim=8, ffn_hidden_size=96, expert_ffn_hidden_size=F,
    n_routed_experts_published=E, n_routed_experts=HELD, first_expert=0,
    zero_expert_num=Z, moe_topk=K, routed_scaling_factor=6.0,
    rms_norm_eps=1e-5, rope_theta=1e7, router_aux_loss_coef=0.01,
)


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"ref_{name}", ROOT / "benchmark" / "reference" / f"{name}.py"
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load("longcat_flash")
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
        + ["--prompt-len", "12", "--max-new-tokens", "12", "--logger-backend", "none"]
        + list(extra),
    )
    args.validate()
    return args


def _seed_bias(params, seed=11, size=0.0):
    """The model's weights with a seeded router bias of the given size
    (the initial bias is zero)."""
    p = jax.tree_util.tree_map(lambda x: x, params)
    rng = np.random.default_rng(seed)
    for i in range(L):
        bank = dict(p["params"][f"block_{i}"]["experts"])
        bank["router_bias"] = jnp.asarray(size * rng.normal(size=E + Z), jnp.float32)
        p["params"][f"block_{i}"] = {**p["params"][f"block_{i}"], "experts": bank}
    return p


@pytest.fixture(scope="module")
def net():
    """The model as the program's arguments build it, and its weights with
    a small seeded router bias (so that the bias is not a silent zero)."""
    model = build_genrl_model(_args())
    params = model.init(jax.random.PRNGKey(3), jnp.zeros((1, 2), jnp.int32))
    return model, _seed_bias(jax.device_get(params), size=0.01)


def _min_gap(tokens, params):
    _logits, _values, routing = ref.forward(params, tokens, GEO)
    return min(float(jnp.min(gap)) for _p, _w, gap in routing)


def test_program_arguments_choose_the_family(net):
    model, params = net
    assert model.block == block_spec(
        "longcat", norm_eps=1e-5, rope_theta=1e7, num_experts=E, experts_per_token=K,
        expert_width=F, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8, ffn_hidden=96, zero_experts=Z,
        experts_held=HELD, first_expert=0, routed_scaling=6.0,
    )
    assert model.head_dim == 12
    block = params["params"]["block_0"]
    assert set(block) == {
        "attn_norm_0", "attn_0", "ffn_norm_0", "ffn_0", "experts",
        "attn_norm_1", "attn_1", "ffn_norm_1", "ffn_1",
    }
    assert set(block["attn_0"]) == {"q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b", "proj"}
    assert block["attn_0"]["kv_a"]["kernel"].shape == (D, 16 + 4)
    assert block["attn_0"]["kv_b"].shape == (16, H * (8 + 8))
    assert block["attn_0"]["proj"]["kernel"].shape == (H * 8, D)
    # the router scores every published output; the banks are the share
    assert block["experts"]["router"].shape == (D, E + Z)
    assert block["experts"]["router_bias"].shape == (E + Z,)
    assert block["experts"]["w_gate"].shape == (HELD, D, F)
    assert "pos_embed" not in params["params"]
    # the cache the model describes: two latent pools a layer, one row a
    # token in whole 128-lane tiles, no V
    cache = model.init_paged_cache(5, 4)
    assert isinstance(cache, ModelCache) and len(cache.rows) == 2 * L
    assert cache == ModelCache(rows=cache.rows)  # every other field empty
    assert {p.shape for p in cache.rows} == {(5, 4, 128)}
    with pytest.raises(ValueError, match="gpt2 \\| olmoe \\| longcat"):
        _args("--block-family", "llama")
    with pytest.raises(ValueError, match="first_expert"):
        build_genrl_model(_args("--moe-first-expert", "6"))  # 6 + 4 > 8


def test_full_forward_matches_reference(net):
    model, params = net
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, V, (2, 300)), jnp.int32)
    out = model.apply(params, tokens)  # 600 tokens: the sorted form
    logits, values, _routing = ref.forward(params, tokens, GEO)
    assert _min_gap(tokens, params) > 1e-5
    np.testing.assert_allclose(out.policy_logits, logits, atol=ATOL)
    np.testing.assert_allclose(out.baseline, values, atol=ATOL)
    short = tokens[:, :40]  # 80 tokens: the streamed form
    np.testing.assert_allclose(
        model.apply(params, short).policy_logits, ref.forward(params, short, GEO)[0], atol=ATOL
    )
    # activations stay of order one through the layers with seeded weights
    assert 0.3 < float(jnp.std(out.policy_logits)) < 3.0
    # what the tolerance refuses: the reference itself at float8 operands
    low, _v, _r = ref.forward(params, tokens, ref.geometry(CFG, round_to="float8_e4m3fn"))
    assert float(jnp.median(jnp.abs(low - logits))) > 100 * ATOL


def test_interleaved_rotary_layout_keeps_every_score():
    """The program lays the rotated pairs out half-wise, the reference
    keeps them interleaved: the same rotation, so q . k agrees."""
    rng = np.random.default_rng(1)
    q, k = (jnp.asarray(rng.normal(size=(2, 5, 3, 8)), jnp.float32) for _ in range(2))
    pos = jnp.asarray(rng.integers(0, 900, (2, 5)))
    rot = rotary_fn(pos, 8, 1e7, "interleaved")
    got = jnp.einsum("bqhd,bkhd->bhqk", rot(q), rot(k))
    want = jnp.einsum("bqhd,bkhd->bhqk", ref._rope(q, pos, 1e7), ref._rope(k, pos, 1e7))
    np.testing.assert_allclose(got, want, atol=1e-5)


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
    """Un-absorbed local prefill, then ABSORBED decode through the latent
    cache (the XLA twin, and the kernel in interpret mode): positions run
    past the first page of 4, and a forked group of 4 shares its prompt's
    pages copy-on-write.  Absorbed equals un-absorbed: the reference has
    only the latter."""
    model, params = net
    engine = _engine(model, params, paged_attn=paged_attn)
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
    # every decoded token of every layer made K picks among all the
    # router's outputs, and each pick is of exactly one of three kinds
    assert stats["expert_tokens"].shape == (L, E + Z)
    decoded = sum(len(c.response_tokens) for c in done)
    np.testing.assert_array_equal(stats["expert_tokens"].sum(axis=1), [K * decoded] * L)
    kinds = [stats[f"{k}_expert_tokens"] for k in ("zero", "held", "absent")]
    assert sum(kinds) == K * decoded * L and min(kinds) > 0
    assert stats["held_expert_tokens"] == stats["expert_tokens"][:, :HELD].sum()
    assert stats["zero_expert_tokens"] == stats["expert_tokens"][:, E:].sum()
    # hits are of the HELD experts only
    assert 0 < stats["expert_hits"] <= stats["expert_substeps"] * HELD


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
    assert engine.stats()["expert_tokens"].sum() == before + L * K * 3


def test_tail_prefill_over_a_cached_prefix_matches_reference(net):
    """A second admission of a prompt whose first pages are cached takes
    the shared-table tail prefill: absorbed queries over gathered rows
    whose rotary part was rotated when it was written."""
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


def test_speculative_verify_matches_reference(net):
    """The verify pass rides the tail-prefill path with T = drafts + 1."""
    model, params = net
    engine = _engine(model, params, spec_k=2, prefix_cache=False)
    rng = np.random.default_rng(3)
    for tag in range(3):
        assert engine.submit(rng.integers(0, V, 9).astype(np.int32), 9, tag=tag)
    done = engine.run_until(3)
    assert len(done) == 3
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


@pytest.mark.parametrize("kernel", ["dense", "segment_flash", "causal_fn"])
def test_packed_rows_match_reference(net, kernel):
    """Packed rows with per-segment positions (the dense packed mask, and
    the flash segment kernel in interpret mode, which takes one head size:
    v is padded from 8 to q and k's 12 and the pad sliced off); and the
    plain causal ``attn_fn`` call site, padded the same way."""
    model, params = net
    tok, seg, pos = _rows(4, [9, 14, 6], 32)
    if kernel == "causal_fn":
        seen = []

        def attn(q, k, v):
            seen.append((q.shape[-1], v.shape[-1]))
            return full_attention(q, k, v, causal=True)

        out = model.clone(attn_fn=attn).apply(params, tok[:, :9])
        want, _v, _r = ref.forward(params, tok[:, :9], GEO)
        assert seen and set(seen) == {(12, 12)}
        np.testing.assert_allclose(out.policy_logits, want, atol=ATOL)
        return
    if kernel == "segment_flash":
        from scalerl_tpu.ops.pallas_attention import segment_flash_attention

        model = model.clone(segment_attn_fn=segment_flash_attention)
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


def test_packed_learner_loss_and_gradients_match_reference(net):
    """The PACKED learner (the un-absorbed form under the packed mask) on
    one sequence against the reference's loss with the load-balancing
    term over the router's 12 outputs, and ``jax.grad`` of each."""
    model, params = net
    seqs = _sequences(6, 1)
    packed, _pk = _packed(seqs, S=16)
    a, b = len(seqs["prompts"][0]), len(seqs["resps"][0])
    seq = {
        "tokens": jnp.asarray(np.concatenate([seqs["prompts"][0], seqs["resps"][0]])),
        "mask": jnp.asarray(np.r_[np.zeros(a), np.ones(b)], jnp.float32),
        "behavior_logp": jnp.asarray(np.r_[np.zeros(a), seqs["logps"][0]], jnp.float32),
        "value": jnp.asarray(np.r_[np.zeros(a), seqs["vals"][0]], jnp.float32),
        "reward": jnp.full((a + b,), seqs["rewards"][0], jnp.float32),
    }
    assert _min_gap(seq["tokens"][None], params) > 1e-5
    (total, metrics), grads = jax.jit(jax.value_and_grad(
        lambda w: token_ppo_packed_loss(w, w, model, packed, **_KW), has_aux=True
    ))(params)
    (want, parts), want_grads = jax.jit(jax.value_and_grad(
        lambda w: ref.ppo_loss(ref_ppo, w, w, seq, GEO, _HYPER), has_aux=True
    ))(params)
    np.testing.assert_allclose(float(total), float(want), atol=1e-5)
    np.testing.assert_allclose(float(metrics["moe_aux_loss"]), float(parts["moe_aux_loss"]), atol=1e-6)
    np.testing.assert_allclose(float(metrics["moe_max_load"]), float(parts["moe_max_load"]), atol=1e-6)
    got, _ = ravel_pytree(grads)
    exp, _ = ravel_pytree(want_grads)
    # float32 sums in another order; a float8 reference misses by 1e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), atol=1e-5, rtol=1e-4)
    # the bias chooses and does not weigh: no gradient reaches it
    assert not np.any(np.asarray(grads["params"]["block_0"]["experts"]["router_bias"]))


def test_packed_learner_equals_padded_learner(net):
    model, params = net
    seqs = _sequences(7, 5)
    padded = _padded(seqs)
    packed, pk = _packed(seqs, S=32)
    assert pk.rows < 5
    (l1, m1), g1 = jax.jit(jax.value_and_grad(
        lambda w: token_ppo_loss(w, w, model, padded, **_KW), has_aux=True
    ))(params)
    (l2, m2), g2 = jax.jit(jax.value_and_grad(
        lambda w: token_ppo_packed_loss(w, w, model, packed, **_KW), has_aux=True
    ))(params)
    np.testing.assert_allclose(float(l1), float(l2), atol=1e-5)
    for key in ("pg_loss", "value_loss", "moe_aux_loss", "moe_max_load"):
        np.testing.assert_allclose(float(m1[key]), float(m2[key]), atol=1e-5)
    f1, _ = ravel_pytree(g1)
    f2, _ = ravel_pytree(g2)
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f2), atol=1e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# the router and the share


def _ffn(held=HELD, first=0):
    return RoutedExperts(
        E, K, F, zero_experts=Z, held=held, first_expert=first, choice_bias=True,
        routed_scaling=6.0,
    )


@pytest.mark.parametrize("n_tokens", [7, 600])
def test_a_router_bias_changes_picks_and_not_weights(n_tokens):
    """A seeded non-zero bias moves which outputs are picked; the weight
    of a pick stays ``6 x its probability``, bias or no bias.  Both forms
    (7 tokens streamed, 600 sorted) against the reference's masked loop."""
    x = jax.random.normal(jax.random.PRNGKey(n_tokens), (1, n_tokens, D))
    ffn = _ffn()
    p = dict(ffn.init(jax.random.PRNGKey(1), x)["params"])
    geo = GEO
    picks = {}
    for name, size in (("zero", 0.0), ("seeded", 0.05)):
        p["router_bias"] = jnp.asarray(
            size * np.random.default_rng(5).normal(size=E + Z), jnp.float32
        )
        y, sown = ffn.apply({"params": p}, x, mutable=["intermediates"])
        want, probs, weights, gap = ref._moe(p, x, geo)
        assert float(jnp.min(gap)) > 1e-6
        np.testing.assert_allclose(y, want, atol=1e-5)
        ids = np.asarray(sown["intermediates"]["expert_ids"][0])[0]  # [N, K]
        picked = np.zeros((n_tokens, E + Z), bool)
        np.put_along_axis(picked, ids, True, axis=1)
        np.testing.assert_array_equal(picked, np.asarray(weights[0] > 0))
        # a pick's weight is 6 x its probability whatever the bias
        np.testing.assert_allclose(
            np.asarray(weights[0])[picked], 6.0 * np.asarray(probs[0])[picked], rtol=1e-6
        )
        picks[name] = picked
    assert (picks["zero"] != picks["seeded"]).any()
    # K picks a token among all 12 outputs, nothing dropped
    balance = router_balance({"block_0": {"experts": sown["intermediates"]}}, jnp.ones((1, n_tokens)))
    assert balance.counts.shape == (1, E + Z) and int(balance.counts.sum()) == K * n_tokens


@pytest.mark.parametrize("n_tokens", [9, 600])
def test_the_shares_add_up_to_the_uncut_layer(n_tokens):
    """Over both shares of 4 of the 8 routed experts, the parts of the
    layer's output that the shares give, with what every chip computes
    alike (both attentions, both dense FFNs, the identity experts) counted
    once, equal the UNCUT reference layer: ``sum_s out_s - (shares - 1) x
    (the layer with no expert held)``.  The program computes each share
    (banks sliced from one uncut set of weights); the reference computes
    the uncut layer and the layer with nothing held."""
    spec = block_spec(
        "longcat", num_experts=E, experts_per_token=K, expert_width=F,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, ffn_hidden=96, zero_experts=Z, experts_held=E,
        routed_scaling=6.0, rope_theta=1e7,
    )
    x = jax.random.normal(jax.random.PRNGKey(2), (1, n_tokens, D))
    pos = jnp.arange(n_tokens)[None]
    causal = jnp.tril(jnp.ones((n_tokens, n_tokens), bool))[None]
    masked = Call("masked", attn_mask=causal)

    def block(s):
        return _ShortcutBlock(
            D, H, 4, None, spec=s, rotary=rotary_fn(pos, 4, 1e7, "interleaved")
        )

    uncut = jax.device_get(block(spec).init(jax.random.PRNGKey(4), x, masked))
    uncut["params"]["experts"]["router_bias"] = jnp.asarray(
        0.02 * np.random.default_rng(8).normal(size=E + Z), jnp.float32
    )
    geo = GEO._replace(first_expert=0, held=E)
    want, _p, weights, gap = ref.layer(uncut["params"], x, pos, causal, geo)
    assert float(jnp.min(gap)) > 1e-6
    banks = ("w_gate", "w_up", "w_down")

    def sliced(first, held):
        bank = dict(uncut["params"]["experts"])
        bank.update({k: bank[k][first : first + held] for k in banks})
        return {**uncut["params"], "experts": bank}

    total = 0.0
    for first in (0, HELD):
        share = dataclasses.replace(spec, experts_held=HELD, first_expert=first)
        out, none, _r = block(share).apply({"params": sliced(first, HELD)}, x, masked)  # no second stream
        assert none is None  # a layer on no cache hands none back
        total = total + out
    nothing, _p, _w, _g = ref.layer(
        sliced(0, 0), x, pos, causal, geo._replace(first_expert=0, held=0)
    )
    np.testing.assert_allclose(total - nothing, want, atol=ATOL)
    # and the held picks of the two shares are all of the routed picks
    assert float(jnp.sum(weights[..., :E] > 0)) > 0


def test_normal_entry_point_generates_and_learns(tmp_path):
    """``--block-family longcat`` through ``SequenceRLTrainer``'s normal
    entry point at a small size: generation rounds on the continuous
    engine and packed learn steps, finite losses."""
    from scalerl_tpu.trainer.sequence_rl import SequenceRLTrainer

    args = _args(
        "--learner-packing", "true", "--samples-per-prompt", "4", "--genrl-lanes", "8",
        "--work-dir", str(tmp_path), "--platform", "cpu",
    )
    trainer = SequenceRLTrainer(args)
    m1 = trainer.train_round()
    m2 = trainer.train_round()
    assert np.isfinite(m1["total_loss"]) and np.isfinite(m2["total_loss"])
    stats = trainer.engine.stats()
    assert stats["completed"] > 0 and stats["zero_expert_tokens"] > 0


# ---------------------------------------------------------------------------
# the other two families are what they were (their traced programs are held
# to the parent's digests in ``tests/test_parent_programs.py``)


@pytest.mark.parametrize("family", ["gpt2", "olmoe"])
def test_gpt2_and_olmoe_keep_their_trees_and_caches(family):
    """The same parameter tree (names and shapes) as before the family
    came: what it added to ``BlockSpec``, the cache and the routed FFN is
    invisible to the two families that were there."""
    kw = dict(head_dim=16, num_experts=8, experts_per_token=3, expert_width=32)
    model = TransformerPolicy(
        num_actions=V, vocab_size=V, d_model=64, num_heads=4, num_layers=2, max_len=64,
        block=block_spec(family, **(kw if family == "olmoe" else {})),
    )
    tokens = jnp.zeros((2, 24), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), tokens))
    block = params["params"]["block_0"]
    if family == "olmoe":
        assert set(block) == {"attn_norm", "q_norm", "k_norm", "qkv", "proj", "ffn_norm", "experts"}
        assert set(block["experts"]) == {"router", "w_gate", "w_up", "w_down"}
        assert block["experts"]["router"].shape == (64, 8)
    else:
        assert set(block) == {"LayerNorm_0", "LayerNorm_1", "qkv", "proj", "mlp_in", "mlp_out"}
    cache = model.init_paged_cache(9, 4)
    assert isinstance(cache, ModelCache) and len(cache.k) == len(cache.v) == 2
    assert cache == ModelCache(k=cache.k, v=cache.v)  # every other field empty
