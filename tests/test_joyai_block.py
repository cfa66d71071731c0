"""The ``joyai`` block family of the token model (ISSUE 32): a stack of
more than one kind of layer (a leading dense layer, then routed ones),
latent (MLA) attention in a plain layer with one cache pool a layer, a
sigmoid router with a choice bias, renormalised picks and an always-on
shared expert over a held share of the experts, and a
multi-token-prediction module that the packed learner runs and trains and
generation never builds.

Every comparison is against ``benchmark/reference/joyai_flash.py`` (plain
``jax.numpy``, float32 at ``highest``, un-absorbed attention, a masked loop
over the held experts) and, for the learner, ``reference/mtp_token_ppo.py``
over ``reference/token_ppo.py``.  The model here is 1 dense + 2 routed
layers, hidden 48, 4 heads (q/k 8 + 4 rotary, v 8), ranks 24 and 16, a
dense FFN of 96, a router over 8 experts of width 32 with 3 a token, of
which experts 0-3 are held, one shared expert, one MTP module; float32 on
both sides.  At that size and precision the two sides see the same router
scores to about 1e-7 while the smallest gap between a kept and a left-out
score over a few hundred tokens is about 1e-4, so a routing flip cannot
happen and the tolerance is 1e-4 or tighter; each routed case asserts that
gap rather than trust it.  A reference whose matmul operands are rounded
to float8 misses every one of these by two orders of magnitude
(``test_full_forward_matches_reference`` measures it).
"""

import dataclasses
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.flatten_util import ravel_pytree

from scalerl_tpu.agents.token_ppo import token_ppo_packed_loss
from scalerl_tpu.config import GenRLArguments, parse_args
from scalerl_tpu.genrl.continuous import ContinuousConfig, ContinuousEngine
from scalerl_tpu.genrl.rollout import pack_learner_batch
from scalerl_tpu.models.routed_ffn import RoutedExperts, router_balance
from scalerl_tpu.models.transformer import (
    Call,
    ModelCache,
    _Block,
    block_spec,
    layer_specs,
    packed_attention_mask,
    rotary_fn,
)
from scalerl_tpu.runtime import telemetry
from scalerl_tpu.trainer.sequence_rl import build_genrl_model

ROOT = Path(__file__).resolve().parents[1]
ATOL = 1e-4  # see the module docstring
V, D, H, L = 53, 48, 4, 3
E, HELD, K, F = 8, 4, 3, 32
CFG = dict(
    vocab_size=V, hidden_size=D, num_hidden_layers=L, first_k_dense_replace=1,
    num_attention_heads=H, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
    qk_rope_head_dim=4, v_head_dim=8, intermediate_size=96, moe_intermediate_size=F,
    n_routed_experts_published=E, n_routed_experts=HELD, first_expert=0,
    n_shared_experts=1, num_experts_per_tok=K, scoring_func="sigmoid",
    routed_scaling_factor=2.5, norm_topk_prob=True, rms_norm_eps=1e-6,
    rope_theta=3.2e7, router_aux_loss_coef=0.0, num_nextn_predict_layers=1,
    mtp_loss_coef=0.1,
)
_SIZES = dict(
    norm_eps=1e-6, rope_theta=3.2e7, num_experts=E, experts_per_token=K,
    expert_width=F, norm_topk_prob=True, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, ffn_hidden=96,
    routed_scaling=2.5, scoring="sigmoid", shared_experts=1,
)


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"ref_{name}", ROOT / "benchmark" / "reference" / f"{name}.py"
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load("joyai_flash")
ref_ppo = _load("token_ppo")
ref_mtp = _load("mtp_token_ppo")
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


def _routed_banks(params):
    """``(path, bank)`` of every router in the tree, the module's too."""
    p = params["params"]
    for i in range(1, L):
        yield ("params", f"block_{i}", "experts"), p[f"block_{i}"]["experts"]
    yield ("params", "mtp", "block", "experts"), p["mtp"]["block"]["experts"]


def _seed_bias(params, seed=11, size=0.0):
    """The model's weights with a seeded router bias of the given size
    (the initial bias is zero)."""
    rng = np.random.default_rng(seed)
    p = jax.tree_util.tree_map(lambda x: x, params)
    for path, _bank in list(_routed_banks(p)):
        node = p
        for name in path:
            node = node[name]
        node["router_bias"] = jnp.asarray(size * rng.normal(size=E), jnp.float32)
    return p


@pytest.fixture(scope="module")
def net():
    """The model as the program's arguments build it, and its weights with
    a small seeded router bias (so that the bias is not a silent zero)."""
    model = build_genrl_model(_args())
    params = model.init(jax.random.PRNGKey(3), jnp.zeros((1, 2), jnp.int32))
    params = jax.tree_util.tree_map(np.asarray, jax.device_get(params))
    return model, _seed_bias(_thaw(params), size=0.01)


def _thaw(tree):
    return {k: _thaw(v) if isinstance(v, dict) else v for k, v in tree.items()}


def _min_gap(routing):
    return min(float(jnp.min(gap)) for _s, _w, gap in routing)


def test_program_arguments_choose_the_family(net):
    model, params = net
    spec = block_spec("joyai", experts_held=HELD, first_expert=0, **_SIZES)
    assert model.block == spec and model.mtp_layers == 1
    # a per-layer list: the leading dense layer, then the routed ones
    assert model.layers == layer_specs(spec, L, 1) and model.routed_layers == L - 1
    assert [s.ffn for s in model.layer_specs] == ["swiglu", "experts", "experts"]
    assert {(s.attention, s.layer) for s in model.layer_specs} == {("mla", "plain")}
    assert not spec.mla_scale and block_spec(
        "longcat", **{**{k: v for k, v in _SIZES.items() if k not in ("scoring", "shared_experts")},
                      "zero_experts": 4}
    ).mla_scale
    assert model.head_dim == 12
    p = params["params"]
    assert set(p) == {
        "token_embed", "block_0", "block_1", "block_2", "final_norm", "policy_head",
        "value_head", "mtp", "mtp_final_norm",
    }
    assert set(p["block_0"]) == {"attn_norm", "attn", "ffn_norm", "ffn"}
    assert set(p["block_1"]) == {"attn_norm", "attn", "ffn_norm", "experts", "shared"}
    assert set(p["mtp"]) == {"h_norm", "e_norm", "eh_proj", "block"}
    assert set(p["mtp"]["block"]) == set(p["block_1"])
    assert p["mtp"]["eh_proj"]["kernel"].shape == (2 * D, D)
    attn = p["block_0"]["attn"]
    assert set(attn) == {"q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b", "proj"}
    assert attn["kv_a"]["kernel"].shape == (D, 16 + 4) and attn["kv_b"].shape == (16, H * 16)
    assert p["block_0"]["ffn"]["gate"]["kernel"].shape == (D, 96)
    assert p["block_1"]["shared"]["down"]["kernel"].shape == (F, D)
    # the router scores every published expert; the banks are the share
    bank = p["block_1"]["experts"]
    assert bank["router"].shape == (D, E) and bank["router_bias"].shape == (E,)
    assert bank["w_gate"].shape == (HELD, D, F)
    # the cache the model describes: one latent pool a plain layer, none
    # for the module (generation never runs it)
    cache = model.init_paged_cache(5, 4)
    assert isinstance(cache, ModelCache) and {x.shape for x in cache.rows} == {(5, 4, 128)}
    assert cache == ModelCache(rows=cache.rows)  # every other field empty
    assert len(cache.rows) == L
    with pytest.raises(ValueError, match="gpt2 \\| olmoe \\| longcat \\| joyai"):
        _args("--block-family", "llama")
    with pytest.raises(ValueError, match="learner_packing"):
        _args("--learner-packing", "false")
    with pytest.raises(ValueError, match="joyai family's"):
        _args("--block-family", "gpt2")
    with pytest.raises(ValueError, match="first_expert"):
        build_genrl_model(_args("--moe-first-expert", "6"))  # 6 + 4 > 8


def test_full_forward_matches_reference(net):
    model, params = net
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, V, (2, 300)), jnp.int32)
    out = model.apply(params, tokens, mtp=True)  # 600 tokens: the sorted form
    logits, values, mtp_logits, routing = ref.forward_mtp(params, tokens, GEO)
    assert _min_gap(routing) > 1e-5 and len(routing) == L  # two layers and the module
    np.testing.assert_allclose(out.policy_logits, logits, atol=ATOL)
    np.testing.assert_allclose(out.baseline, values, atol=ATOL)
    np.testing.assert_allclose(out.mtp_logits, mtp_logits, atol=ATOL)
    short = tokens[:, :40]  # 80 tokens: the streamed form
    plain = model.apply(params, short)
    assert plain.mtp_logits is None  # only a forward that asks runs the module
    np.testing.assert_allclose(plain.policy_logits, ref.forward(params, short, GEO)[0], atol=ATOL)
    np.testing.assert_allclose(
        model.apply(params, short, mtp=True).mtp_logits,
        ref.forward_mtp(params, short, GEO)[2], atol=ATOL,
    )
    # activations and attention scores stay of order one through the
    # layers with seeded weights and no ``mla_scale`` factor
    assert 0.3 < float(jnp.std(out.policy_logits)) < 3.0
    assert 0.3 < float(jnp.std(out.mtp_logits)) < 3.0
    # what the tolerance refuses: the reference itself at float8 operands
    low = ref.forward_mtp(params, tokens, ref.geometry(CFG, round_to="float8_e4m3fn"))
    assert float(jnp.median(jnp.abs(low[0] - logits))) > 100 * ATOL
    assert float(jnp.median(jnp.abs(low[2] - mtp_logits))) > 100 * ATOL


def test_seeded_attention_scores_are_of_order_one():
    """Without the two ``mla_scale`` factors plain fan-in over the ranks
    already gives q and k unit variance: at the published ranks' ratio the
    seeded scores have a deviation near one, not LongCat's 5.8."""
    spec = block_spec("joyai", **{**_SIZES, "q_lora_rank": 96, "kv_lora_rank": 32,
                                   "qk_nope_head_dim": 32, "qk_rope_head_dim": 16})
    d = 256
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 64, d))
    seen = {}

    def attn(q, k, v):
        seen["scores"] = jnp.einsum("bqhd,bkhd->bhqk", q, k) / q.shape[-1] ** 0.5
        return jnp.zeros(q.shape[:-1] + (v.shape[-1],), q.dtype)

    block = _Block(d, 4, 4, attn, spec=spec, rotary=rotary_fn(jnp.arange(64)[None], 16, 3.2e7, "interleaved"))
    block.apply(block.init(jax.random.PRNGKey(1), x, Call("causal")), x, Call("causal"))
    assert 0.5 < float(jnp.std(seen["scores"])) < 2.0


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


def _without_mtp(params):
    return {"params": {k: v for k, v in params["params"].items() if not k.startswith("mtp")}}


@pytest.mark.parametrize("paged_attn", ["xla", "pallas"])
def test_engine_prefill_decode_and_fork_match_reference(net, paged_attn):
    """Un-absorbed local prefill, then ABSORBED decode through the latent
    cache of one pool a layer (the XLA twin, and the kernel in interpret
    mode), a forked group sharing its prompt's pages, and a second
    admission over a cached prefix (the tail prefill).  The engine is
    given a tree WITHOUT the module's parameters: none of its programs
    reads one."""
    model, params = net
    engine = _engine(model, _without_mtp(params), paged_attn=paged_attn)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, V, 10).astype(np.int32)  # 2 full pages + a partial one
    assert engine.submit_group(prompt, 4, 10, tag=0)
    assert engine.submit(rng.integers(0, V, 7).astype(np.int32), 7, tag=1)
    done = engine.run_until(5)
    again = np.concatenate([prompt[:8], rng.integers(0, V, 3)]).astype(np.int32)
    assert engine.submit(again, len(again), tag=2)
    done += engine.run_until(1)
    assert len(done) == 6 and all(len(c.response_tokens) == 12 for c in done)
    assert engine.prefix_tokens_saved >= 8  # the tail path ran
    _check_against_reference(params, done)
    stats = engine.stats()
    # the dense layer has no router: counts of the two routed layers only
    assert stats["expert_tokens"].shape == (L - 1, E)
    decoded = sum(len(c.response_tokens) for c in done)
    np.testing.assert_array_equal(stats["expert_tokens"].sum(axis=1), [K * decoded] * (L - 1))
    assert stats["held_expert_tokens"] + stats["absent_expert_tokens"] == K * decoded * (L - 1)
    assert stats["zero_expert_tokens"] == 0 and stats["held_expert_tokens"] > 0
    # and the engine's programs hold no trace of the module
    assert "mtp" not in str(jax.tree_util.tree_structure(engine._params)) if hasattr(engine, "_params") else True


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


def _has_next(seg):
    seg = np.asarray(seg)
    nxt = np.concatenate([seg[:, 1:], np.zeros((len(seg), 1), seg.dtype)], axis=1)
    return (seg > 0) & (nxt == seg)


@pytest.mark.parametrize("kernel", ["dense", "segment_flash"])
def test_packed_rows_match_reference(net, kernel):
    """Packed rows with per-segment positions, the module included: the
    dense packed mask, and the flash segment kernel in interpret mode,
    which takes q and k at 12 and v at 8 (no pad).  A segment's logits are
    those of the sequence alone."""
    model, params = net
    tok, seg, pos = _rows(4, [9, 14, 6], 32)
    if kernel == "segment_flash":
        from scalerl_tpu.ops.pallas_attention import segment_flash_attention

        seen = []

        def kern(q, k, v, s):
            seen.append((q.shape[-1], v.shape[-1]))
            return segment_flash_attention(q, k, v, s)

        model = model.clone(segment_attn_fn=kern)
    out = model.apply(params, tok, positions=pos, segment_ids=seg, mtp=True)
    logits, values, mtp_logits, _routing = ref.forward_mtp(
        params, tok, GEO, positions=pos, mask=packed_attention_mask(seg), has_next=_has_next(seg)
    )
    real = np.asarray(seg)[0] > 0
    np.testing.assert_allclose(out.policy_logits[0][real], logits[0][real], atol=ATOL)
    np.testing.assert_allclose(out.baseline[0][real], values[0][real], atol=ATOL)
    np.testing.assert_allclose(out.mtp_logits[0][real], mtp_logits[0][real], atol=ATOL)
    alone = ref.forward_mtp(params, tok[:, 9:23], GEO)  # the middle segment
    np.testing.assert_allclose(out.policy_logits[0, 9:23], alone[0][0], atol=ATOL)
    np.testing.assert_allclose(out.mtp_logits[0, 9:23], alone[2][0], atol=ATOL)
    if kernel == "segment_flash":
        assert seen and set(seen) == {(12, 8)}  # four attentions, none padded


_HYPER = dict(
    clip_range=0.2, value_cost=0.5, entropy_cost=0.01, kl_cost=0.0, adv_norm=True,
    mtp_loss_coef=0.1,
)
_KW = {("mtp_coef" if k == "mtp_loss_coef" else k): v for k, v in _HYPER.items()}


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


def _ref_forward(w, tokens):
    return ref.forward_mtp(w, tokens, GEO)[:3]


def test_packed_learner_loss_and_gradients_match_reference(net):
    """The PACKED learner on one sequence against the reference's loss
    (PPO terms and ``mtp_loss``) and ``jax.grad`` of each; a dropped MTP
    gradient is a hundred tolerances off."""
    model, params = net
    seqs = _sequences(6, 1)
    packed, _pk = _packed(seqs, S=16)
    seq = _one(seqs)
    assert _min_gap(ref.forward_mtp(params, seq["tokens"][None], GEO)[3]) > 1e-5
    (total, metrics), grads = jax.jit(jax.value_and_grad(
        lambda w: token_ppo_packed_loss(w, w, model, packed, **_KW), has_aux=True
    ))(params)
    (want, parts), want_grads = jax.jit(jax.value_and_grad(
        lambda w: ref_mtp.loss(ref_ppo, w, w, seq, _ref_forward, _HYPER), has_aux=True
    ))(params)
    np.testing.assert_allclose(float(total), float(want), atol=1e-5)
    for key in ("pg_loss", "value_loss", "entropy", "mtp_loss", "mtp_top1_match"):
        np.testing.assert_allclose(float(metrics[key]), float(parts[key]), atol=1e-5)
    got, _ = ravel_pytree(grads)
    exp, _ = ravel_pytree(want_grads)
    # float32 sums in another order; a float8 reference misses by 1e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), atol=1e-5, rtol=1e-4)
    # the term's gradient reaches the module, and through it the trunk,
    # the embedding and the head: without it the gradient is another one
    _t, no_mtp = jax.jit(jax.value_and_grad(
        lambda w: token_ppo_packed_loss(w, w, model, packed, **{**_KW, "mtp_coef": 0.0}), has_aux=True
    ))(params)
    flat, _ = ravel_pytree(no_mtp)
    assert float(jnp.max(jnp.abs(flat - got))) > 1e-3
    assert float(jnp.max(jnp.abs(grads["params"]["mtp"]["eh_proj"]["kernel"]))) > 1e-4
    assert not np.any(np.asarray(no_mtp["params"]["mtp"]["eh_proj"]["kernel"]))
    trunk = lambda g: g["params"]["block_0"]["attn"]["q_a"]["kernel"]  # noqa: E731
    assert float(jnp.max(jnp.abs(trunk(grads) - trunk(no_mtp)))) > 1e-5
    # the bias chooses and does not weigh: no gradient reaches it
    for _path, bank in _routed_banks(grads):
        assert not np.any(np.asarray(bank["router_bias"]))
    # the picks of the real tokens, of each kind, over the three routers
    real = float(jnp.sum(packed["segment_ids"] > 0))
    assert float(metrics["moe_held_picks"] + metrics["moe_absent_picks"]) == K * L * real
    held, absent, max_load = ref.picks(
        ref.forward_mtp(params, seq["tokens"][None], GEO)[3], jnp.ones((1, int(real))), GEO
    )
    assert float(metrics["moe_held_picks"]) == float(held) > 0
    np.testing.assert_allclose(float(metrics["moe_max_load"]), float(max_load), rtol=1e-6)


def test_packed_rows_of_several_sequences_match_the_reference_on_each(net):
    """Two and more sequences a row: every loss term is the token-weighted
    mean of the reference's per-sequence terms (no advantage norm, so that
    the terms separate)."""
    model, params = net
    seqs = _sequences(7, 5)
    packed, pk = _packed(seqs, S=32)
    assert pk.rows < 5
    kw = {**_KW, "adv_norm": False}
    _total, metrics = jax.jit(
        lambda w: token_ppo_packed_loss(w, w, model, packed, **kw)
    )(params)
    hyper = {**_HYPER, "adv_norm": False}
    terms = {"pg_loss": 0.0, "mtp_loss": 0.0}
    counts = {"pg_loss": 0.0, "mtp_loss": 0.0}
    for i in range(5):
        seq = _one(seqs, i)
        _t, parts = ref_mtp.loss(ref_ppo, params, params, seq, _ref_forward, hyper)
        n = {"pg_loss": float(seq["mask"][1:].sum()), "mtp_loss": float(seq["mask"][2:].sum())}
        for key in terms:
            terms[key] += n[key] * float(parts[key])
            counts[key] += n[key]
    for key in terms:
        np.testing.assert_allclose(float(metrics[key]), terms[key] / counts[key], atol=1e-5)


def test_the_mtp_term_never_reads_across_a_segment_boundary(net):
    """Changing a neighbour segment's tokens changes no logit and no loss
    term of this one: the module's input at a segment's last position has
    no next-token half, and positions whose token i + 1 or i + 2 lies in
    another segment are not counted."""
    model, params = net
    seqs = _sequences(8, 2)
    packed, pk = _packed(seqs, S=32)
    assert pk.rows == 1
    seg = np.asarray(packed["segment_ids"])[0]
    first, second = seg == 1, seg == 2
    other = dict(packed)
    tokens = np.asarray(packed["tokens"]).copy()
    tokens[0, second] = (tokens[0, second] + 17) % V  # the neighbour changes
    other["tokens"] = jnp.asarray(tokens)
    # only the first segment counts in the loss
    mask = np.asarray(packed["mask"]) * first[None]
    packed["mask"] = other["mask"] = jnp.asarray(mask)

    def run(batch):
        out = model.apply(
            params, batch["tokens"], positions=batch["positions"],
            segment_ids=batch["segment_ids"], mtp=True,
        )
        total, metrics = token_ppo_packed_loss(params, params, model, batch, **_KW)
        return out, total, metrics

    (a, ta, ma), (b, tb, mb) = run(packed), run(other)
    np.testing.assert_array_equal(a.mtp_logits[0][first], b.mtp_logits[0][first])
    np.testing.assert_array_equal(a.policy_logits[0][first], b.policy_logits[0][first])
    assert float(jnp.max(jnp.abs(a.mtp_logits[0][second] - b.mtp_logits[0][second]))) > 1e-3
    assert float(ta) == float(tb)
    for key in ("pg_loss", "value_loss", "mtp_loss", "mtp_top1_match"):
        assert float(ma[key]) == float(mb[key]), key
    # counted: response tokens of the first segment from its third token on
    resp = np.asarray(packed["mask"])[0] > 0
    assert float(ma["mtp_loss"]) > 0 and resp[np.flatnonzero(first)[2:]].sum() > 0


# ---------------------------------------------------------------------------
# the router and the share


def _ffn(held=HELD, first=0, experts=E):
    return RoutedExperts(
        experts, K, F, norm_topk_prob=True, held=held, first_expert=first,
        choice_bias=True, routed_scaling=2.5, scoring="sigmoid",
    )


@pytest.mark.parametrize("n_tokens", [7, 600])
def test_a_router_bias_changes_picks_and_not_weights(n_tokens):
    """A seeded non-zero bias moves which experts are picked; the weight
    of a pick stays ``2.5 x its score / the picked scores' sum``, bias or
    no bias, and no gradient reaches the bias.  Both forms (7 tokens
    streamed, 600 sorted) against the reference's masked loop."""
    x = jax.random.normal(jax.random.PRNGKey(n_tokens), (1, n_tokens, D))
    ffn = _ffn()
    p = dict(ffn.init(jax.random.PRNGKey(1), x)["params"])
    picks = {}
    for name, size in (("zero", 0.0), ("seeded", 0.2)):
        p["router_bias"] = jnp.asarray(
            size * np.random.default_rng(5).normal(size=E), jnp.float32
        )
        y, sown = ffn.apply({"params": p}, x, mutable=["intermediates"])
        want, scores, weights, gap = ref._moe(p, x, GEO)
        assert float(jnp.min(gap)) > 1e-6
        np.testing.assert_allclose(y, want, atol=1e-5)
        ids = np.asarray(sown["intermediates"]["expert_ids"][0])[0]  # [N, K]
        picked = np.zeros((n_tokens, E), bool)
        np.put_along_axis(picked, ids, True, axis=1)
        np.testing.assert_array_equal(picked, np.asarray(weights[0] > 0))
        # a pick's weight is its score's share of the picked scores x 2.5
        s = np.asarray(scores[0])
        total = np.sum(np.where(picked, s, 0.0), axis=1, keepdims=True)
        np.testing.assert_allclose(
            np.asarray(weights[0])[picked], (2.5 * s / total)[picked], rtol=1e-6
        )
        np.testing.assert_allclose(np.asarray(weights[0]).sum(axis=1), 2.5, rtol=1e-6)
        # the sown scores are the sigmoid's, what the load metrics read
        np.testing.assert_allclose(sown["intermediates"]["router_probs"][0][0], s, atol=1e-6)
        picks[name] = picked
    assert (picks["zero"] != picks["seeded"]).any()
    grads = jax.grad(lambda w: jnp.sum(ffn.apply({"params": w}, x) ** 2))(p)
    assert not np.any(np.asarray(grads["router_bias"]))
    assert np.any(np.asarray(grads["router"]))
    # K picks a token among all 8 experts, nothing dropped
    balance = router_balance({"block_1": {"experts": sown["intermediates"]}}, jnp.ones((1, n_tokens)))
    assert balance.counts.shape == (1, E) and int(balance.counts.sum()) == K * n_tokens


@pytest.mark.parametrize(
    "held,hot,form", [(2, 0, "streamed"), (2, 2, "streamed"), (4, 0, "sorted"), (4, 3, "sorted")],
    ids=["held<=k", "held<=k-hot", "held>k", "held>k-hot"],
)
def test_the_share_takes_the_form_its_shapes_say(held, hot, form):
    """A share of 32 experts at 600 tokens, 3 picks a token.  The sorted
    form multiplies all 1,800 sorted rows however few are held (a grouped
    matmul does not skip the rows past its last group on the chip), the
    streamed form 600 x ``held``: so with 2 held (``held <= k``) the
    streamed form runs, no grouped matmul in the program, and with 4 held
    the sorted one, three of them.  Whatever the router does: also with a
    bias that sends every token to ``hot`` of the held experts.  Dropless
    either way: outputs and the gradients of the tokens, the banks and the
    router against the reference's masked loop."""
    n, experts = 600, 32
    ffn = _ffn(held=held, experts=experts)
    x = jax.random.normal(jax.random.PRNGKey(9), (1, n, D))
    p = dict(ffn.init(jax.random.PRNGKey(1), x)["params"])
    bias = 0.05 * np.random.default_rng(3).normal(size=experts)
    bias[:hot] += 5.0
    p["router_bias"] = jnp.asarray(bias, jnp.float32)
    geo = GEO._replace(n_routed=experts, first_expert=0, held=held)
    _y, _s, weights, gap = ref._moe(p, x, geo)
    assert int(jnp.sum(weights[..., :held] > 0)) >= hot * n
    assert float(jnp.min(gap)) > 1e-6
    text = str(jax.make_jaxpr(lambda w, x: ffn.apply({"params": w}, x))(p, x))
    assert text.count("ragged_dot_general[") == (3 if form == "sorted" else 0)

    def loss(fn):
        return lambda w, x: jnp.sum(jnp.sin(fn(w, x)))

    got = jax.grad(loss(lambda w, x: ffn.apply({"params": w}, x)), argnums=(0, 1))(p, x)
    want = jax.grad(loss(lambda w, x: ref._moe(w, x, geo)[0]), argnums=(0, 1))(p, x)
    np.testing.assert_allclose(ffn.apply({"params": p}, x), ref._moe(p, x, geo)[0], atol=1e-5)
    # a bank's gradient sums 600 tokens' terms of up to 60 in float32:
    # 60 x 2^-23 a term, and the two sides add them in another order
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=1e-4)


def test_the_sorted_share_takes_nothing_from_rows_of_no_group(monkeypatch):
    """What a grouped matmul leaves in the rows past its last group is not
    defined, and on the chip it was at times not finite: one seed in five
    of ``joyai_packed_learn`` read NaN gradients in one bank and in a few
    absent experts' router columns (PR 32, while the cell ran the sorted
    form).  Here every grouped matmul leaves NaN there, in its output and
    in what its transpose hands back for those rows: the output and the
    gradients of the tokens, the pick weights and the banks are finite and
    equal to the clean ones'."""
    from scalerl_tpu.models import routed_ffn

    clean = lax.ragged_dot

    @jax.custom_vjp
    def leftovers(x, n):
        return jnp.where(jnp.arange(x.shape[0])[:, None] < n, x, jnp.nan)

    leftovers.defvjp(
        lambda x, n: (leftovers(x, n), n),
        lambda n, g: (leftovers(g, n), jnp.zeros_like(n)),
    )

    def dirty(lhs, rhs, sizes, **kw):
        n = jnp.sum(sizes).astype(jnp.float32)
        # going back, the lhs' cotangent is dirtied as the output was
        return leftovers(clean(leftovers(lhs, n), rhs, sizes, **kw), n)

    n, k, held, d, f = 40, 3, 2, 16, 8
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    x = jax.random.normal(keys[0], (n, d))
    top_p = jax.random.uniform(keys[1], (n, k), minval=0.1)
    top_i = jax.random.randint(keys[2], (n, k), 0, 16)  # 2 of 16 held
    here = top_i < held
    local = jnp.where(here, top_i, held)
    banks = tuple(
        0.3 * jax.random.normal(kk, shape)
        for kk, shape in zip(keys[3:], ((held, d, f), (held, d, f), (held, f, d)))
    )
    assert 0 < int(jnp.sum(here)) < n * k

    def grads():
        return jax.value_and_grad(
            lambda x, top_p, *banks: jnp.sum(jnp.sin(
                routed_ffn._sorted(x, top_p, local, *banks, held_rows=here.reshape(-1))
            )),
            argnums=(0, 1, 2, 3, 4),
        )(x, top_p, *banks)

    want = grads()
    monkeypatch.setattr(routed_ffn.lax, "ragged_dot", dirty)
    got = grads()
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert np.all(np.isfinite(np.asarray(a)))
        np.testing.assert_allclose(a, b, atol=1e-6)


@pytest.mark.parametrize("n_tokens", [9, 600])
def test_the_shares_add_up_to_the_uncut_layer(n_tokens):
    """Over all 32 shares of ONE of 32 routed experts each, the parts of
    the layer's output that the shares give, with what every chip computes
    alike (the attention, the residual and the shared expert) counted
    once, equal the UNCUT reference layer: ``sum_s out_s - (shares - 1) x
    (the layer with no expert held)``.  The program computes each share
    (banks sliced from one uncut set of weights); the reference computes
    the uncut layer and the layer with nothing held.  The picked scores
    are normalised over all the picks in every share, or the parts would
    not add up."""
    shares = 32
    spec = block_spec("joyai", **{**_SIZES, "num_experts": shares}, experts_held=shares)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, n_tokens, D))
    pos = jnp.arange(n_tokens)[None]
    causal = jnp.tril(jnp.ones((n_tokens, n_tokens), bool))[None]
    masked = Call("masked", attn_mask=causal)

    def block(s):
        return _Block(D, H, 4, None, spec=s, rotary=rotary_fn(pos, 4, 3.2e7, "interleaved"))

    uncut = _thaw(jax.device_get(block(spec).init(jax.random.PRNGKey(4), x, masked)))
    uncut["params"]["experts"]["router_bias"] = jnp.asarray(
        0.05 * np.random.default_rng(8).normal(size=shares), jnp.float32
    )
    geo = GEO._replace(n_routed=shares, first_expert=0, held=shares)
    want, _s, weights, gap = ref.layer(uncut["params"], x, pos, causal, geo)
    assert float(jnp.min(gap)) > 1e-6
    banks = ("w_gate", "w_up", "w_down")

    def sliced(first, held):
        bank = dict(uncut["params"]["experts"])
        bank.update({k: bank[k][first : first + held] for k in banks})
        return {**uncut["params"], "experts": bank}

    apply = jax.jit(
        lambda w, first: block(
            dataclasses.replace(spec, experts_held=1, first_expert=first)
        ).apply({"params": w}, x, masked)[0],  # (out, no cache, no second stream)
        static_argnums=1,
    )
    total = sum(apply(sliced(first, 1), first) for first in range(shares))
    nothing, _s, _w, _g = ref.layer(sliced(0, 0), x, pos, causal, geo._replace(held=0))
    np.testing.assert_allclose(total - (shares - 1) * nothing, want, atol=ATOL)
    # and every share's picks together are all of the layer's picks
    assert int(jnp.sum(weights > 0)) == K * n_tokens


def test_normal_entry_point_generates_and_learns(tmp_path):
    """``--block-family joyai`` through ``SequenceRLTrainer``'s normal
    entry point at a small size: generation rounds on the continuous
    engine and packed learn steps with the MTP term, finite losses."""
    from scalerl_tpu.trainer.sequence_rl import SequenceRLTrainer

    args = _args(
        "--samples-per-prompt", "4", "--genrl-lanes", "8",
        "--work-dir", str(tmp_path), "--platform", "cpu",
    )
    trainer = SequenceRLTrainer(args)
    m1 = trainer.train_round()
    m2 = trainer.train_round()
    assert np.isfinite(m1["total_loss"]) and np.isfinite(m2["total_loss"])
    assert np.isfinite(m2["mtp_loss"]) and 0.0 <= m2["mtp_top1_match"] <= 1.0
    assert m2["moe_held_picks"] > 0 and m2["moe_absent_picks"] > 0
    stats = trainer.engine.stats()
    assert stats["completed"] > 0 and stats["held_expert_tokens"] > 0


def test_the_stack_says_what_it_is_once_a_traced_shape(net):
    """The ``model.layers`` note: a zero-length program span once a shape,
    with the layer kinds, the attention kind, the share and the module."""
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
    assert notes[0]["layers"] == ["plain/swiglu", "plain/experts", "plain/experts"]
    assert (notes[0]["attention"], notes[0]["held"], notes[0]["num_experts"]) == ("mla", HELD, E)
    assert notes[0]["mtp_layers"] == 1 and notes[0]["shape"] == [1, 6]
