"""Token-level sequence-RL plane (ISSUE 10): KV-cached decode parity, the
generation engine's one-batched-read round discipline, token-PPO learning,
and the hermetic generate -> score -> learn e2e on the synthetic recall
task.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from scalerl_tpu.agents.token_ppo import TokenPPOAgent, token_ppo_loss
from scalerl_tpu.config import GenRLArguments
from scalerl_tpu.genrl.engine import (
    GenerationConfig,
    GenerationEngine,
)
from scalerl_tpu.genrl.rollout import pack_sequences, sequence_field_shapes
from scalerl_tpu.genrl.task import TokenRecallTask
from scalerl_tpu.models.transformer import (
    TransformerPolicy,
    decode_attention_mask,
    init_kv_cache,
    prefill_attention_mask,
    sequence_attention_mask,
    sequence_positions,
)
from scalerl_tpu.trainer.sequence_rl import SequenceRLTrainer


def _token_model(vocab=11, d_model=32, layers=2, heads=2, max_len=16):
    return TransformerPolicy(
        num_actions=vocab, vocab_size=vocab, d_model=d_model,
        num_heads=heads, num_layers=layers, max_len=max_len,
    )


def _genrl_args(**kw):
    base = dict(
        seed=3, vocab_size=8, prompt_len=4, max_new_tokens=4,
        d_model=32, n_layers=2, n_heads=2,
        genrl_batch=16, genrl_sample_batch=16, genrl_buffer_sequences=32,
        telemetry_interval_s=0.0, logger_backend="none",
    )
    base.update(kw)
    return GenRLArguments(**base)


# ---------------------------------------------------------------------------
# KV cache: prefill + single-token decode == the full masked forward


def test_kv_cache_decode_matches_full_forward():
    """The incremental path must reproduce the training forward exactly:
    per-position logits/baselines from prefill + R decode steps match the
    one-shot masked forward over the same left-padded sequence."""
    V, P, R = 11, 6, 4
    S = P + R
    m = _token_model(vocab=V, max_len=S)
    B = 3
    lengths = jnp.array([6, 3, 1], jnp.int32)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, V, size=(B, S)), jnp.int32)
    params = m.init(jax.random.PRNGKey(0), toks[:, :2])

    full = m.apply(
        params, toks,
        positions=sequence_positions(lengths, P, S),
        attn_mask=sequence_attention_mask(lengths, P, S),
    )

    cache = init_kv_cache(B, S, m.num_layers, m.num_heads,
                          m.d_model // m.num_heads)
    out, cache = m.apply(
        params, toks[:, :P],
        positions=sequence_positions(lengths, P, S)[:, :P],
        kv_cache=cache, cache_index=0,
        attn_mask=prefill_attention_mask(lengths, P, S),
    )
    np.testing.assert_allclose(
        out.policy_logits[:, -1], full.policy_logits[:, P - 1], atol=1e-5
    )
    np.testing.assert_allclose(
        out.baseline[:, -1], full.baseline[:, P - 1], atol=1e-5
    )

    # one jitted decode step reused across t: same program, traced cursor
    @jax.jit
    def decode(cache, tok, pos, mask, idx):
        return m.apply(
            params, tok, positions=pos, kv_cache=cache,
            cache_index=idx, attn_mask=mask,
        )

    for t in range(R):
        out, cache = decode(
            cache, toks[:, P + t][:, None], (lengths + t)[:, None],
            decode_attention_mask(lengths, P, t, S),
            jnp.int32(P + t),
        )
        np.testing.assert_allclose(
            out.policy_logits[:, 0], full.policy_logits[:, P + t], atol=1e-5
        )


def test_token_and_feature_modes_share_param_structure():
    """vocab_size=None keeps the original Dense obs embed (and its param
    names — the sharded-learner rule table matches on them); token mode
    swaps in the embedding table only."""
    feat = TransformerPolicy(num_actions=4, d_model=16, num_heads=2,
                             num_layers=1, max_len=8)
    p_feat = feat.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4, 3), jnp.float32)
    )
    names = set(p_feat["params"])
    assert "obs_embed" in names and "token_embed" not in names
    tok = _token_model(vocab=7, d_model=16, layers=1, max_len=8)
    p_tok = tok.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    names = set(p_tok["params"])
    assert "token_embed" in names and "obs_embed" not in names
    assert "block_0" in names and "policy_head" in names


# ---------------------------------------------------------------------------
# generation engine


def _engine(iter_mode="auto", **cfg_kw):
    V = 11
    cfg = dict(vocab_size=V, max_prompt_len=6, max_new_tokens=4, seed=7)
    cfg.update(cfg_kw)
    config = GenerationConfig(**cfg)
    max_p = config.resolved_prompt_buckets()[-1]
    max_r = config.resolved_response_buckets()[-1]
    # 1 layer: engine-behavior tests exercise the round machinery, not
    # layer stacking (the 2-layer cache path is covered by the kv parity
    # test above) — halves the per-test compile on the tier-1 clock
    m = _token_model(vocab=config.vocab_size, layers=1,
                     max_len=max_p + max_r)
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32))
    return GenerationEngine(m, params, config, iter_mode=iter_mode)


def test_engine_scan_unroll_parity():
    """The decode loop is the same math whether fused as lax.scan or a
    Python-unrolled body (the PR 6 iter_mode contract): same params + same
    key schedule -> identical tokens and behavior logprobs."""
    rng = np.random.default_rng(1)
    prompts = rng.integers(2, 11, size=(5, 6)).astype(np.int32)
    lengths = np.array([6, 4, 3, 2, 1], np.int32)
    r_scan = _engine("scan").generate(prompts, lengths)
    r_unroll = _engine("unroll").generate(prompts, lengths)
    np.testing.assert_array_equal(
        r_scan.response_tokens, r_unroll.response_tokens
    )
    np.testing.assert_allclose(
        r_scan.behavior_logp, r_unroll.behavior_logp, atol=1e-5
    )
    np.testing.assert_allclose(r_scan.values, r_unroll.values, atol=1e-5)


def test_engine_one_batched_transfer_per_round(monkeypatch):
    """The round discipline graftlint JG001 pins statically, enforced
    dynamically: one _device_put up, one _device_get down, per round —
    and the warm (second) round runs under the armed transfer guard."""
    import scalerl_tpu.genrl.engine as engine_mod

    eng = _engine()
    puts, gets = [], []
    real_put, real_get = engine_mod._device_put, engine_mod._device_get
    monkeypatch.setattr(
        engine_mod, "_device_put", lambda x: (puts.append(1), real_put(x))[1]
    )
    monkeypatch.setattr(
        engine_mod, "_device_get", lambda x: (gets.append(1), real_get(x))[1]
    )
    rng = np.random.default_rng(2)
    prompts = rng.integers(2, 11, size=(4, 6)).astype(np.int32)
    lengths = np.full(4, 6, np.int32)
    eng.generate(prompts, lengths)  # cold: compiles
    assert (len(puts), len(gets)) == (1, 1)
    # warm round: steady_state_guard armed — zero violations means the
    # whole decode loop ran without a single implicit host transfer
    eng.generate(prompts, lengths)
    assert (len(puts), len(gets)) == (2, 2)
    assert len(eng._warm) == 1


def test_engine_generation_tags_and_push_params():
    eng = _engine()
    rng = np.random.default_rng(3)
    prompts = rng.integers(2, 11, size=(2, 4)).astype(np.int32)
    r0 = eng.generate(prompts)
    assert r0.generation == 0
    gen = eng.push_params(
        jax.tree_util.tree_map(lambda x: x * 0.5, eng._params)
    )
    assert gen == 1
    r1 = eng.generate(prompts)
    assert r1.generation == 1


def test_engine_buckets_ragged_prompts_without_retrace():
    """Prompt lengths inside one bucket reuse one compiled program; the
    bucket is chosen by the batch's true max length."""
    eng = _engine()
    rng = np.random.default_rng(4)
    short = rng.integers(2, 11, size=(3, 3)).astype(np.int32)
    r = eng.generate(short, np.array([3, 2, 1], np.int32))
    assert r.prompt_pad == 4  # 3 buckets up to 4 in the pow2 ladder
    assert len(eng._programs) == 1
    r2 = eng.generate(short[:, :2], np.array([2, 2, 1], np.int32))
    assert r2.prompt_pad == 2
    assert len(eng._programs) == 2  # a new bucket pair compiles once
    r3 = eng.generate(short, np.array([3, 3, 3], np.int32))
    assert r3.prompt_pad == 4
    assert len(eng._programs) == 2  # back inside a warm bucket: no retrace


def test_engine_eos_early_stop_masks_and_lengths():
    """With an EOS id, lanes latch done on sampling it: later steps emit
    EOS with a zero mask and response_len counts real tokens only."""
    eng = _engine(eos_token=1)
    rng = np.random.default_rng(5)
    prompts = rng.integers(2, 11, size=(8, 6)).astype(np.int32)
    r = eng.generate(prompts, np.full(8, 6, np.int32))
    for b in range(8):
        n = int(r.response_len[b])
        assert 0 < n <= r.response_pad
        np.testing.assert_array_equal(r.mask[b, n:], 0.0)
        if n < r.response_pad:
            # the latch step sampled EOS (real, counted); everything after
            # is forced EOS with mask 0
            assert r.response_tokens[b, n - 1] == 1
            np.testing.assert_array_equal(r.response_tokens[b, n:], 1)


def test_engine_behavior_logp_matches_sampling_distribution():
    """Stored logprobs are the log-density of the ACTUAL sampling
    distribution (temperature + top-k applied): at temperature 1, no
    top-k, they must equal log_softmax of the model logits at the sampled
    token — recomputed here from the full forward."""
    eng = _engine()
    rng = np.random.default_rng(6)
    prompts = rng.integers(2, 11, size=(3, 6)).astype(np.int32)
    lengths = np.full(3, 6, np.int32)
    r = eng.generate(prompts, lengths)
    P, S = r.prompt_pad, r.prompt_pad + r.response_pad
    m, params = eng.model, eng._params
    lens = jnp.asarray(r.prompt_len)
    full = m.apply(
        params, jnp.asarray(r.sequences),
        positions=sequence_positions(lens, P, S),
        attn_mask=sequence_attention_mask(lens, P, S),
    )
    logp_all = jax.nn.log_softmax(full.policy_logits[:, P - 1:S - 1], -1)
    expect = np.take_along_axis(
        np.asarray(logp_all), r.response_tokens[..., None], axis=-1
    )[..., 0]
    np.testing.assert_allclose(r.behavior_logp, expect, atol=1e-4)


def test_generation_config_validation():
    with pytest.raises(ValueError):
        GenerationConfig(vocab_size=1).validate()
    with pytest.raises(ValueError):
        GenerationConfig(vocab_size=8, temperature=-0.5).validate()
    GenerationConfig(vocab_size=8, temperature=0.0).validate()  # greedy
    with pytest.raises(ValueError):
        GenerationConfig(vocab_size=8, top_k=9).validate()
    with pytest.raises(ValueError):
        GenerationConfig(vocab_size=8, eos_token=8).validate()


# ---------------------------------------------------------------------------
# task + rollout packing


def test_token_recall_task_scoring():
    task = TokenRecallTask(vocab_size=8, prompt_len=3, response_len=3)
    prompts = np.array([[5, 2, 7], [4, 4, 4]], np.int32)
    lengths = np.array([3, 3], np.int32)
    resp = np.array([[5, 5, 2], [4, 4, 4]], np.int32)
    rew = task.score(prompts, lengths, resp, np.array([3, 3], np.int32))
    np.testing.assert_allclose(rew, [2 / 3, 1.0])
    # early-stopped lanes score over their real tokens only
    rew = task.score(prompts, lengths, resp, np.array([1, 2], np.int32))
    np.testing.assert_allclose(rew, [1.0, 1.0])


def test_token_copy_task_scoring():
    task = TokenRecallTask(vocab_size=8, prompt_len=3, response_len=3,
                           mode="copy")
    prompts = np.array([[5, 2, 7]], np.int32)
    rew = task.score(
        prompts, np.array([3], np.int32),
        np.array([[5, 2, 6]], np.int32), np.array([3], np.int32),
    )
    np.testing.assert_allclose(rew, [2 / 3])


def test_pack_sequences_fields_and_priorities():
    eng = _engine()
    rng = np.random.default_rng(7)
    prompts = rng.integers(2, 11, size=(4, 6)).astype(np.int32)
    r = eng.generate(prompts, np.full(4, 6, np.int32))
    rewards = np.array([0.0, 0.25, 0.5, 1.0], np.float32)
    fields, prios = pack_sequences(r, rewards)
    S = r.prompt_pad + r.response_pad
    assert fields["tokens"].shape == (4, S)
    assert fields["behavior_logp"].shape == (4, r.response_pad)
    np.testing.assert_array_equal(fields["reward"], rewards)
    np.testing.assert_array_equal(fields["generation"], 0)
    np.testing.assert_array_equal(prios, 1.0)
    # explicit priorities are floored away from the empty-slot sentinel
    _f, prios = pack_sequences(r, rewards, priorities=np.zeros(4))
    assert (prios >= 1e-6).all()
    shapes = sequence_field_shapes(r.prompt_pad, r.response_pad)
    assert set(shapes) == set(fields)


# ---------------------------------------------------------------------------
# token-PPO learner


def _fake_batch(B=6, P=4, R=4, V=8, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "tokens": jnp.asarray(rng.integers(0, V, (B, P + R)), jnp.int32),
        "behavior_logp": jnp.asarray(
            np.log(rng.uniform(0.05, 0.5, (B, R))), jnp.float32
        ),
        "value": jnp.asarray(rng.normal(0, 0.1, (B, R)), jnp.float32),
        "mask": jnp.asarray(
            (np.arange(R)[None, :] < rng.integers(1, R + 1, (B, 1))),
            jnp.float32,
        ),
        "reward": jnp.asarray(rng.uniform(0, 1, (B,)), jnp.float32),
        "prompt_len": jnp.asarray(rng.integers(1, P + 1, (B,)), jnp.int32),
        "generation": jnp.zeros((B,), jnp.int32),
    }


def test_token_ppo_loss_masks_padding():
    """Padded response positions are numerically invisible: corrupting the
    stored logp/value under a zero mask leaves the loss unchanged."""
    args = _genrl_args()
    m = _token_model(vocab=8, max_len=8)
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32))
    batch = _fake_batch()
    loss, _ = token_ppo_loss(
        params, params, m, batch, clip_range=0.2, value_cost=0.5,
        entropy_cost=0.01, kl_cost=0.0, adv_norm=True,
    )
    poisoned = dict(batch)
    pad = 1.0 - batch["mask"]
    poisoned["behavior_logp"] = batch["behavior_logp"] - 7.0 * pad
    poisoned["value"] = batch["value"] + 100.0 * pad
    loss2, _ = token_ppo_loss(
        params, params, m, poisoned, clip_range=0.2, value_cost=0.5,
        entropy_cost=0.01, kl_cost=0.0, adv_norm=True,
    )
    np.testing.assert_allclose(loss, loss2, atol=1e-5)
    del args


def test_token_ppo_kl_anchor_zero_at_reference_and_metrics():
    """KL(pi || pi_ref) vanishes when params == ref_params and the kl_ref
    metric appears only when the penalty is compiled in."""
    m = _token_model(vocab=8, max_len=8)
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32))
    batch = _fake_batch(seed=1)
    _loss, metrics = token_ppo_loss(
        params, params, m, batch, clip_range=0.2, value_cost=0.5,
        entropy_cost=0.0, kl_cost=0.1, adv_norm=True,
    )
    assert float(metrics["kl_ref"]) == pytest.approx(0.0, abs=1e-6)
    _loss, metrics = token_ppo_loss(
        params, params, m, batch, clip_range=0.2, value_cost=0.5,
        entropy_cost=0.0, kl_cost=0.0, adv_norm=True,
    )
    assert "kl_ref" not in metrics


def test_token_ppo_agent_learn_one_batched_transfer(monkeypatch):
    """agent.learn reads metrics back through get_metrics — ONE batched
    device_get for the whole metric dict (the dispatch-plane seam)."""
    import scalerl_tpu.runtime.dispatch as dispatch_mod

    args = _genrl_args()
    from scalerl_tpu.trainer.sequence_rl import build_genrl_model

    agent = TokenPPOAgent(args, build_genrl_model(args))
    gets = []
    real = dispatch_mod._device_get
    monkeypatch.setattr(
        dispatch_mod, "_device_get",
        lambda x: (gets.append(1), real(x))[1],
    )
    metrics = agent.learn(_fake_batch(B=4, V=args.vocab_size))
    assert len(gets) == 1
    assert np.isfinite(metrics["total_loss"])
    assert "nonfinite_grads" in metrics  # the guard rode along
    assert int(jax.device_get(agent.state.step)) == 1


# ---------------------------------------------------------------------------
# trainer e2e (also runnable standalone via -k e2e)


@pytest.mark.slow
def test_genrl_e2e_token_ppo_improves_reward():
    """The hermetic acceptance loop: token-PPO on the synthetic recall
    task beats the pinned threshold on CPU, with the steady-state rounds
    under the armed transfer guard (a violation raises mid-train)."""
    args = _genrl_args(genrl_batch=64, genrl_sample_batch=64,
                       genrl_buffer_sequences=128, learning_rate=3e-3)
    trainer = SequenceRLTrainer(args)
    summary = trainer.train(60)
    h = trainer.reward_history
    first, last = float(np.mean(h[:10])), float(np.mean(h[-10:]))
    # random policy scores ~1/vocab = 0.125; the pinned seed threshold
    assert last >= 0.5, (first, last)
    assert last > first + 0.2, (first, last)
    assert summary["final_reward_mean"] == pytest.approx(last)
    # the whole run stayed inside the one-read round discipline
    assert trainer.engine._warm  # steady-state guard was armed
    assert summary["staleness"] <= 2.0  # push-per-step keeps lag bounded


@pytest.mark.slow  # ~10 s; mp-sharding parity stays tier-1-covered by
# test_transformer_sharded_matches_unsharded + the fast genrl rounds
def test_genrl_trainer_sharded_mp2_round():
    """The learn step rides the dp×mp sharded plane off the args alone:
    mp=2 lays the transformer's mlp/heads over the mp axis and a round
    still trains."""
    args = _genrl_args(dp_size=4, mp_size=2, n_layers=1)
    trainer = SequenceRLTrainer(args)
    assert trainer.agent.mesh is not None
    assert trainer.agent.mesh.shape["mp"] == 2
    m1 = trainer.train_round()
    m2 = trainer.train_round()
    assert np.isfinite(m1["total_loss"]) and np.isfinite(m2["total_loss"])
    kernel = trainer.agent.state.params["params"]["block_0"]["mlp_in"]["kernel"]
    assert "mp" in str(kernel.sharding.spec)


def test_genrl_args_validation():
    with pytest.raises(ValueError):
        _genrl_args(vocab_size=2).validate()
    with pytest.raises(ValueError):
        _genrl_args(clip_range=1.5).validate()
    with pytest.raises(ValueError):
        _genrl_args(genrl_buffer_sequences=4, genrl_batch=16).validate()
    with pytest.raises(ValueError):
        _genrl_args(genrl_iter_mode="vectorize").validate()
    # packed-learner knobs (ISSUE 15)
    with pytest.raises(ValueError):
        _genrl_args(learner_packed_attn="dense").validate()
    with pytest.raises(ValueError):
        # a row must fit one maximum-length sequence
        _genrl_args(learner_packing=True, learner_pack_len=4).validate()
    _genrl_args(learner_packing=True).validate()
