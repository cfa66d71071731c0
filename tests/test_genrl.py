"""Token-level sequence-RL plane (ISSUE 10): the generation engine against
the full forward, its one-upload-one-read macro-step discipline, token-PPO
learning, and the hermetic generate -> score -> learn e2e on the synthetic
recall task.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from genrl_reference import full_forward, greedy_full_forward, left_padded
from scalerl_tpu.agents.token_ppo import TokenPPOAgent, token_ppo_loss
from scalerl_tpu.config import GenRLArguments
from scalerl_tpu.genrl.continuous import ContinuousConfig, ContinuousEngine
from scalerl_tpu.genrl.rollout import pack_completions, sequence_field_shapes
from scalerl_tpu.genrl.task import TokenRecallTask
from scalerl_tpu.models.transformer import TransformerPolicy
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

P_MAX, R_MAX = 6, 4


def _engine(iter_mode="auto", layers=1, **cfg_kw):
    V = 11
    cfg = dict(
        vocab_size=V, max_prompt_len=P_MAX, max_new_tokens=R_MAX, seed=7,
        lanes=4, page_size=2, steps_per_macro=2,
    )
    cfg.update(cfg_kw)
    config = ContinuousConfig(**cfg)
    max_p = config.resolved_prompt_buckets()[-1]
    max_r = config.resolved_response_buckets()[-1]
    # 1 layer unless a test is about layer stacking: engine-behavior tests
    # exercise the macro-step machinery — halves the per-test compile
    m = _token_model(vocab=config.vocab_size, layers=layers,
                     max_len=max_p + max_r)
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32))
    return ContinuousEngine(m, params, config, iter_mode=iter_mode)


def _generate(eng, prompts, lengths):
    """Every prompt through the engine; completions in prompt order."""
    for i in range(len(lengths)):
        eng.submit(prompts[i], int(lengths[i]), tag=i)
    done = eng.run_until(len(lengths), max_macro_steps=200)
    return sorted(done, key=lambda c: c.tag)


@pytest.mark.parametrize("layers", [1, 2])
def test_paged_decode_matches_full_forward(layers):
    """The incremental path must reproduce the training forward exactly:
    the engine's greedy tokens, log-probabilities and baselines (paged
    prefill over compact prompts, then one token a substep through the
    page table) are those of the one-shot masked forward over the same
    left-padded sequence, token after token."""
    eng = _engine(temperature=0.0, layers=layers)
    rng = np.random.default_rng(0)
    prompts = rng.integers(2, 11, size=(3, P_MAX)).astype(np.int32)
    lengths = np.array([6, 3, 1], np.int32)
    ref = greedy_full_forward(
        eng.model, eng._params, prompts, lengths, P_MAX, R_MAX
    )
    for i, c in enumerate(_generate(eng, prompts, lengths)):
        np.testing.assert_array_equal(c.response_tokens, ref.response_tokens[i])
        np.testing.assert_allclose(c.behavior_logp, ref.behavior_logp[i], atol=1e-5)
        np.testing.assert_allclose(c.values, ref.values[i], atol=1e-5)


@pytest.mark.parametrize("steps_in_flight", [1, 2])
def test_engine_scan_unroll_parity(steps_in_flight):
    """The substep loop is the same math whether fused as lax.scan or a
    Python-unrolled body (the PR 6 iter_mode contract): same params + same
    key schedule -> identical tokens and behavior logprobs."""
    rng = np.random.default_rng(1)
    prompts = rng.integers(2, 11, size=(5, P_MAX)).astype(np.int32)
    lengths = np.array([6, 4, 3, 2, 1], np.int32)
    r_scan = _generate(
        _engine("scan", steps_in_flight=steps_in_flight), prompts, lengths
    )
    r_unroll = _generate(
        _engine("unroll", steps_in_flight=steps_in_flight), prompts, lengths
    )
    for a, b in zip(r_scan, r_unroll):
        np.testing.assert_array_equal(a.response_tokens, b.response_tokens)
        np.testing.assert_allclose(a.behavior_logp, b.behavior_logp, atol=1e-5)
        np.testing.assert_allclose(a.values, b.values, atol=1e-5)


def test_engine_one_upload_one_read_per_macro_step(monkeypatch):
    """The discipline graftlint JG001 pins statically, enforced
    dynamically on a cold engine: an admitting step uploads its prefill
    group(s) and the table and reads once; every steady step after it is
    one _device_put up and one _device_get down, under the armed transfer
    guard once the first macro-step has compiled."""
    import scalerl_tpu.genrl.continuous as cont_mod

    eng = _engine(steps_in_flight=1)
    puts, gets = [], []
    real_put, real_get = cont_mod._device_put, cont_mod._device_get
    monkeypatch.setattr(
        cont_mod, "_device_put", lambda x: (puts.append(1), real_put(x))[1]
    )
    monkeypatch.setattr(
        cont_mod, "_device_get", lambda x: (gets.append(1), real_get(x))[1]
    )
    rng = np.random.default_rng(2)
    prompts = rng.integers(2, 11, size=(4, P_MAX)).astype(np.int32)
    for p in prompts:
        eng.submit(p, P_MAX)
    eng.step()  # cold: compiles; one prefill group (one bucket) + the table
    assert (len(puts), len(gets)) == (2, 1)
    assert eng._warm
    steady = 0
    while eng.live_lanes:
        puts.clear()
        gets.clear()
        eng.step()  # nothing to admit: zero implicit transfers, or it raises
        assert (len(puts), len(gets)) == (1, 1)
        steady += 1
    assert steady >= 1


def test_engine_generation_tags_and_push_params():
    eng = _engine()
    rng = np.random.default_rng(3)
    prompts = rng.integers(2, 11, size=(2, 4)).astype(np.int32)
    lengths = np.full(2, 4, np.int32)
    assert [c.generation for c in _generate(eng, prompts, lengths)] == [0, 0]
    gen = eng.push_params(
        jax.tree_util.tree_map(lambda x: x * 0.5, eng._params)
    )
    assert gen == 1
    assert [c.generation for c in _generate(eng, prompts, lengths)] == [1, 1]


def test_engine_buckets_ragged_prompts_without_retrace():
    """Prompt lengths inside one bucket reuse one compiled prefill
    program; a new bucket compiles once; the decode macro-step is traced
    once whatever the prompts."""
    eng = _engine(prefix_cache=False)
    rng = np.random.default_rng(4)
    short = rng.integers(2, 11, size=(3, 3)).astype(np.int32)
    _generate(eng, short, np.array([3, 3, 3], np.int32))  # bucket 4
    programs = len(eng._prefill_fns)
    assert eng._prefill_traces == programs
    _generate(eng, short, np.array([2, 2, 2], np.int32))  # bucket 2: new
    assert len(eng._prefill_fns) == programs + 1
    _generate(eng, short, np.array([3, 3, 3], np.int32))  # warm bucket
    assert len(eng._prefill_fns) == programs + 1
    assert eng._prefill_traces == len(eng._prefill_fns)
    assert eng._decode_traces == 1


def test_engine_eos_early_stop_masks_and_lengths():
    """With an EOS id, a lane latches done on sampling it: the harvested
    response holds real tokens only, ends in EOS when it stopped short of
    the budget, and packs into the learner layout with a mask that is 1
    exactly on those tokens."""
    eng = _engine(eos_token=1)
    rng = np.random.default_rng(5)
    prompts = rng.integers(2, 11, size=(8, P_MAX)).astype(np.int32)
    done = _generate(eng, prompts, np.full(8, P_MAX, np.int32))
    packed = pack_completions(done, 8, R_MAX)
    for b, c in enumerate(done):
        n = len(c.response_tokens)
        assert 0 < n <= R_MAX and packed.response_len[b] == n
        np.testing.assert_array_equal(packed.mask[b, :n], 1.0)
        np.testing.assert_array_equal(packed.mask[b, n:], 0.0)
        assert 1 not in c.response_tokens[:-1]
        if n < R_MAX:
            assert c.response_tokens[-1] == 1  # the latch step is real
    assert any(len(c.response_tokens) < R_MAX for c in done)


def test_engine_behavior_logp_matches_sampling_distribution():
    """Stored logprobs are the log-density of the ACTUAL sampling
    distribution (temperature + top-k applied): at temperature 1, no
    top-k, they must equal log_softmax of the model logits at the sampled
    token — recomputed here from the full forward."""
    eng = _engine()
    rng = np.random.default_rng(6)
    prompts = rng.integers(2, 11, size=(3, P_MAX)).astype(np.int32)
    lengths = np.array([6, 4, 2], np.int32)
    done = _generate(eng, prompts, lengths)
    S = P_MAX + R_MAX
    seq = left_padded(prompts, lengths, P_MAX, S)
    for b, c in enumerate(done):
        seq[b, P_MAX:] = c.response_tokens
    full = full_forward(eng.model, eng._params, seq, lengths, P_MAX)
    logp_all = jax.nn.log_softmax(full.policy_logits[:, P_MAX - 1:S - 1], -1)
    for b, c in enumerate(done):
        expect = np.asarray(logp_all)[b, np.arange(R_MAX), c.response_tokens]
        np.testing.assert_allclose(c.behavior_logp, expect, atol=1e-4)


def test_generation_config_validation():
    with pytest.raises(ValueError):
        ContinuousConfig(vocab_size=1).validate()
    with pytest.raises(ValueError):
        ContinuousConfig(vocab_size=8, temperature=-0.5).validate()
    ContinuousConfig(vocab_size=8, temperature=0.0).validate()  # greedy
    with pytest.raises(ValueError):
        ContinuousConfig(vocab_size=8, top_k=9).validate()
    with pytest.raises(ValueError):
        ContinuousConfig(vocab_size=8, eos_token=8).validate()
    with pytest.raises(ValueError):
        ContinuousConfig(vocab_size=8, max_new_tokens=0).validate()


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


def test_packed_completions_fields_and_priorities():
    eng = _engine()
    rng = np.random.default_rng(7)
    prompts = rng.integers(2, 11, size=(4, P_MAX)).astype(np.int32)
    done = _generate(eng, prompts, np.full(4, P_MAX, np.int32))
    packed = pack_completions(done, 8, R_MAX)
    rewards = np.array([0.0, 0.25, 0.5, 1.0], np.float32)
    fields, prios = packed.fields(rewards)
    assert fields["tokens"].shape == (4, 8 + R_MAX)
    assert fields["behavior_logp"].shape == (4, R_MAX)
    np.testing.assert_array_equal(fields["reward"], rewards)
    np.testing.assert_array_equal(fields["generation"], 0)
    np.testing.assert_array_equal(prios, 1.0)
    # explicit priorities are floored away from the empty-slot sentinel
    _f, prios = packed.fields(rewards, priorities=np.zeros(4))
    assert (prios >= 1e-6).all()
    assert set(sequence_field_shapes(8, R_MAX)) == set(fields)


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
