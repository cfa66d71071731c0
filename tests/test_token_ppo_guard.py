"""The token learner's all-finite guard decides before the update (ISSUE 33).

``make_token_ppo_learn_fn`` takes its verdict from the loss and the
gradient norm and folds it into the update as ``where(ok, candidate, old)``;
every other agent keeps the post-hoc ``guard_nonfinite_updates``
(``tests/test_chaos.py``).  The contract held here, on a tiny packed
learner, with float32 parameters and with ``bf16_params`` (bfloat16
parameters, float32 moments):

- a batch that yields a NaN loss, an infinite gradient, NaN gradients
  under a finite loss, or finite gradients whose sum of squares overflows
  float32 returns the input state bit for bit, counts
  ``skipped_steps == nonfinite_grads == 1``, and the next finite step
  proceeds;
- on finite steps parameters and moments are the unguarded learn fn's, bit
  for bit;
- whenever the step is kept, the new state is all finite.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scalerl_tpu.agents.token_ppo import (
    TokenPPOAgent,
    make_token_ppo_learn_fn,
    token_ppo_packed_loss,
)
from scalerl_tpu.config import GenRLArguments
from scalerl_tpu.genrl.rollout import pack_learner_batch
from scalerl_tpu.parallel.train_step import (
    guard_nonfinite_updates,
    tree_all_finite,
)
from scalerl_tpu.trainer.sequence_rl import build_genrl_model

V, P, R, B = 16, 4, 4, 4


def _args(**kw):
    base = dict(
        vocab_size=V, prompt_len=P, max_new_tokens=R, d_model=16, n_layers=1,
        n_heads=2, genrl_batch=8, genrl_sample_batch=8,
        genrl_buffer_sequences=16, learner_packing=True, adv_norm=False,
        telemetry_interval_s=0.0, logger_backend="none",
    )
    base.update(kw)
    return GenRLArguments(**base)


def _packed_batch(seed=5):
    rng = np.random.default_rng(seed)
    plens, rlens = rng.integers(1, P + 1, B), rng.integers(1, R + 1, B)
    pk = pack_learner_batch(
        [rng.integers(1, V, n).astype(np.int32) for n in plens],
        [rng.integers(1, V, n).astype(np.int32) for n in rlens],
        [np.log(rng.uniform(0.05, 0.5, n)).astype(np.float32) for n in rlens],
        [rng.normal(0, 0.1, n).astype(np.float32) for n in rlens],
        rng.uniform(0, 1, B).astype(np.float32),
        rng.integers(0, 3, B).astype(np.int32),
        pack_len=P + R,
    )
    return {k: jnp.asarray(v) for k, v in pk.fields()[0].items()}


def _one_token(batch):
    """Index of one response token."""
    return tuple(np.argwhere(np.asarray(batch["mask"]) > 0)[1])


def _sum_of_squares(leaves):
    with np.errstate(over="ignore"):
        return sum(np.sum(np.square(x, dtype=np.float32)) for x in leaves)


# each fault: (what it does to a clean batch, what the gradients must then
# look like for the case to be the one its name says)
FAULTS = {
    # a NaN stored logprob: the ratio, the loss and every gradient are NaN
    "nan_loss": (
        lambda b: {**b, "behavior_logp": b["behavior_logp"].at[_one_token(b)].set(jnp.nan)},
        lambda loss, g: np.isnan(loss),
    ),
    # returns beyond float32's square root: the value loss overflows and
    # the value head's gradient holds an infinity
    "inf_gradient": (
        lambda b: {**b, "reward": b["mask"] * 2e38, "value": b["mask"] * 2e38},
        lambda loss, g: any(np.isinf(x).any() for x in g),
    ),
    # a stored logprob of -200: the ratio is infinite, the clip picks the
    # finite side so the LOSS IS FINITE, and 0 x inf makes every gradient NaN
    "nan_gradient_finite_loss": (
        lambda b: {**b, "behavior_logp": b["behavior_logp"].at[_one_token(b)].set(-200.0)},
        lambda loss, g: np.isfinite(loss) and any(np.isnan(x).any() for x in g),
    ),
    # advantages of 1e25: loss and every gradient are finite (about 1e24),
    # their squares are not
    "norm_overflow": (
        lambda b: {**b, "value": b["value"] - 1e25 * b["mask"]},
        lambda loss, g: np.isfinite(loss)
        and all(np.isfinite(x).all() for x in g)
        and not np.isfinite(_sum_of_squares(g)),
    ),
}
# kept steps, one of them near the edge: advantages of 1e17, gradients of
# about 1e16, a finite norm far above ``max_grad_norm``
KEPT = {
    "clean": lambda b: b,
    "large_but_finite": lambda b: {**b, "value": b["value"] - 1e17 * b["mask"]},
}


class _Learner:
    def __init__(self, bf16):
        self.args = _args(bf16_params=bf16)
        self.agent = TokenPPOAgent(self.args, build_genrl_model(self.args))
        self.state = self.agent.state
        self.learn = self.agent._learn  # plain jit, no donation
        unguarded = make_token_ppo_learn_fn(
            self.agent.model, self.agent.optimizer, _args(bf16_params=bf16, nonfinite_guard=False)
        )
        self.unguarded = jax.jit(unguarded)
        # the form every other agent keeps, and this learner had before
        self.post_hoc = jax.jit(guard_nonfinite_updates(unguarded))
        self.batch = _packed_batch()

    def grads(self, batch):
        a = self.args
        (loss, _), g = jax.value_and_grad(token_ppo_packed_loss, has_aux=True)(
            self.state.params, self.state.ref_params, self.agent.model, batch,
            clip_range=a.clip_range, value_cost=a.value_cost,
            entropy_cost=a.entropy_cost, kl_cost=a.kl_cost, adv_norm=a.adv_norm,
        )
        return float(loss), [np.asarray(x, np.float32) for x in jax.tree_util.tree_leaves(g)]


@pytest.fixture(scope="module", params=[False, True], ids=["f32", "bf16_params"])
def learner(request):
    return _Learner(request.param)


def _bits(tree):
    return [np.asarray(x).tobytes() for x in jax.tree_util.tree_leaves(tree)]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_refused_step_returns_the_input_state_bit_for_bit(learner, fault):
    poison, is_the_case = FAULTS[fault]
    bad = poison(learner.batch)
    assert is_the_case(*learner.grads(bad)), "the batch does not make the fault it names"
    state, m = learner.learn(learner.state, bad)
    assert float(m["skipped_steps"]) == 1.0 and float(m["nonfinite_grads"]) == 1.0
    assert _bits(state) == _bits(learner.state)
    assert int(state.step) == 0 and int(state.tokens_seen) == 0
    # the post-hoc form agrees, but for the one case on the conservative
    # side: it applied the zero gradient the clip makes of an infinite norm
    _, m_post = learner.post_hoc(learner.state, bad)
    assert float(m_post["skipped_steps"]) == (0.0 if fault == "norm_overflow" else 1.0)
    # the next finite step proceeds, from the state the refusal kept
    state, m = learner.learn(state, learner.batch)
    assert float(m["skipped_steps"]) == 0.0 and float(m["nonfinite_grads"]) == 0.0
    assert int(state.step) == 1
    assert int(state.tokens_seen) == int(np.sum(np.asarray(learner.batch["mask"])))
    assert _bits(state.params) != _bits(learner.state.params)
    assert bool(tree_all_finite(state))


def test_finite_steps_are_the_unguarded_learn_fns(learner):
    """Same clip, same Adam, same precision: three finite steps leave
    parameters, moments and counters bit for bit where the learn fn built
    with ``nonfinite_guard=False`` leaves them (XLA:CPU contracts the
    update's fusion the same way with the select in it)."""
    a = b = learner.state
    for seed in (5, 6, 7):
        batch = _packed_batch(seed)
        a, ma = learner.learn(a, batch)
        b, mb = learner.unguarded(b, batch)
        assert _bits(a) == _bits(b)
        assert _bits(learner.post_hoc(b, batch)[0]) == _bits(learner.learn(b, batch)[0])
        assert float(ma["grad_norm"]) == float(mb["grad_norm"])
        assert float(ma["total_loss"]) == float(mb["total_loss"])
        assert "nonfinite_grads" not in mb and "skipped_steps" not in mb
    assert int(a.step) == 3


@pytest.mark.parametrize("case", sorted(FAULTS) + sorted(KEPT))
def test_a_kept_step_leaves_a_finite_state(learner, case):
    """``ok`` implies ``tree_all_finite(new_state)``; the cases the guard
    keeps are kept (a finite norm above the clip is no fault)."""
    batch = (FAULTS[case][0] if case in FAULTS else KEPT[case])(learner.batch)
    state, m = learner.learn(learner.state, batch)
    kept = float(m["skipped_steps"]) == 0.0
    assert kept == (case in KEPT)
    if kept:
        assert bool(tree_all_finite(state))
        assert int(state.step) == 1
    # the frozen copy is nobody's to change
    assert _bits(state.ref_params) == _bits(learner.state.ref_params)


def test_off_switches_compile_the_guard_out(monkeypatch):
    """``nonfinite_guard=False`` and ``SCALERL_NONFINITE_GUARD=0`` leave no
    counter in the metric dict and apply whatever the update made."""
    args = _args()
    agent = TokenPPOAgent(args, build_genrl_model(args))
    bad = FAULTS["nan_loss"][0](_packed_batch())
    off = jax.jit(make_token_ppo_learn_fn(agent.model, agent.optimizer, _args(nonfinite_guard=False)))
    monkeypatch.setenv("SCALERL_NONFINITE_GUARD", "0")
    env_off = jax.jit(make_token_ppo_learn_fn(agent.model, agent.optimizer, args))
    monkeypatch.delenv("SCALERL_NONFINITE_GUARD")
    for learn in (off, env_off):
        state, m = learn(agent.state, bad)
        assert "nonfinite_grads" not in m and "skipped_steps" not in m
        assert int(state.step) == 1 and not bool(tree_all_finite(state.params))


def test_check_every_is_not_read_by_this_path():
    """``nonfinite_check_every`` amortises the post-hoc guard; a select
    inside the update leaves nothing to amortise, so every step is judged
    whatever it says."""
    args = _args(nonfinite_check_every=4)
    agent = TokenPPOAgent(args, build_genrl_model(args))
    batch = _packed_batch()
    state, _ = agent._learn(agent.state, batch)
    assert int(state.step) == 1  # 1 % 4 != 0: the post-hoc form would pass this step through
    kept, m = agent._learn(state, FAULTS["norm_overflow"][0](batch))
    assert float(m["skipped_steps"]) == 1.0
    assert _bits(kept) == _bits(state)


def test_refused_step_under_a_mesh_with_donation():
    """``dp=4 x mp=2`` over the eight virtual devices, the state donated:
    the verdict is one replicated scalar, a refused step hands back the
    input's values in the donated buffers, and the next step trains."""
    args = _args(n_heads=4, dp_size=4, mp_size=2)
    agent = TokenPPOAgent(args, build_genrl_model(args))
    from scalerl_tpu.parallel.mesh import mesh_spec_from_args

    agent.enable_mesh(mesh_spec_from_args(args))
    before = _bits(agent.state)
    two = _packed_batch(5), _packed_batch(6)
    batch = {k: jnp.concatenate([b[k] for b in two])[:4] for k in two[0]}  # a row a dp shard
    m = agent.learn(FAULTS["nan_gradient_finite_loss"][0](batch))
    assert m["skipped_steps"] == 1.0 and m["nonfinite_grads"] == 1.0
    assert _bits(agent.state) == before
    m = agent.learn(batch)
    assert m["skipped_steps"] == 0.0 and int(agent.state.step) == 1
    assert bool(tree_all_finite(agent.state))


def test_guard_form_is_recorded_once_a_traced_state(monkeypatch):
    """The guard engages on every step, so a trace records which form ran:
    one zero-length ``learn.guard`` span a traced state."""
    from scalerl_tpu.runtime import tracing

    monkeypatch.setenv(tracing.ENV_SAMPLE, "1.0")
    tracing.reset()
    try:
        # a geometry no other test traces (the note is cached by its attrs)
        args = _args(d_model=24, n_heads=3)
        agent = TokenPPOAgent(args, build_genrl_model(args))
        for seed in (5, 6):
            agent.learn(_packed_batch(seed))
        spans = [s for s in tracing.get_tracer().finished() if s["name"] == "learn.guard"]
        assert len(spans) == 1, spans
        judged = [agent.state.params, agent.state.opt_state, agent.state.step, agent.state.tokens_seen]
        leaves = jax.tree_util.tree_leaves(judged)
        assert spans[0]["attrs"] == {
            "verdict": "isfinite(loss, grad_norm)",
            "leaves": len(leaves),
            "state_bytes": sum(x.size * x.dtype.itemsize for x in leaves),
        }
    finally:
        monkeypatch.delenv(tracing.ENV_SAMPLE)
        tracing.reset()
