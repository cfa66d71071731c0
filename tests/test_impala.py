import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scalerl_tpu.agents.impala import (
    ImpalaAgent,
    impala_loss,
    make_impala_learn_fn,
    make_impala_optimizer,
)
from scalerl_tpu.config import ImpalaArguments
from scalerl_tpu.data.trajectory import Trajectory, TrajectorySpec, batch_to_trajectory
from scalerl_tpu.envs import make_jax_vec_env, make_vect_envs
from scalerl_tpu.runtime.device_loop import DeviceActorLearnerLoop
from scalerl_tpu.runtime.param_server import ParameterServer
from scalerl_tpu.runtime.rollout_queue import RolloutQueue


def _args(**kw):
    base = dict(
        env_id="CartPole-v1",
        rollout_length=8,
        batch_size=4,
        num_actors=2,
        num_buffers=8,
        use_lstm=False,
        hidden_size=64,
        logger_backend="none",
    )
    base.update(kw)
    return ImpalaArguments(**base)


def test_impala_agent_vector_obs_learn_step():
    args = _args()
    agent = ImpalaAgent(args, obs_shape=(4,), num_actions=2, obs_dtype=jnp.float32)
    T, B = args.rollout_length, 4
    key = jax.random.PRNGKey(0)
    traj = Trajectory(
        obs=jax.random.normal(key, (T + 1, B, 4)),
        action=jax.random.randint(key, (T + 1, B), 0, 2),
        reward=jax.random.normal(key, (T + 1, B)),
        done=jnp.zeros((T + 1, B), bool),
        logits=jax.random.normal(key, (T + 1, B, 2)),
        core_state=(),
    )
    m1 = agent.learn(traj)
    m2 = agent.learn(traj)
    assert np.isfinite(m1["total_loss"]) and np.isfinite(m2["total_loss"])
    assert m1["total_loss"] != m2["total_loss"]
    assert int(agent.state.step) == 2
    assert int(agent.state.env_frames) == 2 * T * B


def test_impala_loss_on_policy_equals_a2c():
    """With behavior == target logits, V-trace advantages equal the
    discounted-return advantage; the loss should be finite and its gradient
    should push the chosen-action probability up for positive advantage."""
    args = _args()
    agent = ImpalaAgent(args, obs_shape=(4,), num_actions=2, obs_dtype=jnp.float32)
    T, B = 4, 2
    obs = jnp.ones((T + 1, B, 4))
    out, _ = agent.model.apply(
        agent.state.params, obs, jnp.zeros((T + 1, B), jnp.int32),
        jnp.zeros((T + 1, B)), jnp.zeros((T + 1, B), bool), (),
    )
    traj = Trajectory(
        obs=obs,
        action=jnp.zeros((T + 1, B), jnp.int32),
        reward=jnp.ones((T + 1, B)),
        done=jnp.zeros((T + 1, B), bool),
        logits=out.policy_logits,
        core_state=(),
    )
    loss, metrics = impala_loss(
        agent.state.params, agent.model, traj,
        discounting=0.99, baseline_cost=0.5, entropy_cost=0.01,
    )
    assert np.isfinite(float(loss))
    assert float(metrics["mean_reward"]) == 1.0


@pytest.mark.slow
def test_impala_lstm_agent_pixels():
    args = _args(use_lstm=True, hidden_size=32, rollout_length=3)
    agent = ImpalaAgent(args, obs_shape=(84, 84, 4), num_actions=6)
    T, B = 3, 2
    traj = Trajectory(
        obs=jnp.zeros((T + 1, B, 84, 84, 4), jnp.uint8),
        action=jnp.zeros((T + 1, B), jnp.int32),
        reward=jnp.zeros((T + 1, B)),
        done=jnp.zeros((T + 1, B), bool),
        logits=jnp.zeros((T + 1, B, 6)),
        core_state=agent.initial_state(B),
    )
    m = agent.learn(traj)
    assert np.isfinite(m["total_loss"])
    # act API
    a, logits, core = agent.act(
        np.zeros((B, 84, 84, 4), np.uint8), np.zeros(B, np.int32),
        np.zeros(B, np.float32), np.zeros(B, bool), agent.initial_state(B),
    )
    assert a.shape == (B,) and logits.shape == (B, 6)


@pytest.mark.slow
def test_device_loop_cartpole_learns():
    """The fused device loop must run and improve returns on CartPole.

    ~35 s of learning wall-clock: rides ``-m slow`` (ISSUE 14 tier-1
    budget trim); the fused driver's mechanics stay covered in tier-1 by
    the dispatch/parity suite and the smoke tests here."""
    args = _args(
        rollout_length=16, gamma=0.99, entropy_cost=0.01,
        learning_rate=1e-2, hidden_size=64,
    )
    venv = make_jax_vec_env("CartPole-v1", num_envs=16)
    agent = ImpalaAgent(args, obs_shape=(4,), num_actions=2, obs_dtype=jnp.float32)
    loop = DeviceActorLearnerLoop(
        model=agent.model, venv=venv,
        learn_fn=make_impala_learn_fn(agent.model, agent.optimizer, args),
        unroll_length=args.rollout_length, iters_per_call=20,
    )
    key = jax.random.PRNGKey(0)
    carry = loop.init_carry(key)
    state = agent.state
    state, carry, _ = loop.run(state, carry, key, num_calls=1)
    early_return = float(
        jnp.sum(carry.return_sum) / jnp.maximum(jnp.sum(carry.episode_count), 1)
    )
    # train more
    state, carry, _ = loop.run(state, carry, jax.random.PRNGKey(1), num_calls=8)
    late = carry
    late_return = float(
        jnp.sum(late.return_sum) / jnp.maximum(jnp.sum(late.episode_count), 1)
    )
    assert int(state.step) == 9 * 20
    assert np.isfinite(late_return)
    # cumulative mean should exceed the early mean if any learning happened
    assert late_return > early_return, (early_return, late_return)


def test_rollout_queue_batching():
    spec = TrajectorySpec(unroll_length=4, batch_size=2, obs_shape=(4,), num_actions=2,
                          obs_dtype=jnp.float32)
    q = RolloutQueue(spec, num_slots=4)
    i1 = q.acquire(); i2 = q.acquire()
    q.slots[i1]["obs"][:] = 1.0
    q.slots[i2]["obs"][:] = 2.0
    q.commit(i1); q.commit(i2)
    batch, idxs = q.get_batch(2, timeout=2.0)
    assert batch["obs"].shape == (5, 4, 4)  # [T+1, 2 slots x B=2, D]
    assert set(np.unique(batch["obs"])) == {1.0, 2.0}
    q.recycle(idxs)
    traj = batch_to_trajectory(batch)
    assert traj.obs.shape == (5, 4, 4)
    assert traj.core_state == ()


def test_rollout_queue_timeout_returns_drained_slots():
    """A partial get_batch that times out must hand its drained slots back
    to the full queue — otherwise every timeout leaks a slot until the
    pool deadlocks."""
    spec = TrajectorySpec(unroll_length=2, batch_size=1, obs_shape=(4,), num_actions=2)
    q = RolloutQueue(spec, num_slots=2)
    i1 = q.acquire()
    q.commit(i1)
    with pytest.raises(TimeoutError):
        q.get_batch(2, timeout=0.2)  # only 1 slot full
    # the drained slot is back: a 1-slot batch succeeds immediately
    batch, idxs = q.get_batch(1, timeout=0.5)
    assert idxs == [i1]


def test_rollout_queue_error_funnel():
    spec = TrajectorySpec(unroll_length=2, batch_size=1, obs_shape=(4,), num_actions=2)
    q = RolloutQueue(spec, num_slots=2)
    q.report_error(ValueError("actor exploded"))
    with pytest.raises(RuntimeError, match="actor worker died"):
        q.get_batch(1, timeout=0.5)


def test_parameter_server_versioning():
    ps = ParameterServer()
    w, v = ps.pull()
    assert w is None and v == 0
    v1 = ps.push({"w": jnp.ones(3)})
    w, v = ps.pull()
    assert v == v1 == 1 and isinstance(w["w"], np.ndarray)
    # current caller gets a no-op
    w2, v2 = ps.pull(have_version=v)
    assert w2 is None and v2 == 1


def test_host_actor_learner_trainer_smoke(tmp_path):
    from scalerl_tpu.trainer.actor_learner import HostActorLearnerTrainer

    args = _args(
        rollout_length=8, batch_size=4, num_actors=2, num_buffers=8,
        logger_frequency=10**9, work_dir=str(tmp_path), hidden_size=32,
    )
    agent = ImpalaAgent(args, obs_shape=(4,), num_actions=2, obs_dtype=jnp.float32)
    env_fns = [
        (lambda i=i: make_vect_envs("CartPole-v1", num_envs=2, seed=i, async_envs=False))
        for i in range(2)
    ]
    trainer = HostActorLearnerTrainer(args, agent, env_fns)
    result = trainer.train(total_frames=512)
    assert result["env_frames"] >= 512
    assert np.isfinite(result["total_loss"])
    assert int(agent.state.step) > 0
    assert trainer.param_server.version > 0


class _CrashOnceVec:
    """Vector-env proxy: the FIRST instance raises after ``crash_after``
    steps (a dead env backend); rebuilds behave normally."""

    built = 0

    def __init__(self, inner, crash_after: int) -> None:
        type(self).built += 1
        self._inner = inner
        self._crash_after = crash_after if type(self).built == 1 else None
        self._steps = 0

    def step(self, actions):
        self._steps += 1
        if self._crash_after is not None and self._steps >= self._crash_after:
            raise RuntimeError("env backend died")
        return self._inner.step(actions)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def test_host_actor_elastic_restart(tmp_path):
    """Elastic actors: a crashing env stack is rebuilt from the factory and
    training runs to completion instead of dying (restart budget honored)."""
    from scalerl_tpu.trainer.actor_learner import HostActorLearnerTrainer

    _CrashOnceVec.built = 0
    args = _args(
        rollout_length=8, batch_size=4, num_actors=1, num_buffers=8,
        logger_frequency=10**9, work_dir=str(tmp_path), hidden_size=32,
    )
    agent = ImpalaAgent(args, obs_shape=(4,), num_actions=2, obs_dtype=jnp.float32)

    def env_fn():
        return _CrashOnceVec(
            make_vect_envs("CartPole-v1", num_envs=4, seed=0, async_envs=False),
            crash_after=12,
        )

    trainer = HostActorLearnerTrainer(
        args, agent, [env_fn], max_actor_restarts=1
    )
    result = trainer.train(total_frames=512)
    assert result["env_frames"] >= 512
    assert trainer.actor_restarts == 1
    assert _CrashOnceVec.built == 2  # the crashed stack was rebuilt
    trainer.close()


def test_parameter_server_lazy_host_snapshot():
    """A to_host=False publish (SEED hot loop) still hands pullers numpy:
    materialization happens lazily on first pull and is cached."""
    server = ParameterServer()
    dev = {"w": jnp.ones((3,))}
    v = server.push(dev, to_host=False)
    weights, version = server.pull()
    assert version == v
    leaf = weights["w"]
    assert isinstance(leaf, np.ndarray)
    # cached: a second pull at an older version returns the same host array
    w2, _ = server.pull(have_version=-1)
    assert w2["w"] is leaf


def test_parameter_server_push_survives_donation():
    """to_host=False publishes a device-side copy: pullers must still read
    the snapshot after the learner's next (donating) step deletes the
    original buffers (parallel/train_step.py donates state)."""
    server = ParameterServer()
    x = jnp.ones((4,))
    server.push({"w": x}, to_host=False)
    x.delete()  # simulate donation invalidating the learner's buffer
    weights, _ = server.pull()
    np.testing.assert_array_equal(np.asarray(weights["w"]), np.ones(4))


def test_host_actor_learner_prefetch_thread(tmp_path):
    """num_learner_threads >= 2 runs the assembly-prefetch learner path
    (reference num_learners capability, impala_atari.py:439-456)."""
    from scalerl_tpu.trainer.actor_learner import HostActorLearnerTrainer

    args = _args(
        rollout_length=8, batch_size=4, num_actors=2, num_buffers=8,
        num_learner_threads=2, logger_frequency=256, work_dir=str(tmp_path),
        hidden_size=32,
    )
    agent = ImpalaAgent(args, obs_shape=(4,), num_actions=2, obs_dtype=jnp.float32)
    env_fns = [
        (lambda i=i: make_vect_envs("CartPole-v1", num_envs=2, seed=i, async_envs=False))
        for i in range(2)
    ]
    trainer = HostActorLearnerTrainer(args, agent, env_fns)
    result = trainer.train(total_frames=512)
    assert result["env_frames"] >= 512
    assert np.isfinite(result["total_loss"])
    assert int(agent.state.step) > 0


def test_impala_bfloat16_compute_dtype():
    """bf16 torso trains: finite loss/grads, f32 params preserved."""
    import jax
    import jax.numpy as jnp

    from scalerl_tpu.agents.impala import ImpalaAgent, make_impala_learn_fn
    from scalerl_tpu.config import ImpalaArguments
    from scalerl_tpu.data.trajectory import Trajectory

    T, B = 4, 2
    args = ImpalaArguments(
        use_lstm=False, hidden_size=32, rollout_length=T, batch_size=B,
        max_timesteps=0, compute_dtype="bfloat16",
    )
    agent = ImpalaAgent(args, obs_shape=(84, 84, 4), num_actions=4)
    assert agent.model.dtype == jnp.bfloat16
    # params stay f32 (mixed precision contract)
    assert all(
        leaf.dtype == jnp.float32
        for leaf in jax.tree_util.tree_leaves(agent.state.params)
    )
    traj = Trajectory(
        obs=jnp.zeros((T + 1, B, 84, 84, 4), jnp.uint8),
        action=jnp.zeros((T + 1, B), jnp.int32),
        reward=jnp.ones((T + 1, B), jnp.float32),
        done=jnp.zeros((T + 1, B), jnp.bool_),
        logits=jnp.zeros((T + 1, B, 4), jnp.float32),
        core_state=(),
    )
    metrics = agent.learn(traj)
    assert all(m == m for m in metrics.values())  # finite


@pytest.mark.slow  # ~11 s; dtype plumbing tier-1-covered by test_bf16_params_with_fp32_opt_state
# + the fp32 fused loop in test_parallel (ISSUE 19 buy-back)
def test_impala_bfloat16_fused_device_loop():
    """The accelerator config — bf16 torso inside the fused
    env+inference+V-trace loop (the ``impala_fused`` cell sets
    compute_dtype='bfloat16') — compiles and produces finite losses."""
    import jax

    from scalerl_tpu.envs.jax_envs.base import JaxVecEnv
    from scalerl_tpu.envs.jax_envs.synthetic import SyntheticPixelEnv

    T, B = 4, 4
    args = ImpalaArguments(
        use_lstm=False, hidden_size=32, rollout_length=T, batch_size=B,
        max_timesteps=0, compute_dtype="bfloat16",
    )
    env = SyntheticPixelEnv()
    venv = JaxVecEnv(env, num_envs=B)
    agent = ImpalaAgent(args, obs_shape=env.observation_shape,
                        num_actions=env.num_actions)
    learn = make_impala_learn_fn(agent.model, agent.optimizer, args)
    loop = DeviceActorLearnerLoop(agent.model, venv, learn, T, iters_per_call=2)
    carry = loop.init_carry(jax.random.PRNGKey(0))
    state, carry, m = loop.train_chunk(agent.state, carry, jax.random.PRNGKey(1))
    assert int(state.step) == 2
    loss = float(m["total_loss"])
    assert loss == loss
