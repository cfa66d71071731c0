"""Multi-chip parallelism tests on the 8-device virtual CPU mesh.

SURVEY.md §4's prescription: multi-chip tests must run single-host via
``--xla_force_host_platform_device_count=8`` (set in conftest.py).  The
correctness bar is the one the reference's DDP learner implied but never
tested: a data-parallel update over a sharded batch must equal the
single-device update over the full batch.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scalerl_tpu.agents.impala import (
    ImpalaAgent,
    make_impala_learn_fn,
)
from scalerl_tpu.config import ImpalaArguments
from scalerl_tpu.data.trajectory import Trajectory
from scalerl_tpu.parallel import (
    MeshSpec,
    make_mesh,
    make_parallel_learn_fn,
)
from scalerl_tpu.parallel.sharding import (
    batch_sharding_tree,
    infer_param_spec,
    pad_to_multiple,
)


def test_mesh_spec_parse():
    spec = MeshSpec.parse("dp=4, tp=2")
    assert spec.size("dp") == 4 and spec.size("tp") == 2 and spec.size("sp") == 1
    assert spec.total == 8
    with pytest.raises(ValueError):
        MeshSpec.parse("bogus=2")


def test_make_mesh_default_all_dp():
    mesh = make_mesh()
    assert mesh.shape["dp"] == len(jax.devices())
    assert mesh.shape["tp"] == 1


def test_make_mesh_rejects_wrong_total():
    with pytest.raises(ValueError):
        make_mesh("dp=3")


def test_infer_param_spec_rules():
    mesh = make_mesh("fsdp=2,tp=2,dp=2")
    # rank-1: replicated
    assert infer_param_spec((), jnp.zeros(128), mesh) == jax.sharding.PartitionSpec()
    # big rank-2: largest dim on fsdp, other on tp
    spec = infer_param_spec((), jnp.zeros((512, 64)), mesh)
    assert spec[0] == "fsdp" and spec[1] == "tp"
    # indivisible dims: replicated
    spec = infer_param_spec((), jnp.zeros((7, 13)), mesh)
    assert all(s is None for s in spec)
    # tiny dims (e.g. a [hidden, num_actions] head's action dim) replicate
    # even when divisible: micro-shards force GSPMD involuntary full
    # rematerialization of the activation gradient (VERDICT r1 weak #6)
    spec = infer_param_spec((), jnp.zeros((64, 6)), mesh)
    assert spec[0] == "fsdp" and spec[1] is None


def test_flagship_sharded_step_no_involuntary_remat(capfd):
    """Compile the flagship dp/fsdp/tp IMPALA step (conv+LSTM AtariNet at
    real 84x84 frame shapes) and fail if XLA's SPMD partitioner reports an
    involuntary full rematerialization — the replicate-then-repartition
    fallback is a multi-chip perf cliff (VERDICT r1 weak #6)."""
    T, B = 4, 16
    args = ImpalaArguments(
        use_lstm=True, hidden_size=64, rollout_length=T, batch_size=B,
        max_timesteps=0,
    )
    agent = ImpalaAgent(args, obs_shape=(84, 84, 4), num_actions=6)
    learn = make_impala_learn_fn(agent.model, agent.optimizer, args)
    core = agent.initial_state(B)
    traj = Trajectory(
        obs=jnp.zeros((T + 1, B, 84, 84, 4), jnp.uint8),
        action=jnp.zeros((T + 1, B), jnp.int32),
        reward=jnp.zeros((T + 1, B), jnp.float32),
        done=jnp.zeros((T + 1, B), jnp.bool_),
        logits=jnp.zeros((T + 1, B, 6), jnp.float32),
        core_state=core,
    )
    mesh = make_mesh("dp=2,fsdp=2,tp=2")
    plearn = make_parallel_learn_fn(
        learn, mesh, agent.state, batch_example=traj, donate_state=False
    )
    capfd.readouterr()  # drop anything already buffered
    plearn.lower(agent.state, traj).compile()
    err = capfd.readouterr().err
    assert "Involuntary full rematerialization" not in err, (
        "SPMD partitioner fell back to replicate-then-repartition:\n"
        + "\n".join(
            l for l in err.splitlines() if "rematerialization" in l
        )[:2000]
    )


def test_pad_to_multiple():
    x = np.ones((5, 3))
    y = pad_to_multiple(x, 4, axis=0)
    assert y.shape == (8, 3) and y[5:].sum() == 0
    assert pad_to_multiple(x, 5, axis=0) is x


def _tiny_traj(key, B, A=4, T=5, obs_dim=8):
    ks = jax.random.split(key, 3)
    return Trajectory(
        obs=jax.random.normal(ks[0], (T + 1, B, obs_dim), jnp.float32),
        action=jax.random.randint(ks[1], (T + 1, B), 0, A),
        reward=jax.random.normal(ks[2], (T + 1, B)),
        done=jnp.zeros((T + 1, B), jnp.bool_),
        logits=jnp.zeros((T + 1, B, A), jnp.float32),
        core_state=(),
    )


def test_data_parallel_learn_matches_single_device():
    """dp-sharded update == single-device update (the DDP contract)."""
    args = ImpalaArguments(
        use_lstm=False, hidden_size=32, rollout_length=5, batch_size=8, max_timesteps=0
    )
    agent = ImpalaAgent(args, obs_shape=(8,), num_actions=4, obs_dtype=jnp.float32)
    learn = make_impala_learn_fn(agent.model, agent.optimizer, args)
    traj = _tiny_traj(jax.random.PRNGKey(0), B=8)

    # single device
    ref_state, ref_metrics = jax.jit(learn)(agent.state, traj)

    mesh = make_mesh("dp=8")
    plearn = make_parallel_learn_fn(
        learn, mesh, agent.state, batch_example=traj, donate_state=False
    )
    state = plearn.shard_state(agent.state)
    sharded = plearn.shard_batch(traj)
    dp_state, dp_metrics = plearn(state, sharded)

    for a, b in zip(
        jax.tree_util.tree_leaves(ref_state.params),
        jax.tree_util.tree_leaves(dp_state.params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(
        float(ref_metrics["total_loss"]), float(dp_metrics["total_loss"]), rtol=1e-5
    )


def test_fsdp_tp_mesh_runs_lstm_model():
    """Full IMPALA step with LSTM on dp=2,fsdp=2,tp=2; params really shard."""
    args = ImpalaArguments(
        use_lstm=True, hidden_size=64, rollout_length=3, batch_size=8, max_timesteps=0
    )
    agent = ImpalaAgent(args, obs_shape=(16,), num_actions=4, obs_dtype=jnp.float32)
    learn = make_impala_learn_fn(agent.model, agent.optimizer, args)
    B = 8
    core = agent.initial_state(B)
    traj = Trajectory(
        obs=jnp.zeros((4, B, 16), jnp.float32),
        action=jnp.zeros((4, B), jnp.int32),
        reward=jnp.zeros((4, B), jnp.float32),
        done=jnp.zeros((4, B), jnp.bool_),
        logits=jnp.zeros((4, B, 4), jnp.float32),
        core_state=core,
    )
    mesh = make_mesh("dp=2,fsdp=2,tp=2")
    plearn = make_parallel_learn_fn(learn, mesh, agent.state, batch_example=traj)
    state = plearn.shard_state(agent.state)
    state, metrics = plearn(state, plearn.shard_batch(traj))
    assert int(state.step) == 1
    assert np.isfinite(float(metrics["total_loss"]))
    specs = {
        leaf.sharding.spec
        for leaf in jax.tree_util.tree_leaves(state.params)
        if hasattr(leaf, "sharding")
    }
    assert any(
        s != jax.sharding.PartitionSpec() for s in specs
    ), "expected at least one fsdp/tp-sharded param"


def test_batch_sharding_tree_core_state_dim0():
    mesh = make_mesh("dp=8")
    B = 8
    traj = Trajectory(
        obs=jnp.zeros((3, B, 4)),
        action=jnp.zeros((3, B), jnp.int32),
        reward=jnp.zeros((3, B)),
        done=jnp.zeros((3, B), jnp.bool_),
        logits=jnp.zeros((3, B, 2)),
        core_state=(jnp.zeros((B, 16)),),
    )
    tree = batch_sharding_tree(traj, mesh)
    assert tree.obs.spec == jax.sharding.PartitionSpec(None, ("dp", "fsdp"))
    assert tree.core_state[0].spec == jax.sharding.PartitionSpec(("dp", "fsdp"))


def test_agent_enable_mesh_matches_unsharded():
    """agent.enable_mesh (the --mesh-shape path) == plain agent.learn."""
    args = ImpalaArguments(
        use_lstm=False, hidden_size=32, rollout_length=5, batch_size=8,
        max_timesteps=0,
    )
    traj = _tiny_traj(jax.random.PRNGKey(3), B=8)
    plain = ImpalaAgent(args, obs_shape=(8,), num_actions=4, obs_dtype=jnp.float32)
    meshed = ImpalaAgent(args, obs_shape=(8,), num_actions=4, obs_dtype=jnp.float32)
    meshed.enable_mesh("dp=4,fsdp=2")
    m_plain = plain.learn(traj)
    m_mesh = meshed.learn(traj)
    assert abs(m_plain["total_loss"] - m_mesh["total_loss"]) < 1e-4
    for a, b in zip(
        jax.tree_util.tree_leaves(plain.state.params),
        jax.tree_util.tree_leaves(meshed.state.params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_fused_device_loop_dp_mesh():
    """Anakin-style fused loop: env lanes sharded over dp, params
    replicated, gradients psum-ed inside the fused step; the env-frames
    counter sees all shards."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from scalerl_tpu.agents.impala import ImpalaAgent, make_impala_learn_fn
    from scalerl_tpu.config import ImpalaArguments
    from scalerl_tpu.envs import make_jax_vec_env
    from scalerl_tpu.parallel import make_mesh
    from scalerl_tpu.runtime.device_loop import DeviceActorLearnerLoop

    mesh = make_mesh("dp=8")
    T, B = 4, 16
    args = ImpalaArguments(
        use_lstm=False, hidden_size=32, rollout_length=T, batch_size=B,
        max_timesteps=0,
    )
    venv = make_jax_vec_env("CartPole-v1", num_envs=B)
    agent = ImpalaAgent(args, obs_shape=(4,), num_actions=2, obs_dtype=jnp.float32)
    learn = make_impala_learn_fn(agent.model, agent.optimizer, args, grad_axis="dp")
    loop = DeviceActorLearnerLoop(
        agent.model, venv, learn, T, iters_per_call=2, mesh=mesh
    )
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    carry = loop.init_carry(k1)
    state, carry, m = loop.train_chunk(agent.state, carry, k2)
    assert int(state.step) == 2
    assert int(state.env_frames) == 2 * T * B  # all shards counted
    assert np.isfinite(float(m["total_loss"]))
    state, carry, m = loop.train_chunk(state, carry, k3)
    assert int(state.step) == 4
    assert np.isfinite(float(m["grad_norm"]))
    # divisibility is enforced up front
    import pytest

    bad = make_jax_vec_env("CartPole-v1", num_envs=12)
    with pytest.raises(ValueError, match="divide"):
        DeviceActorLearnerLoop(agent.model, bad, learn, T, mesh=mesh)

    # a learn_fn built WITHOUT grad_axis must be rejected, not silently
    # train each shard on its own gradients
    unsynced = make_impala_learn_fn(agent.model, agent.optimizer, args)
    loop_bad = DeviceActorLearnerLoop(
        agent.model, venv, unsynced, T, iters_per_call=1, mesh=mesh
    )
    carry2 = loop_bad.init_carry(jax.random.PRNGKey(7))
    with pytest.raises(ValueError, match="grad_axis"):
        loop_bad.train_chunk(agent.state, carry2, jax.random.PRNGKey(8))


def test_grad_axis_psum_matches_single_device():
    """dp=N at global batch B must produce numerically the same update as a
    single device at batch B (grad psum == global-sum gradients)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from scalerl_tpu.agents.impala import ImpalaAgent, make_impala_learn_fn
    from scalerl_tpu.config import ImpalaArguments
    from scalerl_tpu.data.trajectory import Trajectory
    from scalerl_tpu.parallel import make_mesh

    T, B = 4, 16
    args = ImpalaArguments(
        use_lstm=False, hidden_size=32, rollout_length=T, batch_size=B,
        max_timesteps=0,
    )
    agent = ImpalaAgent(args, obs_shape=(4,), num_actions=2, obs_dtype=jnp.float32)
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 4)
    traj = Trajectory(
        obs=jax.random.normal(ks[0], (T + 1, B, 4)),
        action=jax.random.randint(ks[1], (T + 1, B), 0, 2),
        reward=jax.random.normal(ks[2], (T + 1, B)),
        done=jax.random.bernoulli(ks[3], 0.1, (T + 1, B)),
        logits=jnp.zeros((T + 1, B, 2)),
        core_state=(),
    )

    plain = make_impala_learn_fn(agent.model, agent.optimizer, args)
    state_single, m_single = jax.jit(plain)(agent.state, traj)

    mesh = make_mesh("dp=8")
    synced = make_impala_learn_fn(agent.model, agent.optimizer, args, grad_axis="dp")
    state_spec = jax.tree_util.tree_map(lambda x: P(), agent.state)
    traj_spec = jax.tree_util.tree_map(
        lambda x: P(None, "dp", *([None] * (x.ndim - 2))), traj
    )
    fn = shard_map(
        synced,
        mesh=mesh,
        in_specs=(state_spec, traj_spec),
        out_specs=(state_spec, P()),
        check_vma=False,
    )
    state_sharded, m_sharded = jax.jit(fn)(agent.state, traj)

    for a, b in zip(
        jax.tree_util.tree_leaves(state_single.params),
        jax.tree_util.tree_leaves(state_sharded.params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6)

    # logged metrics match too: sum-convention losses are psum-ed across
    # shards (each shard sums over B/n lanes), true means pmean-ed — so a
    # dp=8 loss curve is directly comparable to the single-device run
    for k in ("total_loss", "pg_loss", "baseline_loss", "entropy_loss",
              "mean_value", "mean_reward"):
        np.testing.assert_allclose(
            float(m_sharded[k]), float(m_single[k]), rtol=1e-4,
            err_msg=f"metric {k} diverges between dp=8 and single device",
        )
