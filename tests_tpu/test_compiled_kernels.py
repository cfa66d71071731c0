"""Compiled-TPU validation of the Pallas kernels and the fused programs.

Interpret-mode tests validate only *semantics* — tiling and VMEM legality
can still fail to compile.  Each kernel test here forces
``interpret=False`` and compares against the XLA reference implementation
on-device; the program tests run the fused loops end to end on the chip.
The per-test outcome of a run goes in ``CHANGES.md``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scalerl_tpu.ops.pallas_attention import flash_attention
from scalerl_tpu.ops.pallas_per import (
    hierarchical_sample,
    pallas_sample,
    proportional_sample,
)
from scalerl_tpu.ops.ring_attention import full_attention


def _rand(key, *shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype=dtype)


@pytest.fixture
def f32_matmuls():
    """Full-float32 matmuls on BOTH sides of a parity check.

    By default the TPU rounds float32 matmul operands to bfloat16.  A kernel
    and its XLA reference then round at different points (the kernels scale
    q before the product, the references after) and land ~1e-2 apart with
    nothing wrong in either: the first chip run of this suite measured
    8.8e-3 on the flash forward, 4.3e-2 on its backward and 1.8e-2 nats
    between cached-decode and full-forward logprobs in plain XLA.  At full
    precision the tolerances below test the kernel's tiling, masking and
    accumulation; the default-precision cases carry a bfloat16-sized bound.
    """
    with jax.default_matmul_precision("highest"):
        yield


def _mesh(spec: str, n: int):
    """A mesh over the first ``n`` devices, or a skip when the host has
    fewer (the four-chip cases run on the four-chip host only)."""
    from scalerl_tpu.parallel import make_mesh

    if jax.device_count() < n:
        pytest.skip(f"needs {n} devices, {jax.device_count()} visible")
    return make_mesh(spec, jax.devices()[:n])


@pytest.mark.usefixtures("f32_matmuls")
@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_compiled(causal):
    # TPU-legal tiles: block 128, head dim 128-lane friendly
    B, T, H, D = 2, 256, 4, 128
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = _rand(k1, B, T, H, D), _rand(k2, B, T, H, D), _rand(k3, B, T, H, D)
    out = flash_attention(q, k, v, causal=causal, interpret=False)
    ref = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3, rtol=2e-3)


@pytest.mark.usefixtures("f32_matmuls")
def test_flash_forward_compiled_ragged_tail():
    # T not a block multiple: the padding/masking path must tile legally too
    B, T, H, D = 1, 200, 2, 128
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = _rand(k1, B, T, H, D), _rand(k2, B, T, H, D), _rand(k3, B, T, H, D)
    out = flash_attention(q, k, v, causal=True, interpret=False)
    ref = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3, rtol=2e-3)


@pytest.mark.usefixtures("f32_matmuls")
@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_compiled(causal):
    B, T, H, D = 1, 256, 2, 128
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k, v = _rand(k1, B, T, H, D), _rand(k2, B, T, H, D), _rand(k3, B, T, H, D)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, interpret=False) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-3, rtol=5e-3)


def test_flash_bfloat16_compiled():
    B, T, H, D = 2, 256, 2, 128
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
    q = _rand(k1, B, T, H, D, dtype=jnp.bfloat16)
    k = _rand(k2, B, T, H, D, dtype=jnp.bfloat16)
    v = _rand(k3, B, T, H, D, dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, interpret=False)
    assert out.dtype == jnp.bfloat16
    ref = full_attention(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        causal=True,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=5e-2, rtol=5e-2
    )


def test_pallas_per_sample_compiled():
    rng = np.random.default_rng(0)
    flat_p = jnp.asarray(rng.integers(1, 17, size=4096).astype(np.float32))
    total = float(jnp.sum(flat_p))
    u = rng.uniform(size=128)
    targets = jnp.asarray((np.arange(128) + u) / 128 * total, jnp.float32)
    compiled = pallas_sample(flat_p, targets, block_size=1024, interpret=False)
    ref = hierarchical_sample(flat_p, targets, block_size=1024)
    np.testing.assert_array_equal(np.asarray(compiled), np.asarray(ref))
    # and both agree with the O(n) cumsum reference
    ref2 = proportional_sample(flat_p, targets, method="cumsum")
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ref2))
    # on this backend the default "auto" must route to the Pallas kernel
    # (the flagship Ape-X/R2D2 paths use it), and produce the same sample
    from scalerl_tpu.ops.pallas_per import resolve_sample_method

    assert resolve_sample_method("auto") == "pallas"
    auto = proportional_sample(flat_p, targets)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(ref))


def test_fused_loop_one_chunk_on_tpu():
    """The fused actor-learner program compiles and executes end to end
    on the chip (the ``impala_fused`` cell's path) — at a
    reduced batch so this stays a quick smoke, not a benchmark."""
    from scalerl_tpu.agents.impala import ImpalaAgent
    from scalerl_tpu.config import ImpalaArguments
    from scalerl_tpu.envs.jax_envs.base import JaxVecEnv
    from scalerl_tpu.envs.jax_envs.synthetic import SyntheticPixelEnv
    from scalerl_tpu.runtime.device_loop import DeviceActorLearnerLoop

    args = ImpalaArguments(
        use_lstm=False, hidden_size=512, rollout_length=20, batch_size=64,
        max_timesteps=0, compute_dtype="bfloat16", logger_backend="none",
    )
    env = SyntheticPixelEnv()
    venv = JaxVecEnv(env, num_envs=64)
    agent = ImpalaAgent(args, obs_shape=env.observation_shape,
                        num_actions=env.num_actions)
    loop = DeviceActorLearnerLoop(
        model=agent.model, venv=venv, learn_fn=agent.make_learn_fn(),
        unroll_length=20, iters_per_call=2,
    )
    carry = loop.init_carry(jax.random.PRNGKey(0))
    state, carry, m = loop.train_chunk(agent.state, carry, jax.random.PRNGKey(1))
    assert np.isfinite(float(m["total_loss"]))


def test_fused_program_stores_frames_lane_dense_at_the_cells_geometry():
    """The ``impala_fused`` cell's program as the chip's own compiler
    builds it (2048 envs, unroll 20, 5 iterations a dispatch): no uint8
    frame array with anything but the env axis in the lanes, and no frame
    batch relaid inside a loop.  ``ActorCarry.obs`` carried as
    ``[B, 84, 84, 4]`` was stored ``{3,2,1,0:T(8,128)(4,1)}``, 1.94 GB a
    57.8 MB batch, written and copied back on every environment step
    (PERF.md, PR 31); a later change to the carry must not bring that back
    unseen.  Compiled only: nothing of this size runs here."""
    from scalerl_tpu.agents.impala import ImpalaAgent
    from scalerl_tpu.config import ImpalaArguments
    from scalerl_tpu.envs import make_jax_vec_env
    from scalerl_tpu.runtime.device_loop import DeviceActorLearnerLoop
    from scalerl_tpu.utils import tiled_layout

    B, T = 2048, 20
    args = ImpalaArguments(
        env_id="SyntheticPixel-v0", use_lstm=False, hidden_size=512,
        rollout_length=T, batch_size=B, max_timesteps=0,
        compute_dtype="bfloat16", logger_backend="none",
    )
    venv = make_jax_vec_env(args.env_id, num_envs=B)
    agent = ImpalaAgent(
        args, obs_shape=venv.observation_shape, num_actions=venv.num_actions,
        obs_dtype=venv.env.observation_dtype,
    )
    loop = DeviceActorLearnerLoop(
        agent.model, venv, agent.make_learn_fn(), T, iters_per_call=5
    )
    assert loop.iter_mode == "scan"
    key = jax.random.PRNGKey(0)
    carry = jax.eval_shape(loop.init_carry, key)
    text = loop._train_many.lower(agent.state, carry, key).compile().as_text()
    frame_batch = B * int(np.prod(venv.observation_shape))
    faults = tiled_layout.lane_dense_faults(text, "u8", frame_batch, lane_dim=B)
    assert not faults, faults


def test_fused_program_copies_no_trajectory_on_tpu():
    """A fused dispatch as the chip's own compiler builds it, at a small
    env count: no loop body copies a trajectory-sized bf16 (or uint8)
    array.  With the scan's rows stacked T major-most the learner's
    ``[T, B] -> [T*B]`` merge was ``copy bf16[21,84,84,1,4,B]``, the whole
    scaled trajectory once an iteration (PERF.md, PR 43); the rows are
    now written into a buffer ``[84, 84, T+1, 4, B]`` pinned row-major.
    The dispatch then runs once: the pinned layout executes."""
    from scalerl_tpu.agents.impala import ImpalaAgent
    from scalerl_tpu.config import ImpalaArguments
    from scalerl_tpu.envs import make_jax_vec_env
    from scalerl_tpu.runtime.device_loop import DeviceActorLearnerLoop
    from scalerl_tpu.utils import tiled_layout

    B, T = 256, 20
    args = ImpalaArguments(
        env_id="SyntheticPixel-v0", use_lstm=False, hidden_size=512,
        rollout_length=T, batch_size=B, max_timesteps=0,
        compute_dtype="bfloat16", logger_backend="none",
    )
    venv = make_jax_vec_env(args.env_id, num_envs=B)
    agent = ImpalaAgent(
        args, obs_shape=venv.observation_shape, num_actions=venv.num_actions,
        obs_dtype=venv.env.observation_dtype,
    )
    loop = DeviceActorLearnerLoop(
        agent.model, venv, agent.make_learn_fn(), T, iters_per_call=2
    )
    assert loop.iter_mode == "scan"
    key = jax.random.PRNGKey(0)
    carry = loop.init_carry(key)
    text = loop._train_many.lower(agent.state, carry, key).compile().as_text()
    trajectory = (T + 1) * B * int(np.prod(venv.observation_shape))
    assert f"u8[84,84,{T + 1},4,{B}]{{4,3,2,1,0:" in text
    for dtype in ("bf16", "u8"):
        found = tiled_layout.loop_body_copies(text, dtype, trajectory)
        assert not found, found
    _state, _carry, m = loop.train_chunk(agent.state, carry, jax.random.PRNGKey(1))
    assert np.isfinite(float(m["total_loss"]))


def test_breakout_fused_chunk_on_tpu():
    """The flagship Breakout game + fused IMPALA iteration compiles and
    executes on the chip (the wall-clock-to-score path of
    examples/curves/impala.py::impala_breakout)."""
    from scalerl_tpu.agents.impala import ImpalaAgent
    from scalerl_tpu.config import ImpalaArguments
    from scalerl_tpu.envs import JaxBreakout
    from scalerl_tpu.envs.jax_envs.base import JaxVecEnv
    from scalerl_tpu.runtime.device_loop import DeviceActorLearnerLoop

    args = ImpalaArguments(
        use_lstm=False, hidden_size=256, rollout_length=20, batch_size=32,
        max_timesteps=0, logger_backend="none",
    )
    env = JaxBreakout()
    venv = JaxVecEnv(env, num_envs=32)
    agent = ImpalaAgent(args, obs_shape=env.observation_shape,
                        num_actions=env.num_actions)
    loop = DeviceActorLearnerLoop(
        model=agent.model, venv=venv, learn_fn=agent.make_learn_fn(),
        unroll_length=20, iters_per_call=2,
    )
    carry = loop.init_carry(jax.random.PRNGKey(0))
    state, carry, m = loop.train_chunk(agent.state, carry, jax.random.PRNGKey(1))
    assert np.isfinite(float(m["total_loss"]))


def test_device_r2d2_fused_iteration_on_tpu():
    """The fused R2D2 iteration (collect + sequence-replay insert +
    train_intensity learn steps + priority write-back as ONE program)
    compiles and executes on the chip."""
    from scalerl_tpu.agents.r2d2 import R2D2Agent
    from scalerl_tpu.config import R2D2Arguments
    from scalerl_tpu.envs.jax_envs.base import JaxVecEnv
    from scalerl_tpu.envs.jax_envs.recall import JaxRecall
    from scalerl_tpu.trainer.r2d2_device import DeviceR2D2Trainer

    args = R2D2Arguments(
        env_id="JaxRecall", rollout_length=8, burn_in=2, n_steps=1,
        batch_size=8, replay_capacity=64, warmup_sequences=8,
        use_lstm=True, hidden_size=64, logger_backend="none",
        logger_frequency=10**9, save_model=False,
    )
    env = JaxRecall(size=8, delay=2, num_cues=2)
    venv = JaxVecEnv(env, num_envs=8)
    agent = R2D2Agent(args, obs_shape=env.observation_shape, num_actions=2,
                      obs_dtype=jnp.uint8)
    trainer = DeviceR2D2Trainer(args, agent, venv, fused=True)
    result = trainer.train(total_frames=256)
    assert result["learn_steps"] > 0
    assert np.isfinite(result["total_loss"])
    trainer.close()


@pytest.mark.parametrize("n", [1, 4])
def test_sharded_replay_on_tpu_mesh(n):
    """Lane-sharded PER sampling under shard_map compiles and runs on the
    TPU mesh (psum/pmax weight normalization + per-shard stratified draws):
    the Pallas sample kernel (``auto`` resolves to it on TPU) inside
    shard_map, with size-1 collectives at dp=1 and real ones at dp=4."""
    from scalerl_tpu.data.sharded_replay import ShardedPrioritizedReplay

    mesh = _mesh(f"dp={n}", n)
    buf = ShardedPrioritizedReplay((8,), 16, mesh, num_envs=2 * n)
    rng = np.random.default_rng(0)
    for i in range(4):
        buf.add_with_priorities(
            {
                "obs": rng.normal(size=(2 * n, 8)).astype(np.float32),
                "next_obs": rng.normal(size=(2 * n, 8)).astype(np.float32),
                "action": rng.integers(0, 4, 2 * n).astype(np.int32),
                "reward": rng.normal(size=2 * n).astype(np.float32),
                "done": np.zeros(2 * n, bool),
            },
            rng.uniform(0.1, 2.0, 2 * n).astype(np.float32),
        )
    batch = buf.sample(2 * n, beta=0.4, key=jax.random.PRNGKey(0))
    assert np.isfinite(np.asarray(batch["weights"])).all()
    buf.update_priorities(batch["indices"], np.ones(2 * n, np.float32))


def test_transformer_flash_train_step_on_tpu():
    """One adam step through the Pallas flash-attention transformer on the
    chip — compiled blockwise attention in the BACKWARD pass too."""
    import optax

    from scalerl_tpu.models.transformer import TransformerPolicy

    model = TransformerPolicy(num_actions=4, d_model=128, num_heads=2,
                              num_layers=2, max_len=256, use_flash=True)
    obs = jax.random.normal(jax.random.PRNGKey(0), (4, 256, 16))
    params = model.init(jax.random.PRNGKey(1), obs)
    tx = optax.adam(1e-3)
    opt = tx.init(params)
    actions = jnp.zeros((4, 256), jnp.int32)

    @jax.jit
    def step(params, opt, obs):
        def loss_fn(p):
            out = model.apply(p, obs)
            logp = jax.nn.log_softmax(out.policy_logits)
            return -jnp.mean(jnp.take_along_axis(logp, actions[..., None], -1))

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt2 = tx.update(grads, opt, params)
        return optax.apply_updates(params, updates), opt2, loss

    params, opt, loss = step(params, opt, obs)
    assert np.isfinite(float(loss))


def test_vtrace_pallas_compiled():
    """Compiled (non-interpret) fused V-trace matches the scan reference
    on hardware — the Mosaic legality proof for ops/pallas_vtrace.py."""
    from scalerl_tpu.ops.pallas_vtrace import (
        vtrace_from_importance_weights_pallas,
    )
    from scalerl_tpu.ops.vtrace import vtrace_from_importance_weights

    rng = np.random.default_rng(7)
    T, B = 20, 128
    inp = dict(
        log_rhos=jnp.asarray(rng.normal(size=(T, B)) * 0.4, jnp.float32),
        discounts=jnp.asarray(0.99 * (rng.uniform(size=(T, B)) > 0.1), jnp.float32),
        rewards=jnp.asarray(rng.normal(size=(T, B)), jnp.float32),
        values=jnp.asarray(rng.normal(size=(T, B)), jnp.float32),
        bootstrap_value=jnp.asarray(rng.normal(size=(B,)), jnp.float32),
    )
    ref = vtrace_from_importance_weights(**inp)
    pal = jax.jit(
        lambda **kw: vtrace_from_importance_weights_pallas(**kw, interpret=False)
    )(**inp)
    np.testing.assert_allclose(
        np.asarray(ref.vs), np.asarray(pal.vs), atol=1e-5, rtol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(ref.pg_advantages), np.asarray(pal.pg_advantages),
        atol=1e-5, rtol=1e-5,
    )


def test_per_update_blocks_compiled():
    """Compiled fused priority/sum-tree update matches the XLA reference,
    including a same-block revisit (the aliased-writeback hazard the
    idempotent per-block kernel design exists for)."""
    from scalerl_tpu.ops.pallas_per import update_priorities_blocks

    rng = np.random.default_rng(11)
    n, bs = 4096, 512
    flat = jnp.asarray(rng.uniform(0.1, 2.0, size=n), jnp.float32)
    sums = jnp.asarray(
        np.asarray(flat).reshape(-1, bs).sum(axis=1), jnp.float32
    )
    idx = jnp.asarray([10, 600, 700, 15, 4000], jnp.int32)  # block 0 twice
    newp = jnp.asarray([5.0, 4.0, 3.0, 2.0, 1.0], jnp.float32)
    ref_p, ref_s = update_priorities_blocks(
        flat, idx, newp, block_sums=sums, block_size=bs, method="xla"
    )
    pal_p, pal_s = update_priorities_blocks(
        flat, idx, newp, block_sums=sums, block_size=bs, method="pallas",
        interpret=False,
    )
    np.testing.assert_allclose(np.asarray(ref_p), np.asarray(pal_p), atol=1e-5)
    np.testing.assert_allclose(np.asarray(ref_s), np.asarray(pal_s), atol=1e-5)


def test_anakin_superchunk_one_dispatch_on_tpu():
    """run_anakin on hardware: N chunks of the 84x84 fused loop in one
    dispatch under the armed transfer guard."""
    from scalerl_tpu.agents.impala import ImpalaAgent
    from scalerl_tpu.config import ImpalaArguments
    from scalerl_tpu.envs.jax_envs.base import JaxVecEnv
    from scalerl_tpu.envs.jax_envs.synthetic import SyntheticPixelEnv
    from scalerl_tpu.runtime.device_loop import DeviceActorLearnerLoop

    B, T = 64, 8
    args = ImpalaArguments(
        use_lstm=False, hidden_size=256, rollout_length=T, batch_size=B,
        max_timesteps=0, compute_dtype="bfloat16",
    )
    env = SyntheticPixelEnv()
    venv = JaxVecEnv(env, num_envs=B)
    agent = ImpalaAgent(
        args, obs_shape=env.observation_shape, num_actions=env.num_actions
    )
    loop = DeviceActorLearnerLoop(
        model=agent.model, venv=venv, learn_fn=agent.make_learn_fn(),
        unroll_length=T, iters_per_call=2,
    )
    key = jax.random.PRNGKey(0)
    carry = loop.init_carry(key)
    state, carry, metrics = loop.run_anakin(
        agent.state, carry, jax.random.PRNGKey(1), num_calls=3
    )
    # warm call runs under the armed guard
    state, carry, metrics = loop.run_anakin(
        state, carry, jax.random.PRNGKey(2), num_calls=3
    )
    assert metrics["chunks_done"] == 3.0
    assert np.isfinite(metrics["total_loss"])


@pytest.mark.parametrize("dp,mp", [(1, 1), (2, 2)])
def test_dp_mp_sharded_transformer_step_on_tpu(dp, mp):
    """The dp×mp sharded learner's pjit train step compiles and runs on
    the real chip topology: transformer policy with heads/mlp/vocab over
    the named ``mp`` axis, activations constrained batch-over-dp, state
    donated, bf16 params with fp32 optimizer state.  dp=1,mp=1 is the
    lowering alone (logical-rule NamedShardings + with_sharding_constraint
    + donation); dp=2,mp=2 on the four-chip host exercises the collectives."""
    from scalerl_tpu.agents.impala import ImpalaAgent
    from scalerl_tpu.config import ImpalaArguments
    from scalerl_tpu.data.trajectory import Trajectory

    mesh = _mesh(f"dp={dp},mp={mp}" if mp > 1 else f"dp={dp}", dp * mp)
    T, B = 8, 4 * dp
    args = ImpalaArguments(
        policy_arch="transformer", d_model=128, n_heads=4, n_layers=2,
        bf16_params=True, rollout_length=T, batch_size=B, use_lstm=False,
        max_timesteps=0, num_actors=1, num_buffers=2,
    )
    agent = ImpalaAgent(
        args, obs_shape=(16,), num_actions=8, obs_dtype=jnp.float32
    )
    agent.enable_mesh(mesh)
    if mp > 1:
        assert any(
            "mp" in [s for s in leaf.sharding.spec if s is not None]
            for leaf in jax.tree_util.tree_leaves(agent.state.params)
        )
    key = jax.random.PRNGKey(0)
    traj = Trajectory(
        obs=jax.random.normal(key, (T + 1, B, 16), jnp.float32),
        action=jax.random.randint(key, (T + 1, B), 0, 8, jnp.int32),
        reward=jax.random.normal(key, (T + 1, B), jnp.float32),
        done=jnp.zeros((T + 1, B), jnp.bool_),
        logits=jax.random.normal(key, (T + 1, B, 8), jnp.float32),
        core_state=(),
    )
    for _ in range(2):
        metrics = agent.learn(traj)
    assert np.isfinite(metrics["total_loss"])
    assert int(agent.state.step) == 2


def _genrl_reference():
    """``tests/genrl_reference.py``: greedy decoding by the full forward."""
    import sys
    from pathlib import Path

    tests = str(Path(__file__).resolve().parent.parent / "tests")
    if tests not in sys.path:
        sys.path.insert(0, tests)
    import genrl_reference

    return genrl_reference


@pytest.mark.usefixtures("f32_matmuls")
def test_genrl_generation_round_on_tpu():
    """Sampled generation compiled on the chip (ISSUE 10): the scan-fused
    macro-step at a TPU-shaped bucket pair, every lane busy, and the
    behaviour logprobs must match the full masked forward recomputation
    on-device (the cache-vs-full parity proof under real tiling/bf16-free
    f32 attention)."""
    from scalerl_tpu.genrl.continuous import (
        ContinuousConfig,
        ContinuousEngine,
    )
    from scalerl_tpu.models.transformer import TransformerPolicy

    ref = _genrl_reference()
    V, P, R, B = 256, 64, 64, 16
    model = TransformerPolicy(
        num_actions=V, vocab_size=V, d_model=128, num_heads=4,
        num_layers=2, max_len=P + R,
    )
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32))
    engine = ContinuousEngine(
        model, params,
        ContinuousConfig(
            vocab_size=V, max_prompt_len=P, max_new_tokens=R, lanes=B,
            page_size=16, steps_per_macro=8,
        ),
        iter_mode="scan",
    )
    rng = np.random.default_rng(0)
    prompts = rng.integers(2, V, size=(B, P)).astype(np.int32)
    lengths = rng.integers(P // 2, P + 1, size=B).astype(np.int32)
    for i in range(B):
        engine.submit(prompts[i], int(lengths[i]), tag=i)
    done = sorted(engine.run_until(B, max_macro_steps=40), key=lambda c: c.tag)
    assert [len(c.response_tokens) for c in done] == [R] * B
    # on-device parity: recompute the sampling distribution from the full
    # masked forward over the left-padded sequences
    S = P + R
    seq = ref.left_padded(prompts, lengths, P, S)
    for b, c in enumerate(done):
        seq[b, P:] = c.response_tokens
    full = ref.full_forward(engine.model, params, seq, lengths, P)
    logp_all = np.asarray(
        jax.nn.log_softmax(full.policy_logits[:, P - 1:S - 1], -1)
    )
    for b, c in enumerate(done):
        assert np.isfinite(c.behavior_logp).all()
        expect = logp_all[b, np.arange(R), c.response_tokens]
        np.testing.assert_allclose(c.behavior_logp, expect, atol=1e-3)


@pytest.mark.parametrize("precision,tol", [("highest", 2e-3), (None, 3e-2)])
@pytest.mark.parametrize(
    "B,H,D,ps,M,dtype",
    [
        (8, 4, 128, 16, 4, jnp.float32),
        # the chip_smoke shape: 64 lanes, 8 heads of 32, 8-token pages
        (64, 8, 32, 8, 32, jnp.float32),
        (64, 8, 32, 8, 32, jnp.bfloat16),
        # the benchmark's rollout cell: gpt2-medium, 16 lanes, 2049 pages
        (16, 16, 64, 8, 128, jnp.float32),
        # the OLMoE rollout cell: 32 lanes, 16 heads of 128, 4097 pages
        # (page rows 2048 wide: twice gpt2-medium's block)
        (32, 16, 128, 8, 128, jnp.float32),
    ],
)
def test_paged_decode_attention_compiled(B, H, D, ps, M, dtype, precision, tol):
    """The continuous-batching decode kernel (ISSUE 11) compiled on the
    chip through its dense entry, the engine's own: scalar-prefetch
    page-table indexing + online softmax over blocks of whole lane-dense
    ``[page, H*D]`` pages, pinned to the XLA gather reference on-device
    across a fragmented table with a partially-filled last page.  The 4-D
    entry (tests hold such pools) must give the same bits."""
    from scalerl_tpu.ops.pallas_paged_attention import (
        paged_attention_reference,
        paged_decode_attention,
    )

    N = B * M + 1
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
    q = _rand(k1, B, 1, H, D, dtype=dtype)
    k_pages = _rand(k2, N, ps, H * D, dtype=dtype)
    v_pages = _rand(k3, N, ps, H * D, dtype=dtype)
    rng = np.random.default_rng(7)
    # fragmented layout: every lane owns a random disjoint page set
    perm = rng.permutation(np.arange(1, N))[: B * M].reshape(B, M)
    table = jnp.asarray(perm, jnp.int32)
    lengths = jnp.asarray(rng.integers(1, M * ps + 1, size=B), jnp.int32)
    # precision=None is what the engine runs (see the f32_matmuls fixture)
    with jax.default_matmul_precision(precision or "default"):
        out = paged_decode_attention(
            q, k_pages, v_pages, table, lengths, interpret=False
        )
        ref = paged_attention_reference(q, k_pages, v_pages, table, lengths)
        four_d = paged_decode_attention(
            q, k_pages.reshape(N, ps, H, D), v_pages.reshape(N, ps, H, D),
            table, lengths, interpret=False,
        )
    if dtype == jnp.bfloat16:
        tol = 3e-2  # one bfloat16 rounding of the output
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=tol, rtol=tol,
    )
    np.testing.assert_array_equal(np.asarray(four_d), np.asarray(out))


@pytest.mark.parametrize(
    "B,H,D",
    [(16, 16, 64), (32, 16, 128)],
    ids=["gpt2m_group_rollout", "olmoe_group_rollout"],
)
def test_paged_decode_attention_ragged_at_the_cells_geometry(B, H, D):
    """The walk over live pages (ISSUE 26) at each rollout cell's geometry:
    2,049 / 4,097 float32 pages of 8, tables of 128 slots, and lanes whose
    lengths sit on and around the 128-token block's boundaries, dead lanes
    (length 1) between long ones.  Every slot past a lane's length holds
    another lane's page id, which must not reach the result.  The kernel
    runs at the engine's ambient precision and keeps float32 operands on
    its own; the gather reference needs ``highest`` to be an oracle."""
    from scalerl_tpu.ops.pallas_paged_attention import (
        paged_attention_reference,
        paged_decode_attention,
    )

    ps, M = 8, 128
    N = B * M + 1
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(26), 3)
    q = _rand(k1, B, 1, H, D)
    k_pages = _rand(k2, N, ps, H * D)
    v_pages = _rand(k3, N, ps, H * D)
    rng = np.random.default_rng(26)
    table = jnp.asarray(
        rng.permutation(np.arange(1, N))[: B * M].reshape(B, M), jnp.int32
    )
    mix = [1, 7, 64, 65, 511, 1023, 1024, 1, 127, 128, 129, 1, 256, 257, 640, 1]
    lengths = jnp.asarray([mix[b % len(mix)] for b in range(B)], jnp.int32)
    out = paged_decode_attention(
        q, k_pages, v_pages, table, lengths, interpret=False
    )
    with jax.default_matmul_precision("highest"):
        ref = paged_attention_reference(q, k_pages, v_pages, table, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_paged_decode_latent_ragged_at_the_cells_geometry():
    """The absorbed latent decode kernel (ISSUE 30) compiled at
    ``longcat_group_rollout``'s geometry: 128 lanes of 64 heads over rows
    of 576 in a 16,385-page pool 640 lanes wide, lanes whose lengths sit
    on and around the 128-token block's boundaries with dead lanes between
    them, twice over so that a block's buffer parity carries from a lane
    to the next in both states (interpret mode starts every scratch alike
    and cannot show a stale parity: PR 26).  Every slot past a lane's
    length holds another lane's page id, which must not reach the result;
    the pool's pad columns hold junk that the zero-padded query must not
    see either."""
    from scalerl_tpu.ops.pallas_paged_attention import (
        latent_pool_width,
        paged_decode_latent,
        paged_latent_attention_reference,
    )

    B, H, W, VW, ps, M = 128, 64, 576, 512, 8, 128
    N = B * M + 1
    k1, k2 = jax.random.split(jax.random.PRNGKey(30))
    q = _rand(k1, B, 1, H, W)
    pool = _rand(k2, N, ps, latent_pool_width(W))
    rng = np.random.default_rng(30)
    table = jnp.asarray(
        rng.permutation(np.arange(1, N))[: B * M].reshape(B, M), jnp.int32
    )
    mix = [1, 7, 64, 65, 511, 1023, 1024, 1, 127, 128, 129, 1, 256, 257, 640, 1, 385]
    lengths = jnp.asarray([mix[b % len(mix)] for b in range(B)], jnp.int32)
    scale = 192 ** -0.5
    out = paged_decode_latent(q, pool, table, lengths, VW, scale, interpret=False)
    with jax.default_matmul_precision("highest"):
        ref = paged_latent_attention_reference(q, pool, table, lengths, VW, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_paged_decode_grouped_heads_at_the_cells_geometry():
    """Grouped key/value heads (ISSUE 40) compiled at
    ``nemotron_group_rollout``'s geometry: 96 lanes, 32 query heads of 128
    over 2 key/value heads, pools ``[12289, 8, 256]`` float32, lanes whose
    lengths sit on and around the block's boundaries with dead lanes
    between them.  The block-diagonal query has 16 rows a key/value head;
    the gather reference repeats each key/value head under its query
    heads and needs ``highest`` to be an oracle."""
    from scalerl_tpu.ops.pallas_paged_attention import (
        paged_attention_reference,
        paged_decode_attention,
    )

    B, H, KV, D, ps, M = 96, 32, 2, 128, 8, 128
    N = B * M + 1
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(40), 3)
    q = _rand(k1, B, 1, H, D)
    k_pages = _rand(k2, N, ps, KV * D)
    v_pages = _rand(k3, N, ps, KV * D)
    rng = np.random.default_rng(40)
    table = jnp.asarray(
        rng.permutation(np.arange(1, N))[: B * M].reshape(B, M), jnp.int32
    )
    mix = [1, 7, 64, 65, 511, 1023, 1024, 1, 127, 128, 129, 1, 256, 257, 640, 1, 385]
    lengths = jnp.asarray([mix[b % len(mix)] for b in range(B)], jnp.int32)
    out = paged_decode_attention(
        q, k_pages, v_pages, table, lengths, interpret=False
    )
    with jax.default_matmul_precision("highest"):
        ref = paged_attention_reference(q, k_pages, v_pages, table, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    # a query head reads ITS key/value head: head 15 the first, head 16 the second
    only_first = paged_decode_attention(
        q, k_pages.at[:, :, D:].set(0.0), v_pages.at[:, :, D:].set(0.0), table, lengths,
        interpret=False,
    )
    np.testing.assert_allclose(
        np.asarray(only_first[:, :, :16]), np.asarray(out[:, :, :16]), atol=1e-5
    )
    assert float(jnp.max(jnp.abs(only_first[:, :, 16:] - out[:, :, 16:]))) > 1e-2


def _mixed_runs_table(rng, B, M):
    """Every pool page once, ``[B, M]``: runs of 1 to 37 adjacent pages in
    shuffled order, so that a lane's table holds runs of every size, runs
    cut by a block's end and single pages; the run that ends on the pool's
    last page leads lane 0."""
    runs, at = [], 1
    while at <= B * M:
        n = min(int(rng.choice([1, 1, 2, 3, 5, 8, 16, 20, 37])), B * M + 1 - at)
        runs.append(np.arange(at, at + n))
        at += n
    last = runs.pop()
    flat = np.concatenate([last] + [runs[i] for i in rng.permutation(len(runs))])
    return flat.reshape(B, M)


@pytest.mark.parametrize(
    "kernel,B,H,KV,D",
    [("decode", 16, 16, 16, 64), ("decode", 40, 8, 2, 128), ("latent", 128, 64, 1, 576)],
    ids=["gpt2m_group_rollout", "zaya_group_rollout", "longcat_group_rollout"],
)
def test_paged_decode_kernels_on_a_table_of_mixed_runs(kernel, B, H, KV, D):
    """One copy a run of adjacent pages (ISSUE 50), compiled at three
    cells' geometries on a table of mixed runs: copies of 16, 4 and 1
    pages in one kernel, from any page of the pool to any slot of a block,
    so that a copy size Mosaic refuses shows here before the benchmark
    does.  Lengths on and around the block's boundaries with dead lanes
    between; against the gather reference at ``highest``."""
    from scalerl_tpu.ops import pallas_paged_attention as ppa

    ps, M = 8, 128
    N = B * M + 1
    rng = np.random.default_rng(50)
    table = jnp.asarray(_mixed_runs_table(rng, B, M), jnp.int32)
    mix = [1, 7, 64, 65, 511, 1023, 1024, 1, 127, 128, 129, 1, 256, 257, 640, 1, 385]
    lengths = jnp.asarray([mix[b % len(mix)] for b in range(B)], jnp.int32)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(50), 3)
    if kernel == "latent":
        q = _rand(k1, B, 1, H, D)
        pool = _rand(k2, N, ps, ppa.latent_pool_width(D))
        out = ppa.paged_decode_latent(q, pool, table, lengths, 512, 192 ** -0.5, interpret=False)
        with jax.default_matmul_precision("highest"):
            ref = ppa.paged_latent_attention_reference(q, pool, table, lengths, 512, 192 ** -0.5)
    else:
        q = _rand(k1, B, 1, H, D)
        k_pages, v_pages = _rand(k2, N, ps, KV * D), _rand(k3, N, ps, KV * D)
        out = ppa.paged_decode_attention(q, k_pages, v_pages, table, lengths, interpret=False)
        with jax.default_matmul_precision("highest"):
            ref = ppa.paged_attention_reference(q, k_pages, v_pages, table, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    live = np.clip(-(-np.asarray(lengths) // ps), 1, M)
    copies = ppa.table_copies(np.asarray(table), live, ps, KV * D if kernel == "decode" else 640, 4, N)
    assert copies < live.sum() / 2  # the table really holds runs


@pytest.mark.parametrize("lanes", [8, 96])
def test_ssm_decode_update_compiled(lanes):
    """The Mamba-2 decode update (ISSUE 40; plain ``jax.numpy``, one XLA
    fusion) compiled on the chip at Nemotron-3-Nano's sizes (64 heads of
    64, state 128, 8 groups) against the recurrence written out in float64
    on the host, twice in a row so that the second call reads what the
    first wrote in place; the state's buffer is donated.  1e-4: float32
    sums of 128 products, on values of order ten."""
    from scalerl_tpu.models.transformer import ssm_decode_update

    H, P, N, G = 64, 64, 128, 8
    k = jax.random.split(jax.random.PRNGKey(lanes), 7)
    state = _rand(k[0], lanes, H, P, N)
    x = _rand(k[1], lanes, H, P)
    dt = jax.nn.softplus(_rand(k[2], lanes, H) - 3.0)
    A = -jnp.exp(jax.random.uniform(k[3], (H,), minval=0.0, maxval=2.7))
    Bm, Cm = _rand(k[4], lanes, G, N), _rand(k[5], lanes, G, N)
    D = jnp.ones((H,))

    def written_out(s):
        x64, dt64 = np.asarray(x, np.float64), np.asarray(dt, np.float64)
        Bh = np.repeat(np.asarray(Bm, np.float64), H // G, axis=1)
        Ch = np.repeat(np.asarray(Cm, np.float64), H // G, axis=1)
        s = (
            np.exp(dt64 * np.asarray(A, np.float64))[:, :, None, None] * s
            + (dt64[:, :, None] * x64)[..., None] * Bh[:, :, None, :]
        )
        return np.einsum("lhpn,lhn->lhp", s, Ch) + x64, s

    y_ref, s_ref = written_out(np.asarray(state, np.float64))
    y_ref2, s_ref2 = written_out(s_ref)
    step = jax.jit(lambda s: ssm_decode_update(s, x, dt, A, Bm, Cm, D), donate_argnums=0)
    y1, s1 = step(state + 0.0)
    np.testing.assert_allclose(np.asarray(y1), y_ref, atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), s_ref, atol=1e-5, rtol=1e-5)
    y2, s2 = step(s1)
    np.testing.assert_allclose(np.asarray(y2), y_ref2, atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(s2), s_ref2, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("lanes", [8, 96])
def test_gdn_decode_update_compiled(lanes):
    """The Gated DeltaNet decode update (ISSUE 42; the Pallas kernel of
    ``ops/pallas_gdn.py``: a block of 16 heads of a lane a step, the state
    read once and written over itself) compiled on the chip at
    Qwen3-Next's sizes (32 value heads of 128 over 16 key heads of 128)
    against the rule written out in its LITERAL order in float64 on the
    host (decay, ``S^T k``, rank-one write, ``S^T q``), twice in a row so
    that the second call reads what the first wrote in place; the state's
    buffer is donated.  1e-4: float32 sums of 128 products."""
    from scalerl_tpu.models.transformer import gdn_decode_update

    H, N, P, G = 32, 128, 128, 16
    key = jax.random.split(jax.random.PRNGKey(lanes), 6)
    unit = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    state = _rand(key[0], lanes, H, N, P)
    q = unit(_rand(key[1], lanes, G, N)) * N ** -0.5
    k = unit(_rand(key[2], lanes, G, N))
    v = _rand(key[3], lanes, H, P)
    g = -jax.nn.softplus(_rand(key[4], lanes, H))
    beta = jax.nn.sigmoid(_rand(key[5], lanes, H))

    def written_out(s):
        f64 = lambda a: np.asarray(a, np.float64)  # noqa: E731
        qh, kh = np.repeat(f64(q), H // G, axis=1), np.repeat(f64(k), H // G, axis=1)
        s = np.exp(f64(g))[:, :, None, None] * s
        d = f64(beta)[:, :, None] * (f64(v) - np.einsum("lhnp,lhn->lhp", s, kh))
        s = s + kh[..., None] * d[:, :, None, :]
        return np.einsum("lhnp,lhn->lhp", s, qh), s

    o_ref, s_ref = written_out(np.asarray(state, np.float64))
    o_ref2, s_ref2 = written_out(s_ref)
    step = jax.jit(lambda s: gdn_decode_update(s, q, k, v, g, beta), donate_argnums=0)
    assert "tpu_custom_call" in step.lower(state).compile().as_text()
    o1, s1 = step(state + 0.0)
    np.testing.assert_allclose(np.asarray(o1), o_ref, atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), s_ref, atol=1e-5, rtol=1e-5)
    o2, s2 = step(s1)
    np.testing.assert_allclose(np.asarray(o2), o_ref2, atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(s2), s_ref2, atol=1e-5, rtol=1e-5)


@pytest.mark.usefixtures("f32_matmuls")
def test_continuous_engine_macro_step_on_tpu():
    """One continuous-batching macro-step compiled on the chip: paged
    prefill into allocated pages, the fused multi-substep decode with the
    Pallas paged-attention kernel behind the attn seam, one batched read
    — and greedy parity against the full forward on-device."""
    from scalerl_tpu.genrl.continuous import (
        ContinuousConfig,
        ContinuousEngine,
    )
    from scalerl_tpu.models.transformer import TransformerPolicy

    V, P, R = 256, 64, 32
    model = TransformerPolicy(
        num_actions=V, vocab_size=V, d_model=128, num_heads=4,
        num_layers=2, max_len=2 * (P + R),
    )
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32))
    rng = np.random.default_rng(1)
    prompts = rng.integers(2, V, size=(4, P)).astype(np.int32)
    lengths = rng.integers(P // 2, P + 1, size=4).astype(np.int32)
    ref = _genrl_reference().greedy_full_forward(
        model, params, prompts, lengths, P, R
    )
    engine = ContinuousEngine(
        model, params,
        ContinuousConfig(
            vocab_size=V, max_prompt_len=P, max_new_tokens=R,
            temperature=0.0, lanes=8, page_size=16, steps_per_macro=8,
            paged_attn="pallas",
        ),
        iter_mode="scan",
    )
    for i in range(4):
        engine.submit(prompts[i], lengths[i])
    done = {
        tuple(c.prompt.tolist()): c
        for c in engine.run_until(4, max_macro_steps=30)
    }
    for i in range(4):
        c = done[tuple(prompts[i][: lengths[i]].tolist())]
        np.testing.assert_array_equal(
            c.response_tokens, ref.response_tokens[i]
        )
    assert engine._decode_traces == 1


# the second case is the chip_smoke head geometry: 8 heads of 32
@pytest.mark.parametrize(
    "precision,fwd_tol,bwd_tol", [("highest", 2e-3, 5e-3), (None, 3e-2, 1e-1)]
)
# the third is OLMoE's: 16 heads of 128 over a 1024-token packed row
@pytest.mark.parametrize(
    "B,T,H,D", [(2, 384, 2, 128), (2, 384, 8, 32), (2, 1024, 16, 128)]
)
def test_segment_flash_forward_backward_compiled(
    B, T, H, D, precision, fwd_tol, bwd_tol
):
    """ISSUE 15: the packed-learner segment flash kernel, fwd AND bwd,
    compiled on-chip — segment-blocked causal masking, skipped
    cross-segment/pad blocks, and the custom_vjp backward all tile
    legally at TPU-native blocks (128), head-major."""
    from scalerl_tpu.ops.pallas_attention import (
        segment_attention_reference,
        segment_flash_attention,
    )

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
    q, k, v = _rand(k1, B, T, H, D), _rand(k2, B, T, H, D), _rand(k3, B, T, H, D)
    # multi-segment rows with a pad tail: block-skip liveness exercises
    # cross-segment, pad-only, and boundary-straddling tiles
    seg = np.zeros((B, T), np.int32)
    seg[0, :100], seg[0, 100:260], seg[0, 260:330] = 1, 2, 3
    seg[1, :200] = 1
    seg = jnp.asarray(seg)
    # precision=None is what the packed learner runs (see f32_matmuls)
    ctx = jax.default_matmul_precision(precision or "default")
    with ctx:
        out = segment_flash_attention(q, k, v, seg, None, 128, 128, False)
        ref = segment_attention_reference(q, k, v, seg)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=fwd_tol, rtol=fwd_tol
    )

    def loss_kernel(q, k, v):
        o = segment_flash_attention(q, k, v, seg, None, 128, 128, False)
        return jnp.sum(o * o)

    def loss_ref(q, k, v):
        o = segment_attention_reference(q, k, v, seg)
        return jnp.sum(o * o)

    with ctx:
        gk = jax.jit(jax.grad(loss_kernel, argnums=(0, 1, 2)))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=bwd_tol, rtol=bwd_tol
        )



# the two learn cells' shapes (a shard of gpt2-large holds 10 heads) and the
# OLMoE learner's, with the tile left to segment_flash_tiling
@pytest.mark.parametrize(
    "B,T,H,D,dtype,fwd_tol,bwd_tol",
    [
        (2, 1024, 16, 64, jnp.float32, 2e-3, 5e-3),
        (2, 1024, 10, 64, jnp.float32, 2e-3, 5e-3),
        (1, 1024, 16, 128, jnp.bfloat16, 3e-2, 1e-1),
    ],
)
def test_segment_flash_default_tiling_compiled(
    B, T, H, D, dtype, fwd_tol, bwd_tol, f32_matmuls
):
    """ISSUE 29: a grid step covers a 512-row tile of one head and loops
    over its live blocks of the other operand; the tile comes from the shape.  Rows of
    two and three segments with a pad tail, so that a 512-row tile holds a
    segment boundary, a cross-segment block and padding."""
    from scalerl_tpu.ops.pallas_attention import (
        segment_attention_reference,
        segment_flash_attention,
        segment_flash_tiling,
    )

    tl = segment_flash_tiling(T, D, dtype)
    assert max(tl.grid_steps(B, H)) <= 64, tl
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(11), 3)
    q, k, v = (_rand(kk, B, T, H, D).astype(dtype) for kk in (k1, k2, k3))
    seg = np.zeros((B, T), np.int32)
    seg[0, :400], seg[0, 400:780], seg[0, 780:980] = 1, 2, 3
    if B > 1:
        seg[1, :520], seg[1, 520:990] = 1, 2
    seg = jnp.asarray(seg)
    f32 = lambda x: np.asarray(x.astype(jnp.float32))  # noqa: E731

    out = segment_flash_attention(q, k, v, seg, interpret=False)
    ref = segment_attention_reference(q, k, v, seg)
    np.testing.assert_allclose(f32(out), f32(ref), atol=fwd_tol, rtol=fwd_tol)
    np.testing.assert_array_equal(f32(out)[0, 980:], 0.0)

    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2)

    gk = jax.jit(jax.grad(
        loss(lambda q, k, v: segment_flash_attention(q, k, v, seg, interpret=False)),
        argnums=(0, 1, 2),
    ))(q, k, v)
    gr = jax.grad(
        loss(lambda q, k, v: segment_attention_reference(q, k, v, seg)),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(f32(a), f32(b), atol=bwd_tol, rtol=bwd_tol)


@pytest.mark.parametrize(
    "Dv,grad_tol", [(128, 1e-1), (192, 1.5e-1)], ids=["v128", "v192-padded"]
)
def test_segment_flash_latent_head_shape_compiled(Dv, grad_tol):
    """ISSUE 32: ``joyai_packed_learn``'s attention on the chip: q and k
    192 wide, v 128 (and, beside it, v padded to 192 as the model did
    before), bfloat16, 32 heads over 1,024-token packed rows, the tile left
    to ``segment_flash_tiling``; outputs and all three gradients against
    the dense oracle.  bfloat16 operands and one bf16 pass a product: the
    tolerances are the OLMoE learner's case above; with v 192 wide ``dp``
    sums one and a half times the columns, and the gradients' tolerance is
    one and a half times as wide (one element of 12.6 M read 0.133 off on
    the chip, PR 32)."""
    from scalerl_tpu.ops.pallas_attention import (
        segment_attention_reference,
        segment_flash_attention,
    )

    B, T, H, D = 2, 1024, 32, 192
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(13), 3)
    q, k = (_rand(kk, B, T, H, D).astype(jnp.bfloat16) for kk in (k1, k2))
    v = _rand(k3, B, T, H, Dv).astype(jnp.bfloat16)
    seg = np.zeros((B, T), np.int32)
    seg[0, :400], seg[0, 400:780], seg[0, 780:980] = 1, 2, 3
    seg[1, :520], seg[1, 520:990] = 1, 2
    seg = jnp.asarray(seg)
    f32 = lambda x: np.asarray(x.astype(jnp.float32))  # noqa: E731

    out = segment_flash_attention(q, k, v, seg, interpret=False)
    assert out.shape == (B, T, H, Dv)
    ref = segment_attention_reference(q, k, v, seg)
    np.testing.assert_allclose(f32(out), f32(ref), atol=3e-2, rtol=3e-2)
    np.testing.assert_array_equal(f32(out)[0, 980:], 0.0)

    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2)

    gk = jax.jit(jax.grad(
        loss(lambda q, k, v: segment_flash_attention(q, k, v, seg, interpret=False)),
        argnums=(0, 1, 2),
    ))(q, k, v)
    gr = jax.grad(
        loss(lambda q, k, v: segment_attention_reference(q, k, v, seg)),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(f32(a), f32(b), atol=grad_tol, rtol=grad_tol)


@pytest.mark.parametrize("bf16", [False, True], ids=["f32", "bf16_params"])
def test_token_learner_guard_decides_before_the_update_on_tpu(bf16):
    """ISSUE 33: the packed token learner as the learn cells run it (one
    chip's mesh, the state donated, the segment kernels), compiled by the
    chip's own compiler: no ``conditional`` and no ``copy`` of a train-state
    leaf (the post-hoc guard wrote its chosen candidate over the donated
    buffers, 4.9 GB a step at gpt2-medium; PERF.md, PR 33).  Then on the
    device: a stored logprob of -200 makes every gradient NaN under a
    finite loss, the step is refused and the donated buffers hold the input
    state bit for bit; the next step trains and leaves a finite state."""
    from scalerl_tpu.agents.token_ppo import TokenPPOAgent
    from scalerl_tpu.config import GenRLArguments
    from scalerl_tpu.genrl.rollout import pack_learner_batch
    from scalerl_tpu.parallel.train_step import tree_all_finite
    from scalerl_tpu.trainer.sequence_rl import build_genrl_model
    from scalerl_tpu.utils import tiled_layout

    V, S, n = 640, 256, 8
    args = GenRLArguments(
        vocab_size=V, d_model=384, n_layers=2, n_heads=6, prompt_len=32,
        max_new_tokens=32, learner_packing=True, learner_pack_len=S,
        bf16_params=bf16, adv_norm=False, telemetry_interval_s=0.0,
        logger_backend="none",
    )
    agent = TokenPPOAgent(args, build_genrl_model(args))
    agent.enable_mesh(_mesh("dp=1", 1))
    rng = np.random.default_rng(33)
    plens, rlens = rng.integers(8, 33, n), rng.integers(8, 33, n)
    pk = pack_learner_batch(
        [rng.integers(1, V, k).astype(np.int32) for k in plens],
        [rng.integers(1, V, k).astype(np.int32) for k in rlens],
        [np.log(rng.uniform(0.05, 0.5, k)).astype(np.float32) for k in rlens],
        [rng.normal(0, 0.1, k).astype(np.float32) for k in rlens],
        rng.uniform(0, 1, n).astype(np.float32), np.zeros(n, np.int32), pack_len=S,
    )
    batch = {k: jnp.asarray(v) for k, v in pk.fields()[0].items()}

    text = agent.lower_learn(batch).compile().as_text()
    assert "tpu_custom_call" in text  # the segment kernels, as in the cells
    leaves = jax.tree_util.tree_leaves(agent.state.params)
    faults = tiled_layout.candidate_state_faults(text, [x.shape for x in leaves])
    assert not faults, (len(faults), faults[:2])

    bits = lambda tree: [np.asarray(x).tobytes() for x in jax.tree_util.tree_leaves(tree)]  # noqa: E731
    before = bits(agent.state)
    at = tuple(np.argwhere(np.asarray(batch["mask"]) > 0)[1])
    m = agent.learn({**batch, "behavior_logp": batch["behavior_logp"].at[at].set(-200.0)})
    assert np.isfinite(m["total_loss"]) and not np.isfinite(m["grad_norm"])
    assert m["skipped_steps"] == 1.0 and m["nonfinite_grads"] == 1.0
    assert bits(agent.state) == before
    m = agent.learn(batch)
    assert m["skipped_steps"] == 0.0 and int(agent.state.step) == 1
    assert bits(agent.state.params) != before[: len(leaves)]
    assert bool(tree_all_finite(agent.state))


def test_async_collectives_reschedule_the_learn_step_and_keep_its_sums():
    """ISSUE 41, ISSUE 44: on the four-chip host ``make_parallel_learn_fn``
    compiles the ``dp=2 x mp=2`` learn program with the option table
    (asynchronous collectives, the combiner's threshold).  The reductions
    are the parent's (operands, float32, groups), run earlier: one step of
    a small packed token learner with the options and one by the parent's
    plain ``jax.jit`` call give the same loss, gradient norm and updated
    ``block_0/qkv/kernel``, and only the first text holds asynchronous
    collective fusions."""
    from scalerl_tpu.agents.token_ppo import TokenPPOAgent
    from scalerl_tpu.config import GenRLArguments
    from scalerl_tpu.genrl.rollout import pack_learner_batch
    from scalerl_tpu.parallel.sharding import replicated
    from scalerl_tpu.parallel.train_step import (
        ASYNC_COLLECTIVE_OPTIONS,
        make_parallel_learn_fn,
    )
    from scalerl_tpu.trainer.sequence_rl import build_genrl_model

    mesh = _mesh("dp=2,mp=2", 4)
    V, S, n, lr = 640, 256, 16, 1e-3
    args = GenRLArguments(
        vocab_size=V, d_model=128, n_layers=2, n_heads=4, prompt_len=32,
        max_new_tokens=32, learner_packing=True, learner_pack_len=S,
        adv_norm=False, learning_rate=lr, telemetry_interval_s=0.0,
        logger_backend="none",
    )
    agent = TokenPPOAgent(args, build_genrl_model(args))
    agent.enable_mesh(mesh)
    assert agent._learn.compile_options is ASYNC_COLLECTIVE_OPTIONS
    rng = np.random.default_rng(41)
    plens, rlens = rng.integers(8, 33, n), rng.integers(8, 33, n)
    pk = pack_learner_batch(
        [rng.integers(1, V, k).astype(np.int32) for k in plens],
        [rng.integers(1, V, k).astype(np.int32) for k in rlens],
        [np.log(rng.uniform(0.05, 0.5, k)).astype(np.float32) for k in rlens],
        [rng.normal(0, 0.1, k).astype(np.float32) for k in rlens],
        rng.uniform(0, 1, n).astype(np.float32), np.zeros(n, np.int32), pack_len=S,
    )
    fields, _ = pk.bucketed(4).fields()  # rows divide by dp
    batch = agent._shard_batch({k: jnp.asarray(v) for k, v in fields.items()})

    st_sh = agent._learn.state_sharding
    with_options = make_parallel_learn_fn(
        agent._learn_fn, mesh, agent.state, batch_time_major=False,
        param_specs=st_sh, donate_state=False,
    )
    parent = jax.jit(
        agent._learn_fn, in_shardings=(st_sh, None), out_shardings=(st_sh, replicated(mesh))
    )
    # each program is compiled once: its text is read, then it runs
    with_options, parent = (
        fn.lower(agent.state, batch).compile() for fn in (with_options, parent)
    )
    fusions = lambda c: c.as_text().count("calls=%async_collective_fusion")  # noqa: E731
    assert fusions(parent) == 0 and fusions(with_options) >= 1

    (new_a, m_a), (new_b, m_b) = with_options(agent.state, batch), parent(agent.state, batch)
    assert m_a["skipped_steps"] == 0.0 and np.isfinite(m_a["total_loss"])
    for name in ("total_loss", "grad_norm"):
        np.testing.assert_allclose(float(m_a[name]), float(m_b[name]), rtol=1e-5)
    kernel = lambda st: np.asarray(st.params["params"]["block_0"]["qkv"]["kernel"])  # noqa: E731
    old = kernel(agent.state)
    # Adam's first step moves a weight by lr x g / (|g| + 1e-8): an element
    # whose gradient is a rounding away from zero may differ by a fraction
    # of one update, never by the 2 x lr a flipped sign would cost
    np.testing.assert_allclose(kernel(new_a), kernel(new_b), rtol=0, atol=0.05 * lr)
    assert np.abs(kernel(new_a) - old).max() > 0.5 * lr


def test_engine_push_overwrites_the_snapshot_behind_a_macro_step_in_flight():
    """ISSUE 38: the engine's push is one program whose outputs take the
    retired snapshot's buffers (a donated operand).  What only the chip's
    asynchronous queue shows: a macro-step enqueued BEFORE the push and read
    AFTER it returns the old generation's tokens, though its operands'
    buffers were donated meanwhile (the runtime orders the overwrite behind
    the reader); and after the second push every snapshot leaf sits at the
    first snapshot's address."""
    from scalerl_tpu.genrl.continuous import (
        ContinuousConfig,
        ContinuousEngine,
    )
    from scalerl_tpu.models.transformer import TransformerPolicy

    V, P, R, n = 4096, 64, 32, 8
    model = TransformerPolicy(
        num_actions=V, vocab_size=V, d_model=512, num_heads=8,
        num_layers=4, max_len=2 * (P + R),
    )
    old = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32))
    new = model.init(jax.random.PRNGKey(1), jnp.zeros((1, 2), jnp.int32))
    rng = np.random.default_rng(38)
    prompts = rng.integers(2, V, size=(n, P)).astype(np.int32)
    lengths = rng.integers(P // 2, P + 1, size=n).astype(np.int32)

    def engine(params):
        return ContinuousEngine(
            model, params,
            ContinuousConfig(
                vocab_size=V, max_prompt_len=P, max_new_tokens=R,
                temperature=0.0, lanes=n, page_size=16, steps_per_macro=R,
                steps_in_flight=2, paged_attn="pallas",
            ),
            iter_mode="scan",
        )

    def decode(eng, before_read=lambda: None):
        for i in range(n):
            eng.submit(prompts[i], lengths[i], tag=i)
        assert eng.step() == []  # every response decoded whole, enqueued, unread
        before_read()
        done = sorted(eng.run_until(n, max_macro_steps=10), key=lambda c: c.tag)
        return [c.response_tokens for c in done], {c.generation for c in done}

    leaves = jax.tree_util.tree_leaves
    where = lambda tree: [x.unsafe_buffer_pointer() for x in leaves(tree)]  # noqa: E731
    expect_old, _ = decode(engine(old))  # never pushed
    expect_new, _ = decode(engine(new))
    assert any(not np.array_equal(a, b) for a, b in zip(expect_old, expect_new))

    eng = engine(old)
    first, _ = eng._snapshot_params()
    at = where(first)
    eng.push_params(jax.tree_util.tree_map(jnp.copy, old))  # builds the program
    assert eng.last_push["programs"] == 1 and eng.last_push["in_place"] is True
    assert all(x.is_deleted() for x in leaves(first))
    tokens, gens = decode(eng, before_read=lambda: eng.push_params(new))
    assert eng.last_push["in_place"] is True and gens == {1}
    for got, want in zip(tokens, expect_old):
        np.testing.assert_array_equal(got, want)
    snapshot, gen = eng._snapshot_params()
    assert gen == 2 and where(snapshot) == at
    assert not set(at) & set(where(new))
    tokens, gens = decode(eng)
    assert gens == {2}
    for got, want in zip(tokens, expect_new):
        np.testing.assert_array_equal(got, want)
    assert eng._copy_over._cache_size() == 1 and eng._decode_traces == 1
