"""The dp×mp sharded big-model learner plane (ISSUE 7 tentpole).

Covers, on the 8-virtual-CPU-device mesh of conftest:

- the ``mp`` mesh axis + ``mesh_spec_from_args`` resolution
  (``dp_size``/``mp_size`` -> ``"dp=D,mp=M"``);
- the logical rule table (``parallel/logical.py``): heads/mlp/vocab/expert
  dims shard over ``mp``, non-divisible dims degrade to replication, the
  optimizer moments inherit the param layout through trailing-path
  matching, and ``make_shard_and_gather_fns`` round-trips leaves;
- sharded-vs-unsharded PARITY: an IMPALA learn step on the transformer and
  MoE policies over ``dp=4,mp=2`` matches the single-device update at the
  same global batch (loss / grad-norm / params within float tolerance),
  step after step — the acceptance criterion of the sharded plane;
- sharded checkpoint save -> restore -> resume (riding the sha256
  manifests) preserves values AND layouts;
- the trainer wiring: ``ImpalaArguments(policy_arch="transformer",
  mp_size=2)`` trains end-to-end through ``HostActorLearnerTrainer`` with
  the mesh resolved from the args alone;
- bf16 params / fp32 optimizer state (``fp32_optimizer_state``).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from scalerl_tpu.agents.impala import ImpalaAgent
from scalerl_tpu.config import ImpalaArguments
from scalerl_tpu.data.trajectory import Trajectory
from scalerl_tpu.parallel import (
    make_mesh,
    make_shard_and_gather_fns,
    mesh_spec_from_args,
    mp_param_sharding,
)
from scalerl_tpu.parallel.logical import (
    logical_to_spec,
    mp_param_spec,
    update_sharding_counts,
    with_update_axis,
)
from scalerl_tpu.parallel.sharding import holds_axis


def _impala_args(**kw):
    base = dict(
        rollout_length=6, batch_size=8, use_lstm=False, max_timesteps=0,
        num_actors=2, num_buffers=4, logger_backend="none",
        telemetry_interval_s=0.0,
    )
    base.update(kw)
    return ImpalaArguments(**base)


def _transformer_args(**kw):
    return _impala_args(
        policy_arch="transformer", d_model=32, n_heads=2, n_layers=2, **kw
    )


def _make_agent(args, key=0):
    return ImpalaAgent(
        args, obs_shape=(4,), num_actions=2, obs_dtype=jnp.float32,
        key=jax.random.PRNGKey(key),
    )


def _traj(T1=7, B=8, obs_dim=4, num_actions=2, seed=1):
    ks = [jax.random.PRNGKey(seed + i) for i in range(4)]
    return Trajectory(
        obs=jax.random.normal(ks[0], (T1, B, obs_dim)),
        action=jax.random.randint(ks[1], (T1, B), 0, num_actions),
        reward=jax.random.normal(ks[2], (T1, B)),
        done=jnp.zeros((T1, B), bool),
        logits=jax.random.normal(ks[3], (T1, B, num_actions)),
        core_state=(),
    )


# ---------------------------------------------------------------------------
# mesh + spec resolution


def test_mesh_carries_mp_axis():
    mesh = make_mesh("dp=4,mp=2")
    assert mesh.shape["dp"] == 4 and mesh.shape["mp"] == 2
    assert mesh.devices.size == 8


def test_mesh_spec_from_args_resolution():
    assert mesh_spec_from_args(_impala_args()) is None
    assert mesh_spec_from_args(_impala_args(mp_size=2), n_devices=8) == "dp=4,mp=2"
    assert (
        mesh_spec_from_args(_impala_args(mp_size=2, dp_size=2)) == "dp=2,mp=2"
    )
    assert mesh_spec_from_args(_impala_args(dp_size=8)) == "dp=8"
    # explicit mesh_shape wins over the knobs
    assert (
        mesh_spec_from_args(_impala_args(mesh_shape="dp=8", mp_size=2)) == "dp=8"
    )
    with pytest.raises(ValueError):
        mesh_spec_from_args(_impala_args(mp_size=3), n_devices=8)


# ---------------------------------------------------------------------------
# logical rules


def test_logical_rules_shard_heads_mlp_vocab_over_mp():
    mesh = make_mesh("dp=4,mp=2")

    def spec_of(names, shape):
        path = tuple(type("K", (), {"key": n})() for n in names)
        return mp_param_spec(path, jnp.zeros(shape), mesh)

    assert spec_of(("block_0", "qkv", "kernel"), (32, 96)) == P(None, "mp")
    assert spec_of(("block_0", "proj", "kernel"), (32, 32)) == P("mp", None)
    assert spec_of(("block_0", "mlp_in", "kernel"), (32, 128)) == P(None, "mp")
    assert spec_of(("block_0", "mlp_out", "kernel"), (128, 32)) == P("mp", None)
    assert spec_of(("policy_head", "kernel"), (32, 4)) == P(None, "mp")
    # MoE expert banks: leading expert dim over mp
    assert spec_of(("moe", "w_in"), (4, 32, 64)) == P("mp", None, None)
    # unmatched leaves replicate
    assert spec_of(("obs_embed", "kernel"), (4, 32)) == P()
    # non-divisible dims degrade to replication instead of erroring
    assert spec_of(("policy_head", "kernel"), (32, 3)) == P(None, None)


def test_logical_to_spec_never_double_maps_an_axis():
    mesh = make_mesh("dp=4,mp=2")
    spec = logical_to_spec(("experts", "mlp", "heads"), (4, 8, 8), mesh)
    named = [s for s in spec if s is not None]
    assert named.count("mp") == 1


@pytest.mark.parametrize("dp", [1, 2])
def test_opt_state_moments_inherit_param_layout(dp):
    """The moments take their parameter's ``mp`` layout and, where the
    update is sharded over a ``dp`` of extent 2, ``dp`` on the largest
    dimension it divides and ``mp`` does not hold; at extent 1 their specs
    are the parameters' own.  Parameters never take it, and a leaf no
    dimension of which ``dp`` divides stays replicated and is counted."""
    args = _transformer_args()
    agent = _make_agent(args)
    mesh = make_mesh(f"dp={dp},mp=2", jax.devices()[: 2 * dp])
    sh = mp_param_sharding(agent.state, mesh, update_axis="dp")
    flat = {
        jax.tree_util.keystr(path): s
        for path, s in jax.tree_util.tree_flatten_with_path(sh)[0]
    }
    qkv_param = [k for k in flat if "qkv']['kernel" in k and "opt_state" not in k]
    qkv_moment = [k for k in flat if "qkv']['kernel" in k and "opt_state" in k]
    assert qkv_param and len(qkv_moment) == 2 * len(qkv_param)
    assert all(flat[k].spec == P(None, "mp") for k in qkv_param)
    held = "dp" if dp > 1 else None
    assert all(flat[k].spec == P(held, "mp") for k in qkv_moment)
    plain = mp_param_sharding(agent.state, mesh)
    if dp == 1:
        assert jax.tree_util.tree_leaves(sh) == jax.tree_util.tree_leaves(plain)
        return
    # everything outside the optimiser's state is laid out as without it
    for k, s in flat.items():
        if "opt_state" not in k:
            assert not holds_axis(s, "dp"), k
    # [32, 32] under P("mp", None): the free dimension; [4, 32]: the larger
    (proj,) = {flat[k].spec for k in flat if "proj']['kernel" in k and "opt_state" in k}
    assert proj == P("mp", "dp")
    (embed,) = {flat[k].spec for k in flat if "obs_embed']['kernel" in k and "opt_state" in k}
    assert embed == P(None, "dp")
    counts = update_sharding_counts(agent.state.opt_state, sh.opt_state, "dp")
    moments = jax.tree_util.tree_leaves(agent.state.opt_state)
    undivided = [x for x in moments if x.ndim == 0 or all(d % 2 for d in x.shape)]
    assert undivided  # the chain's ``count``, odd heads
    # (a vector that ``mp`` holds has no dimension left for ``dp`` either)
    assert counts["leaves_replicated"] >= len(undivided)
    assert counts["leaves_sharded"] + counts["leaves_replicated"] == len(moments)
    assert counts["axis"] == "dp" and counts["extent"] == 2
    assert counts["params_at_rest"] == "gathered"
    whole = sum(
        int(np.prod(s.shard_shape(x.shape))) * x.dtype.itemsize
        for x, s in zip(moments, jax.tree_util.tree_leaves(plain.opt_state))
    )
    assert counts["moment_bytes_per_device_before"] == whole
    assert whole / 2 < counts["moment_bytes_per_device_after"] < whole


@pytest.mark.parametrize(
    "spec, shape, axes, want",
    [
        (P(None, "mp"), (1280, 3840), {"dp": 2, "mp": 2}, P("dp", "mp")),
        (P(None, None), (1280, 50257), {"dp": 2, "mp": 2}, P("dp", None)),
        (P(), (50257, 1280), {"dp": 2, "mp": 2}, P(None, "dp")),
        (P("mp"), (3840,), {"dp": 2, "mp": 2}, P("mp")),  # no dimension left
        (P(), (), {"dp": 2, "mp": 2}, P()),
        (P(), (7, 3), {"dp": 2, "mp": 2}, P()),
        (P(), (6, 8), {"dp": 4}, P(None, "dp")),  # 4 does not divide 6
        (P("mp", None, None), (4, 32, 64), {"dp": 2, "mp": 2}, P("mp", None, "dp")),
        (P(None, "mp"), (32, 96), {"dp": 1, "mp": 2}, P(None, "mp")),
    ],
)
def test_update_axis_takes_the_largest_free_dimension_it_divides(spec, shape, axes, want):
    from types import SimpleNamespace

    assert with_update_axis(spec, shape, SimpleNamespace(shape=axes), "dp") == want


def test_make_shard_and_gather_fns_roundtrip():
    mesh = make_mesh("dp=4,mp=2")
    tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
    sh = jax.tree_util.tree_map(
        lambda _: jax.sharding.NamedSharding(mesh, P(None, "mp")), tree
    )
    shard_fns, gather_fns = make_shard_and_gather_fns(sh)
    placed = shard_fns["w"](tree["w"])
    assert placed.sharding.spec == P(None, "mp")
    back = gather_fns["w"](placed)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(tree["w"]))


# ---------------------------------------------------------------------------
# parity: the sharded step IS the unsharded step


def _assert_parity(plain, meshed, traj, steps=3, atol=5e-5):
    for _ in range(steps):
        mp_ = plain.learn(traj)
        mm = meshed.learn(traj)
        assert abs(mp_["total_loss"] - mm["total_loss"]) < 1e-4, (
            mp_["total_loss"], mm["total_loss"],
        )
        assert abs(mp_["grad_norm"] - mm["grad_norm"]) < 1e-4
    for a, b in zip(
        jax.tree_util.tree_leaves(plain.state.params),
        jax.tree_util.tree_leaves(meshed.state.params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=atol)


def test_transformer_sharded_matches_unsharded():
    args = _transformer_args()
    plain = _make_agent(args)
    meshed = _make_agent(args)
    meshed.enable_mesh("dp=4,mp=2")
    # the layout is real: some param leaves actually shard over mp
    n_mp = sum(
        1
        for leaf in jax.tree_util.tree_leaves(meshed.state.params)
        if any(s == "mp" for s in leaf.sharding.spec if s is not None)
    )
    assert n_mp >= 4
    _assert_parity(plain, meshed, _traj())


def test_moe_sharded_matches_unsharded():
    args = _impala_args(
        policy_arch="moe", d_model=32, moe_experts=4, moe_hidden=64
    )
    plain = _make_agent(args)
    meshed = _make_agent(args)
    meshed.enable_mesh("dp=4,mp=2")
    n_mp = sum(
        1
        for leaf in jax.tree_util.tree_leaves(meshed.state.params)
        if any(s == "mp" for s in leaf.sharding.spec if s is not None)
    )
    assert n_mp >= 2  # w_in/w_out expert banks (+ moments)
    _assert_parity(plain, meshed, _traj(), atol=1e-4)


def test_mp_mesh_without_rules_is_rejected():
    agent = _make_agent(_impala_args(hidden_size=32))  # plain MLP policy
    with pytest.raises(ValueError, match="model-parallel"):
        agent.enable_mesh("dp=4,mp=2")


# ---------------------------------------------------------------------------
# the token learner's weight update, sharded over dp (ISSUE 48)


def _token_agent(kind):
    from tests import tiny_token_learner as tiny

    if kind == "gpt2":
        return tiny.agent()
    return tiny.program_agent(*{"routed": tiny.ROUTED, "bf16": ("--bf16-params", "true")}[kind])


def _replicated_update(agent, mesh):
    """The parent's program for ``agent`` on ``mesh``: the learn fn without
    the hook, the whole state replicated over ``dp``."""
    from scalerl_tpu.parallel import make_parallel_learn_fn

    return make_parallel_learn_fn(
        agent.make_learn_fn(), mesh, agent.state, batch_time_major=False,
        param_specs=mp_param_sharding(agent.state, mesh),
    )


def _dp_specs(tree):
    return [holds_axis(x.sharding, "dp") for x in jax.tree_util.tree_leaves(tree)]


@pytest.mark.parametrize("kind", ["gpt2", "routed", "bf16"])
@pytest.mark.parametrize("spec", ["dp=2,mp=2", "dp=4"])
def test_update_sharded_over_dp_equals_the_replicated_update(spec, kind):
    """One packed learn step (and a second, from moments that have moved,
    where the weights are float32) with the weight update sharded over
    ``dp`` against the same step with the parent's layout on the same mesh:
    parameters, both moments, the counters and every metric, leaf for leaf.
    The two differ in which replica adds which rows and in nothing else, so
    float32 leaves agree to a reordered two-term sum (the comparison below
    says what that leaves open)."""
    from tests.tiny_token_learner import packed_batch

    mesh = make_mesh(spec, jax.devices()[:4])
    sharded, plain = _token_agent(kind), _token_agent(kind)
    sharded.enable_mesh(mesh)
    want_fn = _replicated_update(plain, mesh)
    want = want_fn.shard_state(plain.state)
    # the layout is real: moments hold dp, parameters and the reference do not
    assert sum(_dp_specs(sharded.state.opt_state)) >= 10
    assert not any(_dp_specs((sharded.state.params, sharded.state.ref_params)))
    assert not any(_dp_specs(want))
    batch = packed_batch()
    # (a second step of bfloat16 weights starts an ulp apart on a few of
    # them and its metrics read that, not the layout: one step there)
    steps = 1 if kind == "bf16" else 2
    for _ in range(steps):
        got_metrics = sharded.learn_device(dict(batch))
        want, want_metrics = want_fn(want, want_fn.shard_batch(dict(batch)))
    assert set(got_metrics) == set(want_metrics) and "grad_norm" in got_metrics
    # (bfloat16 activations: the two programs fuse differently on the CPU
    # and round differently, a part in a thousand of a metric)
    rtol, atol = (2e-3, 2e-3) if kind == "bf16" else (2e-5, 1e-6)
    for name in want_metrics:
        np.testing.assert_allclose(
            np.asarray(got_metrics[name], np.float32), np.asarray(want_metrics[name], np.float32),
            rtol=rtol, atol=atol, err_msg=name,
        )
    assert int(sharded.state.step) == int(want.step) == steps
    assert int(sharded.state.tokens_seen) == int(want.tokens_seen)
    # A leaf agrees to a rounding but for a handful of elements whose
    # gradient sits within a rounding of zero: Adam's first steps are
    # ``lr * g / (|g| + eps)``, so there the reordered sum may move a weight
    # by up to ``lr`` a step.  bfloat16 gradients keep 8 bits and XLA:CPU
    # fuses the two programs' backward passes differently, so a small
    # gradient (a norm's scale) reads a few roundings apart on a quarter of
    # its elements: that case holds the dtypes, the layout and the
    # ``fp32_optimizer_state`` path leaf by leaf, and each leaf as a whole
    # to a fiftieth of its norm.
    loose = 2 * steps * sharded.args.learning_rate
    got_leaves = jax.tree_util.tree_flatten_with_path(sharded.state)[0]
    for (path, g), w in zip(got_leaves, jax.tree_util.tree_leaves(want), strict=True):
        name = jax.tree_util.keystr(path)
        assert g.dtype == w.dtype and g.shape == w.shape, name
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        gap = np.abs(g - w)
        if kind == "bf16":
            assert np.linalg.norm(gap) <= 0.02 * np.linalg.norm(w) + 1e-6, name
            continue
        off = gap > 2e-6 + 1e-5 * np.abs(w)
        assert off.mean() <= 1e-3 and gap.max(initial=0.0) <= loose, (
            name, int(off.sum()), float(gap.max(initial=0.0)),
        )
    # after the step the layouts are the ones it started from
    assert sum(_dp_specs(sharded.state.opt_state)) >= 10
    assert not any(_dp_specs((sharded.state.params, sharded.state.ref_params)))


@pytest.mark.parametrize("spec", ["dp=2,mp=2", "dp=4"])
def test_a_refused_sharded_update_returns_its_input_state_on_every_shard(spec, monkeypatch):
    """A NaN planted in ONE element of one weight gradient: one replica's
    shard of one leaf sees it, the norm's scalar reduction tells every
    shard, and each keeps its rows of the input state bit for bit."""
    from tests.tiny_token_learner import agent, packed_batch

    real = jax.value_and_grad

    def planted(fn, **kw):
        def run(*a, **k):
            out, grads = real(fn, **kw)(*a, **k)
            leaves, treedef = jax.tree_util.tree_flatten(grads)
            wide = max(range(len(leaves)), key=lambda i: leaves[i].size)
            leaves[wide] = leaves[wide].at[(0,) * leaves[wide].ndim].set(jnp.nan)
            return out, treedef.unflatten(leaves)

        return run

    learner = agent()
    learner.enable_mesh(make_mesh(spec, jax.devices()[:4]))
    shards = lambda tree: [  # noqa: E731 - every device's rows of every leaf
        (s.device.id, np.array(s.data))
        for x in jax.tree_util.tree_leaves(tree) for s in x.addressable_shards
    ]
    before = shards(learner.state)
    monkeypatch.setattr(jax, "value_and_grad", planted)
    metrics = learner.learn(packed_batch())
    monkeypatch.undo()
    assert np.isfinite(metrics["total_loss"]) and np.isnan(metrics["grad_norm"])
    assert metrics["nonfinite_grads"] == metrics["skipped_steps"] == 1.0
    after = shards(learner.state)
    assert len(after) == len(before)
    for (dev_a, a), (dev_b, b) in zip(after, before):
        assert dev_a == dev_b
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# sharded checkpoints


def _checkpoint_case(layout, key=0):
    """``(agent, batch)``: the classic transformer policy under the ``mp``
    layout alone, or the token learner with its update sharded over ``dp``
    as well (``dp``-sharded moments beside ``mp``-sharded parameters)."""
    if layout == "mp":
        agent = _make_agent(_transformer_args(), key=key)
        agent.enable_mesh("dp=4,mp=2")
        return agent, _traj()
    from tests.tiny_token_learner import packed_batch, program_agent

    agent = program_agent("--seed", str(key))
    agent.enable_mesh(make_mesh("dp=2,mp=2", jax.devices()[:4]))
    return agent, packed_batch()


@pytest.mark.parametrize("layout", ["mp", "mp+update_over_dp"])
def test_sharded_checkpoint_save_restore_resume(tmp_path, layout):
    agent, traj = _checkpoint_case(layout)
    agent.learn(traj)
    saved_step = int(agent.state.step)
    saved = jax.tree_util.tree_map(np.asarray, (agent.state.params, agent.state.opt_state))
    specs = [x.sharding.spec for x in jax.tree_util.tree_leaves(agent.state)]
    path = str(tmp_path / "ckpt")
    agent.save_checkpoint(path)

    restored, _ = _checkpoint_case(layout, key=7)  # different init
    assert any(
        np.any(np.asarray(a) != np.asarray(b))
        for a, b in zip(
            jax.tree_util.tree_leaves(saved[0]), jax.tree_util.tree_leaves(restored.state.params)
        )
    )
    restored.load_checkpoint(path)
    assert int(restored.state.step) == saved_step
    for a, b in zip(
        jax.tree_util.tree_leaves(saved),
        jax.tree_util.tree_leaves((restored.state.params, restored.state.opt_state)),
        strict=True,
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # layouts survive: the restored state is mp-sharded, not host-replicated
    n_mp = sum(
        1
        for leaf in jax.tree_util.tree_leaves(restored.state.params)
        if any(s == "mp" for s in leaf.sharding.spec if s is not None)
    )
    assert n_mp >= 4
    # ... leaf for leaf, the moments' ``dp`` halves included
    assert [x.sharding.spec for x in jax.tree_util.tree_leaves(restored.state)] == specs
    if layout != "mp":
        assert sum(_dp_specs(restored.state.opt_state)) >= 10
    # and the run RESUMES: the restored sharded state steps again
    m = restored.learn(traj)
    assert np.isfinite(m["total_loss"])
    assert int(restored.state.step) == saved_step + 1


# ---------------------------------------------------------------------------
# trainer wiring: mp_size on RLArguments alone drives the whole plane


def test_impala_transformer_mp2_trains_end_to_end(tmp_path):
    from scalerl_tpu.envs.gym_env import make_vect_envs
    from scalerl_tpu.trainer.actor_learner import HostActorLearnerTrainer

    args = _transformer_args(
        mp_size=2, dp_size=4,
        rollout_length=8, batch_size=4, num_actors=2, num_buffers=8,
        logger_frequency=10**9, work_dir=str(tmp_path),
        logger_backend="tensorboard",
    )
    agent = _make_agent(args)
    env_fns = [
        (lambda i=i: make_vect_envs(
            "CartPole-v1", num_envs=2, seed=i, async_envs=False
        ))
        for i in range(2)
    ]
    trainer = HostActorLearnerTrainer(args, agent, env_fns)
    # the trainer, not the test, resolved dp_size×mp_size into the mesh
    assert agent.mesh is not None
    assert agent.mesh.shape["mp"] == 2 and agent.mesh.shape["dp"] == 4
    result = trainer.train(total_frames=256)
    assert result["env_frames"] >= 256
    assert np.isfinite(result["total_loss"])
    assert int(agent.state.step) > 0


def test_on_policy_trainer_resolves_mesh_from_args(tmp_path):
    """PPO/A3C side of the wiring: OnPolicyTrainer construction alone
    enables the mesh declared by the args."""
    from scalerl_tpu.agents.ppo import PPOAgent
    from scalerl_tpu.config import PPOArguments
    from scalerl_tpu.envs.gym_env import make_vect_envs
    from scalerl_tpu.trainer.on_policy import OnPolicyTrainer

    args = PPOArguments(
        policy_arch="transformer", d_model=32, n_heads=2, n_layers=1,
        mp_size=2, dp_size=4, num_workers=4, num_minibatches=1,
        rollout_length=8, work_dir=str(tmp_path), logger_backend="none",
        telemetry_interval_s=0.0,
    )
    agent = PPOAgent(args, obs_shape=(4,), num_actions=2)
    envs = make_vect_envs("CartPole-v1", num_envs=4, seed=0, async_envs=False)
    trainer = OnPolicyTrainer(args, agent, envs)
    assert agent.mesh is not None and agent.mesh.shape["mp"] == 2
    if hasattr(trainer, "close"):
        trainer.close()


# ---------------------------------------------------------------------------
# bf16 params / fp32 optimizer state


@pytest.mark.slow
def test_bf16_params_with_fp32_opt_state():
    args = _transformer_args(bf16_params=True)
    agent = _make_agent(args)
    agent.enable_mesh("dp=4,mp=2")
    block_kernels = [
        leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(
            agent.state.params
        )[0]
        if "qkv" in jax.tree_util.keystr(path)
    ]
    assert block_kernels and all(
        leaf.dtype == jnp.bfloat16 for leaf in block_kernels
    )
    # optimizer moments stay fp32 (fp32_optimizer_state wrapper)
    moment_dtypes = {
        leaf.dtype
        for leaf in jax.tree_util.tree_leaves(agent.state.opt_state)
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.inexact)
    }
    assert moment_dtypes == {jnp.dtype(jnp.float32)}
    m = agent.learn(_traj())
    assert np.isfinite(m["total_loss"])
    # params stayed bf16 through the update (no silent f32 promotion)
    updated_kernels = [
        leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(
            agent.state.params
        )[0]
        if "qkv" in jax.tree_util.keystr(path)
    ]
    assert updated_kernels and all(
        leaf.dtype == jnp.bfloat16 for leaf in updated_kernels
    )
    assert int(agent.state.step) == 1
