"""The fused loop stores observations with the env axis LAST (ISSUE 31).

On the TPU a loop carry takes the default major-to-minor layout and the
minor axis lands in a tile's 128 lanes; ``[B, 84, 84, 4]`` uint8 pads a
frame batch 32 times there.  The storage is an internal of
``runtime/device_loop.py``: the model, ``learn_fn`` and every caller of
``Trajectory`` still see ``[.., B, *obs_shape]``.  These cases hold the
loop to a plain per-step Python loop over ``venv.step`` and ``model.apply``
on the same keys, and the mesh and super-chunk paths to the single-device
chunked one.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scalerl_tpu.agents.impala import ImpalaAgent, make_impala_learn_fn
from scalerl_tpu.config import ImpalaArguments
from scalerl_tpu.data.trajectory import Trajectory
from scalerl_tpu.envs import make_jax_vec_env
from scalerl_tpu.runtime.device_loop import (
    DeviceActorLearnerLoop,
    carry_env_axes,
)

T = 3

# env id, num_envs, use_lstm, env kwargs
ENVS = {
    "pixel": ("SyntheticPixel-v0", 8, False, {}),
    "vector": ("CartPole-v1", 8, False, {}),
    "catch": ("Catch-v0", 4, False, {"size": 12}),
    "lstm": ("Recall-v0", 4, True, {"size": 12, "delay": 2, "num_cues": 2}),
}


def _build(name, iters_per_call=1, mesh=None, num_envs=None):
    env_id, B, use_lstm, kwargs = ENVS[name]
    B = num_envs or B
    args = ImpalaArguments(
        env_id=env_id, use_lstm=use_lstm, hidden_size=32, rollout_length=T,
        batch_size=B, max_timesteps=0, logger_backend="none",
    )
    venv = make_jax_vec_env(env_id, num_envs=B, **kwargs)
    agent = ImpalaAgent(
        args, obs_shape=venv.observation_shape, num_actions=venv.num_actions,
        obs_dtype=venv.env.observation_dtype,
    )
    learn = make_impala_learn_fn(
        agent.model, agent.optimizer, args,
        **({"grad_axis": "dp"} if mesh is not None else {}),
    )
    loop = DeviceActorLearnerLoop(
        agent.model, venv, learn, T, iters_per_call=iters_per_call, mesh=mesh
    )
    return loop, agent, venv


def _plain_unroll(loop, venv, params, key_init, key):
    """What ``init_carry`` + ``_unroll`` compute, one step at a time in
    Python, observations never leaving the env's own ``[B, *obs_shape]``."""
    B = venv.num_envs
    model = loop.model
    env_state, obs = venv.reset(key_init)
    last_action = jnp.zeros(B, jnp.int32)
    reward = jnp.zeros(B, jnp.float32)
    done = jnp.ones(B, jnp.bool_)
    core = core0 = model.initial_state(B)
    rows = []
    for k in jax.random.split(key, T):
        out, new_core = model.apply(
            params, obs[None], last_action[None], reward[None], done[None], core
        )
        logits = out.policy_logits[0]
        k_act, k_env = jax.random.split(k)
        action = jax.random.categorical(k_act, logits, axis=-1)
        rows.append((obs, last_action, reward, done, logits))
        env_state, obs, reward, done = venv.step(env_state, action, k_env)
        last_action, core = action, new_core
    rows.append((obs, last_action, reward, done, jnp.zeros_like(rows[0][4])))
    stacked = [jnp.stack(x) for x in zip(*rows)]
    return Trajectory(*stacked, core_state=core0), obs


def _assert_same(got, want, what):
    """Equal, element for element.  A scan and a Python loop are two
    programs, and XLA:CPU contracts CartPole's float physics differently in
    them by an ulp, so float arrays get float32 rounding; frames, actions
    and flags are exact."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape and got.dtype == want.dtype, what
    if np.issubdtype(got.dtype, np.floating):
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7, err_msg=what)
    else:
        np.testing.assert_array_equal(got, want, err_msg=what)


@pytest.mark.parametrize("name", sorted(ENVS))
def test_init_carry_stores_obs_env_axis_last(name):
    loop, _agent, venv = _build(name)
    carry = loop.init_carry(jax.random.PRNGKey(0))
    B = venv.num_envs
    assert carry.obs.shape == (*venv.observation_shape, B)
    assert carry.obs.dtype == venv.env.observation_dtype
    _state, obs = venv.reset(jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.moveaxis(np.asarray(carry.obs), -1, 0), obs)
    axes = carry_env_axes(carry)
    assert axes.obs == carry.obs.ndim - 1
    for leaf, axis in zip(
        jax.tree_util.tree_leaves(carry._replace(obs=None)),
        jax.tree_util.tree_leaves(axes._replace(obs=None)),
    ):
        assert axis == 0 and leaf.shape[0] == B


@pytest.mark.parametrize("name", sorted(ENVS))
def test_unroll_equals_plain_per_step_loop(name):
    loop, agent, venv = _build(name)
    k_init, k_roll = jax.random.split(jax.random.PRNGKey(3))
    params = agent.state.params
    carry, traj = jax.jit(loop._unroll)(params, loop.init_carry(k_init), k_roll)
    # jitted like the loop, so that both sides contract their float
    # arithmetic the same way (eager CartPole differs by an ulp)
    want, last_obs = jax.jit(
        lambda p, a, b: _plain_unroll(loop, venv, p, a, b)
    )(params, k_init, k_roll)

    B = venv.num_envs
    assert traj.obs.shape == (T + 1, B, *venv.observation_shape)
    assert traj.obs.dtype == venv.env.observation_dtype
    for field in ("obs", "action", "reward", "done", "logits"):
        _assert_same(getattr(traj, field), getattr(want, field), field)
    for got, ref in zip(
        jax.tree_util.tree_leaves(traj.core_state),
        jax.tree_util.tree_leaves(want.core_state),
    ):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    # the carry that leaves holds row T's observation, stored
    _assert_same(jnp.moveaxis(carry.obs, -1, 0), last_obs, "carry.obs")
    _assert_same(traj.obs[-1], last_obs, "row T")


@pytest.mark.parametrize("name", ["pixel", "vector"])
def test_mesh_mode_shards_stored_obs_over_envs(name):
    """dp=2: the observation leaf is split on its LAST axis, every other
    carry leaf on its first, and the step runs as the unsharded loop's
    does (``tests/test_parallel.py``'s assertions)."""
    from scalerl_tpu.parallel import make_mesh

    mesh = make_mesh("dp=2", devices=jax.devices()[:2])
    B = 8
    loop, agent, venv = _build(name, iters_per_call=2, mesh=mesh, num_envs=B)
    single, agent1, _ = _build(name, iters_per_call=2, num_envs=B)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    state, carry, m = loop.train_chunk(agent.state, loop.init_carry(k1), k2)
    state1, carry1, m1 = single.train_chunk(
        agent1.state, single.init_carry(k1), k2
    )

    assert carry.obs.shape == (*venv.observation_shape, B)
    shards = carry.obs.addressable_shards
    assert len(shards) == 2
    assert {s.data.shape for s in shards} == {(*venv.observation_shape, B // 2)}
    assert all(
        s.index[-1] != slice(None) and all(i == slice(None) for i in s.index[:-1])
        for s in shards
    )
    for leaf in (carry.reward, carry.done, carry.episode_count):
        assert {s.data.shape[0] for s in leaf.addressable_shards} == {B // 2}

    # the same counts and the same metric names as the unsharded loop; the
    # values differ only by the per-shard key fold (each shard draws its own)
    assert int(state.step) == int(state1.step) == 2
    assert int(state.env_frames) == int(state1.env_frames) == 2 * T * B
    assert set(m) == set(m1)
    for k in m:
        assert np.isfinite(float(m[k])), k


@pytest.mark.parametrize("name", ["pixel", "vector", "lstm"])
def test_superchunk_matches_chunked_bitwise(name):
    """One dispatch of N chunks against N dispatches on ``run``'s key
    schedule: the same params, carry and metric stream, bit for bit."""
    loop, agent, _venv = _build(name, iters_per_call=2)
    N = 2
    fresh = lambda: jax.tree_util.tree_map(jnp.copy, agent.state)  # noqa: E731
    k_init, key = jax.random.split(jax.random.PRNGKey(5))

    state, carry = fresh(), loop.init_carry(k_init)
    stream = []
    k = key
    for _ in range(N):
        k, sub = jax.random.split(k)
        state, carry, m = loop.train_chunk(state, carry, sub)
        stream.append(m)

    s_state, s_carry, stacked = loop.train_superchunk(
        fresh(), loop.init_carry(k_init), key, N
    )
    for a, b in zip(
        jax.tree_util.tree_leaves((state.params, carry)),
        jax.tree_util.tree_leaves((s_state.params, s_carry)),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for i, m in enumerate(stream):
        for name_, v in m.items():
            np.testing.assert_array_equal(
                np.asarray(v), np.asarray(stacked[name_][i]), err_msg=name_
            )


def test_obs_storage_is_recorded_once_a_shape(monkeypatch):
    """The storage always engages, so a trace records which one ran: one
    zero-length ``fused.obs_storage`` span a traced shape."""
    from scalerl_tpu.runtime import tracing

    monkeypatch.setenv(tracing.ENV_SAMPLE, "1.0")
    tracing.reset()
    try:
        # a geometry no other test traces (the note is cached by shape)
        loop, agent, venv = _build("vector", num_envs=6)
        unroll = jax.jit(loop._unroll)
        carry = loop.init_carry(jax.random.PRNGKey(0))
        for i in range(2):
            carry, _traj = unroll(agent.state.params, carry, jax.random.PRNGKey(i))
        spans = [
            s for s in tracing.get_tracer().finished()
            if s["name"] == "fused.obs_storage"
        ]
        assert len(spans) == 1, spans
        assert spans[0]["attrs"] == {
            "stored_shape": [4, 6], "dtype": "float32", "env_axis": 1,
        }
    finally:
        monkeypatch.delenv(tracing.ENV_SAMPLE)
        tracing.reset()


# -- reading a tiled layout (scalerl_tpu/utils/tiled_layout.py) --------------

FRAMES = 2048 * 84 * 84 * 4


@pytest.mark.parametrize(
    "text, physical, minor",
    [
        # the parent's carry: 4 channels in 128 lanes, 84 columns in tiles of 8
        ("u8[2048,84,84,4]{3,2,1,0:T(8,128)(4,1)}", 2048 * 84 * 88 * 128, 4),
        # what the convolution reads: envs in the lanes, channels in a sublane
        ("u8[2048,84,84,4]{0,3,2,1:T(4,128)(4,1)S(1)}", FRAMES, 2048),
        # the stored form, and the stacked unroll
        ("u8[84,84,4,2048]{3,2,1,0:T(4,128)(4,1)}", FRAMES, 2048),
        ("u8[20,84,84,4,2048]{4,3,2,1,0:T(4,128)(4,1)}", 20 * FRAMES, 2048),
        ("bf16[2048,519]{0,1:T(8,128)(2,1)}", 520 * 2048 * 2, 2048),
        ("pred[2048]{0:T(1024)(128)(4,1)}", 2048, 2048),
        ("f32[6]{0:T(128)}", 512, 6),
        ("f32[4,8]{1,0}", 128, 8),
    ],
)
def test_tiled_layout_physical_bytes(text, physical, minor):
    from scalerl_tpu.utils import tiled_layout

    a = tiled_layout.parse_array(f"  %x.1 = {text} copy(%y)")
    assert a.physical_bytes == physical
    assert a.minor_dim == minor
    assert a.padding == physical / a.logical_bytes


def test_loop_body_copies_finds_a_relayout_a_trip():
    from scalerl_tpu.utils import tiled_layout

    text = """\
%body.1 (p: (u8[64,8,8,4])) -> (u8[64,8,8,4]) {
  %g = u8[64,8,8,4]{3,2,1,0:T(8,128)(4,1)} get-tuple-element(%p), index=0
  %copy.3 = u8[64,8,8,4]{0,3,2,1:T(4,128)(4,1)} copy(%g)
  %small = u8[8,4]{1,0:T(8,128)(4,1)} copy(%h)
  %f = f32[64,8,8,4]{3,2,1,0:T(8,128)} copy(%i)
}

ENTRY %main (a: u8[64,8,8,4]) -> u8[64,8,8,4] {
  %copy.9 = u8[64,8,8,4]{0,3,2,1:T(4,128)(4,1)} copy(%a)
  %w = (u8[64,8,8,4]{3,2,1,0}) while(%t), condition=%cond.1, body=%body.1
}
"""
    found = tiled_layout.loop_body_copies(text, "u8", 64 * 8 * 8 * 4)
    assert len(found) == 1 and found[0].startswith("%copy.3 = ")
    assert [a.dims for a in tiled_layout.arrays(text, "f32")] == [(64, 8, 8, 4)]
    # the whole rule: the two arrays with 4 in the lanes, the one whose 64
    # envs half-fill them, and the copy a trip
    faults = tiled_layout.lane_dense_faults(text, "u8", 64 * 8 * 8 * 4, lane_dim=64)
    assert len(faults) == 4 and faults[0].startswith("32.0x padded, 4 in the lanes")
    assert faults[-1].startswith("relayout a loop trip: %copy.3")
    with pytest.raises(ValueError):
        tiled_layout.lane_dense_faults(text, "s8", 1, lane_dim=64)


def test_candidate_state_faults_reads_a_guards_branch():
    """A ``conditional`` and the copies of train-state leaves in its chosen
    branch (the post-hoc guard under donation, PERF.md, PR 33); activations
    and leaves a compiler stages in scalar memory are nobody's fault."""
    from scalerl_tpu.utils import tiled_layout

    text = """\
%region_1.2 (p: (f32[1024,4096], f32[4096])) -> (f32[1024,4096], f32[4096]) {
  %copy.1 = f32[1024,4096]{1,0:T(8,128)} copy(%get-tuple-element.1), backend_config={}
  ROOT %copy.2 = f32[4096]{0:T(1024)} copy(%get-tuple-element.2)
}

ENTRY %main (a: f32[1024,4096]) -> f32[1024,4096] {
  %copy.7 = f32[2,1024,1024]{2,1,0:T(8,128)} copy(%act)
  %copy.8 = f32[1]{0:T(128)S(6)} copy(%state_params_value_head_bias)
  %cond.5 = (f32[1024,4096]{1,0:T(8,128)}, f32[4096]{0:T(1024)}) conditional(%ok, %t, %t), branch_computations={%region_1.2, %region_3.4}
}
"""
    leaves = [(1024, 4096), (4096,), (1,)]
    faults = tiled_layout.candidate_state_faults(text, leaves)
    assert [f.split(":")[0] for f in faults] == ["copy of a leaf", "copy of a leaf", "conditional"]
    assert tiled_layout.candidate_state_faults(text, [(8, 8)]) == [faults[-1]]
