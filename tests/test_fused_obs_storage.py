"""The fused loop stores observations with the env axis LAST (ISSUE 31).

On the TPU a loop carry takes the default major-to-minor layout and the
minor axis lands in a tile's 128 lanes; ``[B, 84, 84, 4]`` uint8 pads a
frame batch 32 times there.  The storage is an internal of
``runtime/device_loop.py``: the model, ``learn_fn`` and every caller of
``Trajectory`` still see ``[.., B, *obs_shape]``.  These cases hold the
loop to a plain per-step Python loop over ``venv.step`` and ``model.apply``
on the same keys, and the mesh and super-chunk paths to the single-device
chunked one.

The trajectory's observations are written into one buffer whose time axis
sits directly outside the stored frame's two minor axes, pinned row-major
(ISSUE 43): the learner's ``[T, B] -> [T*B]`` merge is then a bitcast where
a scan's stacked rows cost a copy of the whole bf16 trajectory.  The cases
after the storage's hold that form to the stacked one bit for bit.
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
    ActorCarry,
    DeviceActorLearnerLoop,
    _load_obs,
    _store_obs,
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


# -- the trajectory buffer (ISSUE 43) -----------------------------------------


class _StackedRowsLoop(DeviceActorLearnerLoop):
    """The loop as it was before ISSUE 43: the scan stacks ``c.obs`` as a
    ``ys`` row, row T is concatenated on, the env axis moved to 1."""

    def _unroll(self, params, carry, key):
        core0 = carry.core_state

        def step(c, k):
            out, new_core = self.model.apply(
                params, _load_obs(c.obs)[None], c.last_action[None],
                c.reward[None], c.done[None], c.core_state,
            )
            logits = out.policy_logits[0]
            k_act, k_env = jax.random.split(k)
            action = jax.random.categorical(k_act, logits, axis=-1)
            env_state, next_obs, reward, done = self.venv.step(
                c.env_state, action, k_env
            )
            row = (c.obs, c.last_action, c.reward, c.done, logits)
            ep_ret = c.episode_return + reward
            new_c = ActorCarry(
                env_state=env_state, obs=_store_obs(next_obs), last_action=action,
                reward=reward, done=done, core_state=new_core,
                episode_return=jnp.where(done, 0.0, ep_ret),
                return_sum=c.return_sum + jnp.where(done, ep_ret, 0.0),
                episode_count=c.episode_count + done.astype(jnp.float32),
            )
            return new_c, row

        carry, rows = jax.lax.scan(
            step, carry, jax.random.split(key, self.unroll_length)
        )
        obs_rows, la_rows, rew_rows, done_rows, logit_rows = rows
        traj = Trajectory(
            obs=jnp.moveaxis(
                jnp.concatenate([obs_rows, carry.obs[None]], axis=0), -1, 1
            ),
            action=jnp.concatenate([la_rows, carry.last_action[None]], axis=0),
            reward=jnp.concatenate([rew_rows, carry.reward[None]], axis=0),
            done=jnp.concatenate([done_rows, carry.done[None]], axis=0),
            logits=jnp.concatenate(
                [logit_rows, jnp.zeros_like(logit_rows[:1])], axis=0
            ),
            core_state=core0,
        )
        return carry, traj


def _stacked_twin(loop):
    return _StackedRowsLoop(
        loop.model, loop.venv, loop.learn_fn, loop.unroll_length,
        iters_per_call=loop.iters_per_call,
    )


def _assert_bitwise(got, want, what=""):
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape and a.dtype == b.dtype, what
        np.testing.assert_array_equal(a, b, err_msg=what)


@pytest.mark.parametrize("name", sorted(ENVS))
def test_buffered_trajectory_is_the_stacked_one_bitwise(name):
    """``_unroll`` with the rows written into the buffer against the scan
    that stacks them: the same trajectory and the same carry, bit for bit
    (two scans over the same body, so floats too)."""
    loop, agent, _venv = _build(name)
    old = _stacked_twin(loop)
    k_init, k_roll = jax.random.split(jax.random.PRNGKey(11))
    params = agent.state.params
    carry, traj = jax.jit(loop._unroll)(params, loop.init_carry(k_init), k_roll)
    carry0, traj0 = jax.jit(old._unroll)(params, old.init_carry(k_init), k_roll)
    _assert_bitwise(traj, traj0, "trajectory")
    _assert_bitwise(carry, carry0, "carry")


@pytest.mark.parametrize("name", sorted(ENVS))
def test_two_dispatches_give_the_stacked_loops_metrics_bitwise(name):
    """Two dispatches of two iterations each, the second from the first's
    state and carry: every metric, the parameters and the carry equal the
    stacked-rows loop's, bit for bit."""
    loop, agent, _venv = _build(name, iters_per_call=2)
    old = _stacked_twin(loop)
    fresh = lambda: jax.tree_util.tree_map(jnp.copy, agent.state)  # noqa: E731
    k_init, key = jax.random.split(jax.random.PRNGKey(7))

    def two_dispatches(lp):
        state, carry, k, stream = fresh(), lp.init_carry(k_init), key, []
        for _ in range(2):
            k, sub = jax.random.split(k)
            state, carry, m = lp.train_chunk(state, carry, sub)
            stream.append(m)
        return state, carry, stream

    state, carry, stream = two_dispatches(loop)
    state0, carry0, stream0 = two_dispatches(old)
    for i, (m, m0) in enumerate(zip(stream, stream0)):
        assert set(m) == set(m0)
        for k_ in m:
            np.testing.assert_array_equal(
                np.asarray(m[k_]), np.asarray(m0[k_]), err_msg=f"dispatch {i}: {k_}"
            )
    _assert_bitwise(state.params, state0.params, "params")
    _assert_bitwise(carry, carry0, "carry")


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for inner in v if isinstance(v, (tuple, list)) else (v,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


@pytest.mark.parametrize("name", sorted(ENVS))
def test_unroll_writes_rows_in_place_and_concatenates_no_observation(name):
    """The traced ``_unroll``: no ``concatenate`` gives an array the size
    of the trajectory's observations (the stacked loop's does), the buffer
    is written by ``dynamic_update_slice`` with the time axis directly
    outside the stored frame's two minor axes, and every write is pinned."""
    loop, agent, venv = _build(name)
    carry = loop.init_carry(jax.random.PRNGKey(0))
    stored = carry.obs.shape
    t_axis = max(len(stored) - 2, 0)
    buf_shape = (*stored[:t_axis], T + 1, *stored[t_axis:])
    sizes = int(np.prod(buf_shape))

    def concatenated(lp):
        jaxpr = jax.make_jaxpr(lp._unroll)(agent.state.params, carry, jax.random.PRNGKey(1))
        eqns = list(_eqns(jaxpr.jaxpr))
        return eqns, [
            e for e in eqns
            if e.primitive.name == "concatenate"
            and e.outvars[0].aval.dtype == carry.obs.dtype
            and e.outvars[0].aval.size == sizes
        ]

    eqns, found = concatenated(loop)
    assert not found, found
    assert concatenated(_stacked_twin(loop))[1]  # the reader sees the old form's
    writes = [
        e for e in eqns
        if e.primitive.name == "dynamic_update_slice"
        and e.outvars[0].aval.shape == buf_shape
    ]
    pins = [
        e for e in eqns
        if e.primitive.name == "layout_constraint"
        and e.outvars[0].aval.shape == buf_shape
    ]
    assert len(writes) == 2, writes  # the scan body's, and row T's
    assert len(pins) == 3, pins  # the buffer's creation and both writes
    assert all(
        e.params["layout"].major_to_minor == tuple(range(len(buf_shape))) for e in pins
    )


def test_traj_storage_is_recorded_once_a_shape(monkeypatch):
    """As the storage: one zero-length ``fused.traj_storage`` span a traced
    shape says which trajectory buffer ran."""
    from scalerl_tpu.runtime import tracing

    monkeypatch.setenv(tracing.ENV_SAMPLE, "1.0")
    tracing.reset()
    try:
        # geometries no other test traces (the note is cached by shape)
        for name, num_envs, want in (
            ("vector", 10, {"buffer_shape": [T + 1, 4, 10], "dtype": "float32", "time_axis": 0}),
            ("pixel", 2, {"buffer_shape": [84, 84, T + 1, 4, 2], "dtype": "uint8", "time_axis": 2}),
        ):
            loop, agent, _venv = _build(name, num_envs=num_envs)
            unroll = jax.jit(loop._unroll)
            carry = loop.init_carry(jax.random.PRNGKey(0))
            for i in range(2):
                carry, _traj = unroll(agent.state.params, carry, jax.random.PRNGKey(i))
            spans = [
                s for s in tracing.get_tracer().finished()
                if s["name"] == "fused.traj_storage"
                and s["attrs"]["dtype"] == want["dtype"]
            ]
            assert len(spans) == 1, spans
            assert spans[0]["attrs"] == {**want, "pinned": True}
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


# the learner's input in the compiled ``impala_fused`` dispatch at 2048 envs
# (AOT for a described v5e, PR 43): the three lines from the stacked rows
# to conv1's operand, in the iteration loop's body
_BODY = """\
%wide.region_0.145 (p: (s32[], u8[84,84,4,2048])) -> (s32[], u8[84,84,4,2048]) {{
{lines}
}}

ENTRY %main.159 (a: u8[84,84,4,2048]) -> u8[84,84,4,2048] {{
  %while.176 = (s32[], u8[84,84,4,2048]{{3,2,1,0}}) while(%t), condition=%cond.1, body=%wide.region_0.145
}}
"""
TRAJECTORY_TEXTS = {
    # rows stacked as a scan's ``ys``: T major-most, and a copy moves it
    "stacked": """\
  %multiply_bitcast_fusion.3 = bf16[21,84,84,1,4,2048]{5,4,3,2,1,0:T(4,128)(2,1)} fusion(%bitcast.375, %while.175), kind=kLoop, calls=%fused_computation.280
  %copy.52 = bf16[21,84,84,1,4,2048]{5,4,0,3,2,1:T(4,128)(2,1)} copy(%multiply_bitcast_fusion.3), metadata={op_name="jit(train_chunk)/while/body/closed_call/jvp(AtariNet)/reshape"}
  %bitcast.354 = bf16[43008,84,84,4]{0,3,2,1:T(4,128)(2,1)} bitcast(%copy.52)""",
    # the buffer ``[84, 84, T+1, 4, B]`` left to XLA's layout assignment:
    # the ``while`` carry is given ``{4,3,1,0,2}``, T major-most again, and the
    # copy stays
    "unpinned": """\
  %bitcast_dynamic-update-slice_fusion.9 = u8[84,84,21,4,2048]{4,3,1,0,2:T(4,128)(4,1)} fusion(%while.175, %get-tuple-element.4918), kind=kLoop, calls=%fused_computation.317
  %multiply_bitcast_fusion.3 = bf16[21,84,84,1,4,2048]{5,4,3,2,1,0:T(4,128)(2,1)} fusion(%bitcast_dynamic-update-slice_fusion.9), kind=kLoop, calls=%fused_computation.280
  %copy.51 = bf16[21,84,84,1,4,2048]{5,4,0,3,2,1:T(4,128)(2,1)} copy(%multiply_bitcast_fusion.3), metadata={op_name="jit(train_chunk)/while/body/closed_call/jvp(AtariNet)/reshape"}
  %bitcast.358 = bf16[43008,84,84,4]{0,3,2,1:T(4,128)(2,1)} bitcast(%copy.51)""",
    # the buffer pinned row-major: the merge is a bitcast
    "pinned": """\
  %bitcast_dynamic-update-slice_fusion.9 = u8[84,84,21,4,2048]{4,3,2,1,0:T(4,128)(4,1)} fusion(%while.175, %get-tuple-element.4918), kind=kLoop, calls=%fused_computation.317
  %convert_multiply_fusion.10 = bf16[84,84,21,4,2048]{4,3,2,1,0:T(4,128)(2,1)} fusion(%bitcast_dynamic-update-slice_fusion.9), kind=kLoop, calls=%fused_computation.280
  %bitcast.354 = bf16[43008,84,84,4]{0,3,2,1:T(4,128)(2,1)} bitcast(%convert_multiply_fusion.10)""",
}


@pytest.mark.parametrize(
    "form, copies", [("stacked", ["%copy.52"]), ("unpinned", ["%copy.51"]), ("pinned", [])]
)
def test_loop_body_copies_reports_the_trajectory_copy(form, copies):
    """The reader the layout tests hold the fused program to finds the
    copy of the whole bf16 trajectory in the stacked-rows text and in the
    unpinned buffer's, and nothing in the pinned buffer's (nor a uint8
    relayout in any)."""
    from scalerl_tpu.utils import tiled_layout

    text = _BODY.format(lines=TRAJECTORY_TEXTS[form])
    found = tiled_layout.loop_body_copies(text, "bf16", 10_000_000)
    assert [line.split(" = ")[0] for line in found] == copies
    assert tiled_layout.loop_body_copies(text, "u8", 10_000_000) == []
    # the same bytes either way: every form is stored dense, envs in the lanes
    for a in tiled_layout.arrays(text, "bf16"):
        assert a.padding == 1.0 and a.minor_dim in (2048, 43008), a


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
