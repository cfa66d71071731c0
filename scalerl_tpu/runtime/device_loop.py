"""Fully-fused on-device actor-learner loop (the flagship throughput path).

Replaces the reference's process zoo — actor processes doing per-step CPU
inference + queue hand-off + learner batching (``impala_atari.py:153-268``)
— with ONE XLA program per training iteration: env step, policy forward,
action sample, trajectory collection (``lax.scan`` over the unroll), V-trace
learner update.  Multiple iterations are themselves ``lax.scan``-ed so the
host dispatches once per ``iters_per_call`` updates: a local chip's dispatch
latency is microseconds, but each dispatch still serializes the host against
the device, and one program per many updates keeps the device busy between
metric reads.

Works with any ``JaxVecEnv`` (device-native env) and any model implementing
the recurrent-policy signature (``models/policy.py``).  Within a fused
iteration the behavior policy equals the target policy (V-trace rhos = 1,
the on-policy special case); the *host* actor plane
(``trainer/actor_learner.py``) exercises true off-policy lag.
"""

from __future__ import annotations

from contextlib import nullcontext
from functools import lru_cache, partial
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from scalerl_tpu.agents.impala import ImpalaTrainState
from scalerl_tpu.data.trajectory import Trajectory
from scalerl_tpu.envs.jax_envs.base import JaxVecEnv
from scalerl_tpu.runtime import dispatch, telemetry, tracing
from scalerl_tpu.runtime.dispatch import MetricsPipeline, get_metrics
from scalerl_tpu.utils.profiling import step_marker


class ActorCarry(NamedTuple):
    """Per-env actor state threaded across rollout chunks.

    Every leaf has an env/batch axis (the accumulators are per-env vectors,
    not scalars), so the whole carry shards over a ``dp`` mesh axis in the
    multi-device fused loop.  It leads everywhere but in ``obs``, which is
    stored env-axis-LAST (:func:`carry_env_axes`): a loop carry takes the
    default major-to-minor layout, and on the TPU the minor axis lands in
    the 128 lanes of a tile.  ``[B, 84, 84, 4]`` uint8 puts 4 channels
    there and pads each frame batch 32 times (1.94 GB for 57.8 MB at 2048
    envs); ``[84, 84, 4, B]`` is dense, and is the physical order the
    convolution and the trajectory buffer want anyway
    (docs/PERFORMANCE.md, "Reading a tiled layout").

    The trajectory's observations (:meth:`DeviceActorLearnerLoop._unroll`)
    are written, as the actor produces them, into one buffer
    ``[*obs_shape[:-1], T+1, obs_shape[-1], B]`` (``[84, 84, 21, 4, 2048]``):
    the time axis directly outside the stored frame's two minor axes.  The
    learner merges ``[T, B]`` into one batch axis, and a merge of two axes is
    a bitcast only if the outer one sits directly outside the tile of the
    inner: under the ``(4, 128)`` tile on ``(C, B)`` that buffer IS
    ``[84, 84, 4, (T B)]`` byte for byte.  Rows stacked with T leading (a
    scan's ``ys``) put T major-most, and the learner then copied the whole
    bf16 trajectory (2.4 GB at 2048 envs) to move it (PERF.md, PR 43).  The
    buffer's layout is pinned row-major (:func:`_pin_row_major`): left
    alone, XLA gives a ``while`` carry the layout that makes its
    ``dynamic-update-slice`` cheapest, T major-most again.
    """

    env_state: Any
    obs: jnp.ndarray  # [*obs_shape, B]
    last_action: jnp.ndarray  # [B]
    reward: jnp.ndarray  # [B]
    done: jnp.ndarray  # [B]
    core_state: Any  # model recurrent state
    episode_return: jnp.ndarray  # [B] running return accumulator
    return_sum: jnp.ndarray  # [B] per-env sum of completed-episode returns
    episode_count: jnp.ndarray  # [B] per-env completed-episode count


# the fused iteration's phases, as a device trace's ``op_name`` shows them
# (``benchmark/op_scopes.py``, PERF.md section 3)
_SCOPE_ACT = "act"
_SCOPE_ENV_STEP = "env_step"
_SCOPE_STORE = "store"
_SCOPE_LEARN = "learn"


def _store_obs(obs: jnp.ndarray) -> jnp.ndarray:
    """``[B, *obs_shape]`` as the env hands it out -> ``[*obs_shape, B]``."""
    return jnp.moveaxis(obs, 0, -1)


def _load_obs(stored: jnp.ndarray) -> jnp.ndarray:
    """Stored observations with the env axis moved back to the front
    (logically: the compiler resolves it to a bitcast on the TPU)."""
    return jnp.moveaxis(stored, -1, 0)


def _pin_row_major(x: jnp.ndarray) -> jnp.ndarray:
    """Hold ``x`` to the major-to-minor layout of its logical shape where
    the compiler would otherwise choose one (a ``while`` carry that lives
    inside one program).  Lowers on every backend and under ``shard_map``."""
    return with_layout_constraint(x, Layout(major_to_minor=tuple(range(x.ndim))))


def carry_env_axes(carry: ActorCarry) -> ActorCarry:
    """The position of the env axis in every leaf of ``carry`` (a pytree of
    ints shaped like it): last in the stored observation, leading in the
    rest.  The one place that says so; the mesh path shards by it."""
    axes = jax.tree_util.tree_map(lambda x: 0, carry)
    return axes._replace(obs=jnp.ndim(carry.obs) - 1)


@lru_cache(maxsize=None)
def _note_obs_storage(stored_shape: Tuple[int, ...], dtype: str) -> None:
    """The storage is a layout and engages on every step, so it has no hit
    rate: one zero-length program span a traced shape (the cache is the
    "once"), so that a trace says which storage ran."""
    with tracing.span(
        "fused.obs_storage", kind="loop", stored_shape=list(stored_shape),
        dtype=dtype, env_axis=len(stored_shape) - 1,
    ):
        pass


@lru_cache(maxsize=None)
def _note_traj_storage(buffer_shape: Tuple[int, ...], dtype: str, time_axis: int) -> None:
    """As :func:`_note_obs_storage`, for the trajectory buffer: one
    zero-length span a traced shape."""
    with tracing.span(
        "fused.traj_storage", kind="loop", buffer_shape=list(buffer_shape),
        dtype=dtype, time_axis=time_axis, pinned=True,
    ):
        pass


def resolve_iter_mode(iter_mode: str = "auto") -> str:
    """Resolve the fused loop's iteration-fusion strategy.

    ``"scan"`` wraps the per-iteration (rollout + learn) body in
    ``lax.scan`` — compile time stays flat in ``iters_per_call`` and the
    program is small; this is the right choice on TPU/GPU.  ``"unroll"``
    expands the iterations as a Python loop inside the one jitted program —
    identical math, but no ``while`` wrapper in the HLO.

    Why the knob exists (the r05 bench regression verdict,
    docs/PERFORMANCE.md): XLA:CPU lowers convolution *gradient* ops inside
    a while-loop body through a non-Eigen path that is catastrophically
    slow — the fused IMPALA chunk measured **23.2 s wrapped in a length-1
    ``lax.scan`` vs 0.42 s with the same body unrolled** (~55x) on this
    repo's bench shape.  ``"auto"`` therefore picks ``"unroll"`` on the CPU
    backend and ``"scan"`` everywhere else.  ``SCALERL_ITER_MODE`` overrides
    what ``auto`` resolves to (escape hatch, same pattern as
    ``SCALERL_PER_METHOD``)."""
    import os

    modes = ("scan", "unroll")
    if iter_mode != "auto":
        if iter_mode not in modes:
            raise ValueError(
                f"iter_mode must be one of {('auto',) + modes}, got {iter_mode!r}"
            )
        return iter_mode
    forced = os.environ.get("SCALERL_ITER_MODE")
    if forced:
        if forced not in modes:
            raise ValueError(
                f"SCALERL_ITER_MODE={forced!r} is not one of {modes}"
            )
        return forced
    return "unroll" if jax.default_backend() == "cpu" else "scan"


class DeviceActorLearnerLoop:
    def __init__(
        self,
        model,
        venv: JaxVecEnv,
        learn_fn: Callable[[ImpalaTrainState, Trajectory], Tuple[ImpalaTrainState, Dict]],
        unroll_length: int,
        iters_per_call: int = 10,
        mesh=None,
        axis_name: str = "dp",
        iter_mode: str = "auto",
    ) -> None:
        """``mesh``: shard the fused loop data-parallel over a mesh — env
        lanes and actor carry split along ``axis_name``, params replicated,
        gradients ``psum``-ed inside the learn step (pass a ``learn_fn``
        built with ``grad_axis=axis_name``).  This is the Podracer "Anakin"
        architecture; ``venv.num_envs`` must divide by the axis size.

        ``iter_mode``: how iterations fuse into the chunk program —
        ``"scan"`` (lax.scan body, TPU/GPU), ``"unroll"`` (Python-unrolled
        body; recovers XLA:CPU's ~55x conv-grad-in-while-loop slowdown), or
        ``"auto"`` (backend-resolved, see :func:`resolve_iter_mode`)."""
        self.model = model
        self.venv = venv
        self.learn_fn = learn_fn
        self.unroll_length = unroll_length
        self.iters_per_call = iters_per_call
        self.mesh = mesh
        self.axis_name = axis_name
        self.iter_mode = resolve_iter_mode(iter_mode)
        # superchunk executables keyed by num_chunks (the Anakin whole-run
        # fusion: one dispatch covers N chunks of rollout+learn)
        self._superchunks: Dict[int, Callable] = {}
        self._superchunk_warm: set = set()
        if mesh is None:
            self._train_many = jax.jit(
                partial(self._train_many_impl), donate_argnums=(0, 1)
            )
        else:
            n = mesh.shape[axis_name]
            if venv.num_envs % n != 0:
                raise ValueError(
                    f"num_envs ({venv.num_envs}) must divide by mesh axis "
                    f"{axis_name!r} size ({n})"
                )
            self._sharded_fn = None  # built on first call (needs pytree structure)
            self._train_many = self._sharded_train_many

    # ------------------------------------------------------------------
    def _sharded_train_many(self, state, carry, key):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        if self._sharded_fn is None:
            axis = self.axis_name

            def leaf_spec(x, env_axis):
                ndim = getattr(x, "ndim", 0)
                if ndim >= 1:
                    return P(*(axis if i == env_axis else None for i in range(ndim)))
                return P()

            state_spec = jax.tree_util.tree_map(lambda x: P(), state)
            carry_spec = jax.tree_util.tree_map(
                leaf_spec, carry, carry_env_axes(carry)
            )

            def inner(state, carry, key):
                # distinct randomness per shard: fold the device's ring index
                key = jax.random.fold_in(
                    key, jax.lax.axis_index(self.axis_name)
                )
                return self._train_many_impl(state, carry, key)

            def inner_synced(state, carry, key):
                state, carry, metrics = inner(state, carry, key)
                # monitoring sums fused into the step (a host-side jnp.sum
                # per chunk would cost an extra dispatch each)
                metrics["episode_return_sum"] = jax.lax.psum(
                    jnp.sum(carry.return_sum), axis
                )
                metrics["episode_count_sum"] = jax.lax.psum(
                    jnp.sum(carry.episode_count), axis
                )
                return state, carry, metrics

            fn = shard_map(
                inner_synced,
                mesh=self.mesh,
                in_specs=(state_spec, carry_spec, P()),
                # metrics leave the learn step replicated (sum-convention
                # losses psum-ed, mean_* pmean-ed — impala_loss contract)
                out_specs=(state_spec, carry_spec, P()),
                check_vma=False,
            )
            # check_vma=False disables the replication check, so a learn_fn
            # built WITHOUT grad_axis would silently train each shard on its
            # own grads; verify the traced program psums over our axis.
            # Trace `inner` (pre-monitoring) so the check is independent of
            # how many monitoring psums `inner_synced` adds, and cache only
            # after the check passes — a caller that catches the error and
            # retries must not get an unsynced cached fn.
            probe = shard_map(
                inner,
                mesh=self.mesh,
                in_specs=(state_spec, carry_spec, P()),
                out_specs=(state_spec, carry_spec, P()),
                check_vma=False,
            )
            self._assert_grad_synced(probe, state, carry, key)
            self._sharded_fn = jax.jit(fn, donate_argnums=(0, 1))
        return self._sharded_fn(state, carry, key)

    def _assert_grad_synced(self, fn, state, carry, key) -> None:
        """Fail fast if the sharded step has no *gradient-sized* psum over
        ``axis_name``.  ``fn`` must be the pre-monitoring program — the
        caller passes a probe without the monitoring psums.  Heuristic:
        gradient syncs psum *arrays* (param leaves: kernels, biases), while
        metric/counter psums carry scalars — so require at least one psum
        over the axis with an operand of rank >= 1.  A learn_fn that psums
        only scalar metrics still fails the check.  Best-effort:
        jax-internals changes skip the check rather than break the loop."""
        try:
            jaxpr = jax.make_jaxpr(fn)(state, carry, key)

            def count_array_psums(jxp) -> int:
                n = 0
                for eqn in jxp.eqns:
                    if (
                        eqn.primitive.name == "psum"
                        and self.axis_name in (eqn.params.get("axes") or ())
                        and any(
                            getattr(v.aval, "ndim", 0) >= 1 for v in eqn.invars
                        )
                    ):
                        n += 1
                    for v in eqn.params.values():
                        inner_jaxpr = getattr(v, "jaxpr", v)
                        if hasattr(inner_jaxpr, "eqns"):
                            n += count_array_psums(inner_jaxpr)
                return n

            n_psums = count_array_psums(jaxpr.jaxpr)
        except Exception:  # noqa: BLE001 — introspection only
            return
        if n_psums == 0:
            raise ValueError(
                "mesh mode needs a gradient-synchronized learn_fn: build it "
                f"with grad_axis={self.axis_name!r} (e.g. "
                "agent.make_learn_fn(grad_axis=...)); the traced step "
                "contains no array-valued (gradient-sized) psum over the "
                "mesh axis, so each device would train on its own shard only"
            )

    # ------------------------------------------------------------------
    def init_carry(self, key: jax.Array) -> ActorCarry:
        B = self.venv.num_envs
        env_state, obs = self.venv.reset(key)
        return ActorCarry(
            env_state=env_state,
            obs=_store_obs(obs),
            last_action=jnp.zeros(B, jnp.int32),
            reward=jnp.zeros(B, jnp.float32),
            done=jnp.ones(B, jnp.bool_),
            core_state=self.model.initial_state(B),
            episode_return=jnp.zeros(B, jnp.float32),
            return_sum=jnp.zeros(B, jnp.float32),
            episode_count=jnp.zeros(B, jnp.float32),
        )

    # ------------------------------------------------------------------
    def _unroll(self, params, carry: ActorCarry, key: jax.Array):
        """Collect one [T+1, B] trajectory chunk; row T's logits are unused
        by the learner (behavior_logits[:-1]) and left zero.

        The observations are not stacked as a scan's ``ys``: row ``t`` is
        written in place into a buffer carried through the scan, whose time
        axis sits where the learner's ``[T, B]`` merge wants it
        (:class:`ActorCarry`), and ``Trajectory.obs`` is the logical
        ``[T+1, B, *obs_shape]`` view of it.  The other four rows are
        kilobytes and stay ``ys``."""
        core0 = carry.core_state
        T = self.unroll_length
        stored = carry.obs.shape
        # the time axis goes directly outside the stored frame's two minor
        # axes; a stored observation of rank 1 ([B]) has no second one and
        # takes it leading
        t_axis = max(len(stored) - 2, 0)
        buf_shape = (*stored[:t_axis], T + 1, *stored[t_axis:])
        dtype = jnp.dtype(carry.obs.dtype).name
        _note_obs_storage(tuple(stored), dtype)
        _note_traj_storage(buf_shape, dtype, t_axis)

        def write_row(buf, obs, t):
            with jax.named_scope(_SCOPE_STORE):
                return _pin_row_major(
                    jax.lax.dynamic_update_index_in_dim(buf, obs, t, axis=t_axis)
                )

        def step(cb, kt):
            c, buf = cb
            k, t = kt
            with jax.named_scope(_SCOPE_ACT):
                out, new_core = self.model.apply(
                    params, _load_obs(c.obs)[None], c.last_action[None],
                    c.reward[None], c.done[None], c.core_state,
                )
                logits = out.policy_logits[0]
                k_act, k_env = jax.random.split(k)
                action = jax.random.categorical(k_act, logits, axis=-1)
            with jax.named_scope(_SCOPE_ENV_STEP):
                env_state, next_obs, reward, done = self.venv.step(
                    c.env_state, action, k_env
                )
            row = (c.last_action, c.reward, c.done, logits)
            ep_ret = c.episode_return + reward
            new_c = ActorCarry(
                env_state=env_state,
                obs=_store_obs(next_obs),
                last_action=action,
                reward=reward,
                done=done,
                core_state=new_core,
                episode_return=jnp.where(done, 0.0, ep_ret),
                return_sum=c.return_sum + jnp.where(done, ep_ret, 0.0),
                episode_count=c.episode_count + done.astype(jnp.float32),
            )
            return (new_c, write_row(buf, c.obs, t)), row

        keys = jax.random.split(key, T)
        # every row is written before it is read, so the buffer starts
        # uninitialised: a bare allocation on the TPU, zeros on the CPU (a
        # zero fill took 3.7 ms an iteration at 2048 envs, PERF.md, PR 43)
        buf = _pin_row_major(jax.lax.empty(buf_shape, carry.obs.dtype))
        (carry, buf), rows = jax.lax.scan(
            step, (carry, buf), (keys, jnp.arange(T, dtype=jnp.int32))
        )
        la_rows, rew_rows, done_rows, logit_rows = rows
        # final row T from the post-scan carry (logits zero: unused)
        buf = write_row(buf, carry.obs, T)

        traj = Trajectory(
            # [.., T+1, C, B] -> [T+1, B, .., C]
            obs=jnp.moveaxis(_load_obs(buf), t_axis + 1, 0),
            action=jnp.concatenate([la_rows, carry.last_action[None]], axis=0),
            reward=jnp.concatenate([rew_rows, carry.reward[None]], axis=0),
            done=jnp.concatenate([done_rows, carry.done[None]], axis=0),
            logits=jnp.concatenate(
                [logit_rows, jnp.zeros_like(logit_rows[:1])], axis=0
            ),
            core_state=core0,
        )
        return carry, traj

    # ------------------------------------------------------------------
    def _train_many_impl(self, state: ImpalaTrainState, carry: ActorCarry, key):
        def one_iter(sc, k):
            state, carry = sc
            k_roll, _ = jax.random.split(k)
            carry, traj = self._unroll(state.params, carry, k_roll)
            with jax.named_scope(_SCOPE_LEARN):
                state, metrics = self.learn_fn(state, traj)
            return (state, carry), metrics

        keys = jax.random.split(key, self.iters_per_call)
        if self.iter_mode == "scan":
            (state, carry), metrics = jax.lax.scan(one_iter, (state, carry), keys)
        else:
            # "unroll": same iteration body, Python-expanded — no while
            # wrapper in the HLO, so XLA:CPU's slow conv-grad-in-loop
            # lowering is never hit (the r05 bench regression; the stacked
            # metrics keep the scan path's exact reduction order)
            per_iter = []
            sc = (state, carry)
            for i in range(self.iters_per_call):
                sc, m = one_iter(sc, keys[i])
                per_iter.append(m)
            state, carry = sc
            metrics = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *per_iter
            )
        mean_metrics = {k: jnp.mean(v) for k, v in metrics.items()}
        # monitoring sums ride the fused program (shard-local here; the mesh
        # wrapper overwrites them with the psum-ed globals)
        mean_metrics["episode_return_sum"] = jnp.sum(carry.return_sum)
        mean_metrics["episode_count_sum"] = jnp.sum(carry.episode_count)
        return state, carry, mean_metrics

    # ------------------------------------------------------------------
    def _superchunk_impl(self, state, carry, key, num_chunks: int):
        """The Anakin whole-run fusion: ``num_chunks`` chunks of
        (rollout + V-trace learn) in ONE program.

        The per-chunk key schedule replicates ``run``'s host loop exactly
        (``key, sub = split(key)`` each chunk), so the final state and the
        per-chunk metric stream are bitwise-comparable with the chunked
        driver — the parity contract ``tests/test_dispatch.py`` asserts.
        Per-chunk metric dicts come back stacked ``[num_chunks]`` and are
        materialized by the caller with ONE batched transfer for the whole
        super-chunk.
        """

        def one_chunk(sc, _):
            state, carry, key = sc
            key, sub = jax.random.split(key)
            state, carry, m = self._train_many_impl(state, carry, sub)
            return (state, carry, key), m

        if self.iter_mode == "scan":
            (state, carry, key), stacked = jax.lax.scan(
                one_chunk, (state, carry, key), None, length=num_chunks
            )
        else:
            per_chunk = []
            sc = (state, carry, key)
            for _ in range(num_chunks):
                sc, m = one_chunk(sc, None)
                per_chunk.append(m)
            state, carry, key = sc
            stacked = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *per_chunk
            )
        return state, carry, stacked

    def train_superchunk(
        self, state, carry, key, num_chunks: int
    ) -> Tuple[ImpalaTrainState, ActorCarry, Dict]:
        """One host dispatch covering ``num_chunks`` fused chunks (Anakin).

        Metrics are returned as DEVICE arrays stacked ``[num_chunks]`` per
        key — read them back with one ``dispatch.get_metrics`` call.
        Inputs are donated, like :meth:`train_chunk`.
        """
        if self.mesh is not None:
            raise NotImplementedError(
                "train_superchunk composes with the single-device fused "
                "loop; the mesh path already fuses per-chunk via shard_map "
                "(drive it through run())"
            )
        fn = self._superchunks.get(num_chunks)
        if fn is None:
            fn = jax.jit(
                partial(self._superchunk_impl, num_chunks=num_chunks),
                donate_argnums=(0, 1),
            )
            self._superchunks[num_chunks] = fn
        return fn(state, carry, key)

    def run_anakin(
        self,
        state: ImpalaTrainState,
        carry: ActorCarry,
        key: jax.Array,
        num_calls: int,
        on_metrics: Optional[Callable[[int, Dict[str, float]], None]] = None,
        progress=None,
        instrument: bool = True,
    ) -> Tuple[ImpalaTrainState, ActorCarry, Dict[str, float]]:
        """Drive ``num_calls`` chunks as ONE fused dispatch (Anakin mode).

        Where :meth:`run` dispatches once per chunk and pipelines the metric
        reads, this path dispatches once per *run*: a single jitted
        ``lax.scan`` (or unrolled body, per ``iter_mode``) over (env step ->
        policy -> V-trace learn) covers every chunk, and ONE batched
        device->host transfer materializes the whole stacked metric history
        afterwards.  Steady state (every ``run_anakin`` call after the first
        for a given ``num_calls``) runs under the armed transfer guard.
        ``on_metrics(i, metrics)`` fires per chunk, in order, after the
        read — the metric stream matches :meth:`run`'s exactly.
        """
        guard_ctx = (
            dispatch.steady_state_guard()
            if num_calls in self._superchunk_warm
            else nullcontext()
        )
        with guard_ctx:
            with step_marker(0):
                state, carry, stacked = self.train_superchunk(
                    state, carry, key, num_calls
                )
            if progress is not None:
                progress.bump()
            host = get_metrics(stacked)  # ONE batched transfer, all chunks
        self._superchunk_warm.add(num_calls)
        frames_per_call = (
            self.unroll_length * self.venv.num_envs * self.iters_per_call
        )
        reg = telemetry.get_registry() if instrument else None
        metrics: Dict[str, float] = {}
        nonfinite_chunks = 0
        for i in range(num_calls):
            m = {k: float(v[i]) for k, v in host.items()}
            if reg is not None:
                telemetry.observe_train_metrics(m)
            if m.get("skipped_steps", 0.0) > 0.0:
                nonfinite_chunks += 1
            m["episodes"] = m.pop("episode_count_sum")
            m["return_mean"] = m.pop("episode_return_sum") / max(
                m["episodes"], 1.0
            )
            metrics = m
            if on_metrics is not None:
                on_metrics(i, m)
        if reg is not None:
            # per-superchunk instrument write (chunk-amortized by design)
            reg.meter("rates.chunks_per_s").mark(num_calls)
            reg.meter("rates.fps").mark(frames_per_call * num_calls)
        metrics["chunks_done"] = float(num_calls)
        metrics["nonfinite_chunks"] = float(nonfinite_chunks)
        return state, carry, metrics

    # ------------------------------------------------------------------
    def train_chunk(
        self, state: ImpalaTrainState, carry: ActorCarry, key: jax.Array
    ) -> Tuple[ImpalaTrainState, ActorCarry, Dict]:
        """One fused dispatch (``iters_per_call`` env-unroll+update iterations).

        The public single-dispatch entry point; ``run``/``run_until`` are
        loops over this.  Inputs are donated — do not reuse ``state``/``carry``
        after the call.
        """
        return self._train_many(state, carry, key)

    def run_until(
        self,
        state: ImpalaTrainState,
        carry: ActorCarry,
        key: jax.Array,
        threshold: float,
        max_calls: int,
        on_metrics: Optional[Callable[[int, float, Dict[str, float]], None]] = None,
        chunks_in_flight: int = 2,
        progress=None,
        should_stop: Optional[Callable[[], bool]] = None,
        instrument: bool = True,
    ) -> Tuple[ImpalaTrainState, ActorCarry, Dict[str, float]]:
        """Drive fused chunks until the *windowed* mean episode return (over
        episodes completed since the previous chunk) reaches ``threshold``,
        or ``max_calls`` chunks elapse.

        ``progress``: a supervisor ``ProgressCounter`` bumped once per
        dispatched chunk (stall-watchdog liveness for the host driver).
        ``should_stop``: polled before each dispatch; True stops cleanly
        with in-flight chunks drained and counted — the preemption-guard
        safe point for the fused path.

        ``chunks_in_flight`` chunks stay dispatched ahead of the host's
        metric reads (one batched device->host transfer per chunk), so the
        threshold check and ``on_metrics`` lag the device by
        ``chunks_in_flight - 1`` chunks instead of stalling it; a hit stops
        further dispatch but the chunks already in flight still land (they
        are counted in ``frames`` and folded into the returned state).  The
        metric STREAM — chunk order, values, and the frame counts passed to
        ``on_metrics(frames, windowed_return, chunk_metrics)`` — is
        identical for every ``chunks_in_flight``; 1 is fully synchronous.
        Returns ``(state, carry, summary)`` with summary keys
        ``windowed_return`` / ``frames`` / ``hit``.
        """
        frames_per_call = self.unroll_length * self.venv.num_envs * self.iters_per_call
        init = get_metrics(
            {"s": jnp.sum(carry.return_sum), "c": jnp.sum(carry.episode_count)}
        )
        prev_sum, prev_cnt = init["s"], init["c"]
        windowed = float("nan")
        frames = 0
        hit = False
        nonfinite_chunks = 0
        pipe = MetricsPipeline(depth=chunks_in_flight)
        # instrument=False (args.telemetry_interval_s <= 0) compiles the
        # per-chunk registry feed out of the driver entirely — no meter
        # objects, no observe calls, not even a skipped branch per chunk
        reg = telemetry.get_registry() if instrument else None
        _chunk_meter = reg.meter("rates.chunks_per_s") if instrument else None
        _fps_meter = reg.meter("rates.fps") if instrument else None

        def consume(ready) -> None:
            nonlocal windowed, prev_sum, prev_cnt, hit, nonfinite_chunks
            for i, m in ready:
                # host-side registry feed (m is already host floats via the
                # pipeline's one batched transfer — no extra device traffic)
                if instrument:
                    telemetry.observe_train_metrics(m)
                    _chunk_meter.mark()
                    _fps_meter.mark(frames_per_call)
                if m.get("skipped_steps", 0.0) > 0.0:
                    # guarded learn skipped >= 1 non-finite update this chunk
                    nonfinite_chunks += 1
                s = m["episode_return_sum"]
                c = m["episode_count_sum"]
                if c > prev_cnt:
                    windowed = (s - prev_sum) / (c - prev_cnt)
                    prev_sum, prev_cnt = s, c
                if on_metrics is not None:
                    on_metrics((i + 1) * frames_per_call, windowed, dict(m))
                if windowed >= threshold:
                    hit = True

        for i in range(max_calls):
            if should_stop is not None and should_stop():
                break
            # steady state (chunk 1+) runs under the transfer guard: the
            # only host transfer allowed per chunk is get_metrics' explicit
            # batched device_get; a stray implicit sync raises at its line.
            # Chunk 0 is exempt — tracing/compilation may place constants.
            with dispatch.steady_state_guard() if i > 0 else nullcontext():
                # step_marker: per-chunk device-trace alignment (a cheap
                # profiler annotation — a no-op unless a trace is active)
                with step_marker(i), tracing.span("loop.dispatch", kind="loop"):
                    key, sub = jax.random.split(key)
                    state, carry, m = self.train_chunk(state, carry, sub)
                frames += frames_per_call
                if progress is not None:
                    progress.bump()
                # the sums ride the fused metrics — no extra host dispatches
                consume(pipe.push(i, m))
            if hit:
                break
        consume(pipe.drain())
        summary = {
            "windowed_return": windowed,
            "frames": float(frames),
            "hit": hit,
            "nonfinite_chunks": float(nonfinite_chunks),
        }
        return state, carry, summary

    # ------------------------------------------------------------------
    def run(
        self,
        state: ImpalaTrainState,
        carry: ActorCarry,
        key: jax.Array,
        num_calls: int,
        on_metrics: Optional[Callable[[int, Dict[str, float]], None]] = None,
        chunks_in_flight: int = 2,
        progress=None,
        should_stop: Optional[Callable[[], bool]] = None,
        instrument: bool = True,
    ) -> Tuple[ImpalaTrainState, ActorCarry, Dict[str, float]]:
        """Drive ``num_calls`` fused mega-steps; one host dispatch each.

        Each chunk's metric dict is read back with ONE batched transfer,
        lagging dispatch by ``chunks_in_flight - 1`` chunks so the device
        never idles waiting on the host (``chunks_in_flight=1`` restores
        the synchronous read-after-every-chunk path).  ``on_metrics(i,
        metrics)`` still fires once per chunk, in order.

        ``progress``/``should_stop``: supervision hooks (see ``run_until``).
        The returned metrics carry ``chunks_done`` — with an early
        ``should_stop`` the frame count is ``chunks_done *
        frames_per_call``, which the preemption checkpoint must record
        instead of the requested budget.
        """
        metrics: Dict[str, float] = {}
        nonfinite_chunks = 0
        pipe = MetricsPipeline(depth=chunks_in_flight)
        frames_per_call = self.unroll_length * self.venv.num_envs * self.iters_per_call
        # see run_until: instrument=False compiles the registry feed out
        reg = telemetry.get_registry() if instrument else None
        _chunk_meter = reg.meter("rates.chunks_per_s") if instrument else None
        _fps_meter = reg.meter("rates.fps") if instrument else None

        def consume(ready) -> None:
            nonlocal metrics, nonfinite_chunks
            for i, host_m in ready:
                m = dict(host_m)
                if instrument:
                    telemetry.observe_train_metrics(m)
                    _chunk_meter.mark()
                    _fps_meter.mark(frames_per_call)
                if m.get("skipped_steps", 0.0) > 0.0:
                    nonfinite_chunks += 1
                m["episodes"] = m.pop("episode_count_sum")
                m["return_mean"] = m.pop("episode_return_sum") / max(
                    m["episodes"], 1.0
                )
                metrics = m
                if on_metrics is not None:
                    on_metrics(i, m)

        chunks_done = 0
        for i in range(num_calls):
            if should_stop is not None and should_stop():
                break
            # steady-state transfer guard (see run_until): implicit host
            # syncs raise; get_metrics' one explicit batched get passes
            with dispatch.steady_state_guard() if i > 0 else nullcontext():
                # per-chunk trace step (no-op without an active trace)
                with step_marker(i), tracing.span("loop.dispatch", kind="loop"):
                    key, sub = jax.random.split(key)
                    state, carry, dev_metrics = self.train_chunk(state, carry, sub)
                chunks_done += 1
                if progress is not None:
                    progress.bump()
                consume(pipe.push(i, dev_metrics))
        consume(pipe.drain())
        jax.block_until_ready(state.params)
        metrics["chunks_done"] = float(chunks_done)
        metrics["nonfinite_chunks"] = float(nonfinite_chunks)
        return state, carry, metrics
