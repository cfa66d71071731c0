"""Device-native R2D2: on-device collection feeding on-device replay.

The TPU-fast R2D2 topology, mirroring what ``runtime/device_loop.py``
does for IMPALA: env stepping, recurrent-Q inference, and eps-greedy
action selection run as ONE jitted collector over a ``JaxVecEnv``
(``lax.scan`` over the unroll), the produced ``[B, T+1]`` sequences are
inserted into the device-resident prioritized sequence replay with a
batched dynamic-slice write, and the R2D2 learn step (burn-in + n-step
double-Q + priority write-back) is the same single jitted program the
host plane uses.  The host's whole duty per iteration is a handful of
dispatches — no trajectory ever visits host memory.

Off-policyness note: unlike the fused IMPALA loop (structurally
on-policy), this loop is genuinely off-policy — replayed sequences were
collected under OLD params and OLD (higher) epsilons, which is exactly
the regime the stored-state + burn-in machinery exists for.
"""

from __future__ import annotations

import time
from contextlib import nullcontext
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from scalerl_tpu.agents.r2d2 import R2D2Agent
from scalerl_tpu.config import R2D2Arguments
from scalerl_tpu.runtime import dispatch, telemetry
from scalerl_tpu.runtime.dispatch import get_metrics
from scalerl_tpu.data.sequence_replay import (
    seq_add,
    seq_init,
    seq_sample,
    seq_update_priorities,
    seq_update_priorities_keep_empty,
)
from scalerl_tpu.trainer.base import BaseTrainer


class _CollectCarry(NamedTuple):
    env_state: object
    obs: jnp.ndarray  # [B, ...]
    last_action: jnp.ndarray  # [B]
    reward: jnp.ndarray  # [B]
    done: jnp.ndarray  # [B]
    core: tuple  # model recurrent state
    return_sum: jnp.ndarray  # [B] completed-episode return accumulator
    episode_return: jnp.ndarray  # [B] running
    episode_count: jnp.ndarray  # [B]


class DeviceR2D2Trainer(BaseTrainer):
    """R2D2 over a device-native env (``envs/jax_envs``)."""

    def __init__(
        self,
        args: R2D2Arguments,
        agent: R2D2Agent,
        venv,
        run_name: Optional[str] = None,
        fused: bool = True,
        mesh=None,
        axis_name: str = "dp",
    ) -> None:
        """``fused``: run each iteration (collect + insert + all learn
        steps + priority write-back) as ONE jitted dispatch — the TPU-fast
        default.  ``False`` keeps the piecewise path (one dispatch per
        stage), useful for debugging stage boundaries.

        ``mesh``: run the FUSED iteration data-parallel over a device mesh
        (the Anakin treatment ``runtime/device_loop.py`` gives IMPALA): env
        lanes, collector carry, and the sequence-replay ring all shard over
        ``axis_name`` — each shard keeps an independent local ring fed by
        its own lanes (zero insert comms) — while the learn step psums
        gradients so params stay replicated.  Sampling draws
        ``batch_size/S`` per shard with globally-normalized IS weights
        (``data/sharded_replay.seq_sample_sharded_local``).  Requires
        ``fused=True`` and a plain (non-``enable_mesh``) agent: the mesh
        treatment here subsumes the agent-side DDP form.
        """
        super().__init__(args, run_name=run_name)
        if getattr(agent, "_learn_mesh", None) is not None:
            if mesh is not None:
                raise ValueError(
                    "pass EITHER DeviceR2D2Trainer(mesh=...) (fused sharded "
                    "loop, replay included) OR agent.enable_mesh (DDP learn "
                    "step only, piecewise loop) — not both"
                )
            if fused:
                raise ValueError(
                    "fused=True runs the raw single-device learn fn and would "
                    "silently bypass agent.enable_mesh's sharded learner; use "
                    "DeviceR2D2Trainer(mesh=...) for the fused sharded loop, "
                    "or fused=False for the piecewise DDP combination"
                )
        if mesh is not None and not fused:
            raise ValueError("mesh= requires fused=True (the sharded fused loop)")
        self.fused = fused
        self.mesh = mesh
        self.axis_name = axis_name
        self.agent = agent
        self.venv = venv
        B = venv.num_envs
        T1 = args.rollout_length + 1
        obs_shape = venv.env.observation_shape
        obs_dtype = venv.env.observation_dtype
        field_shapes = {
            "obs": ((T1,) + tuple(obs_shape), obs_dtype),
            "action": ((T1,), jnp.int32),
            "reward": ((T1,), jnp.float32),
            "done": ((T1,), bool),
        }
        core = agent.initial_state(1)
        core_shapes = tuple(tuple(c.shape[1:]) for c, _ in core)
        self.replay = seq_init(field_shapes, core_shapes, args.replay_capacity)
        self._collect = jax.jit(self._collect_impl, donate_argnums=(1,))
        if mesh is None:
            # fused iteration: collect + insert + train_intensity x
            # (sample + learn + priority write-back) as ONE program — one
            # host dispatch per iteration instead of ~3 + train_intensity
            self._fused_iter = jax.jit(
                self._fused_iter_impl, donate_argnums=(0, 1, 2)
            )
            self._collect_insert = jax.jit(
                self._collect_insert_impl, donate_argnums=(1, 2)
            )
        else:
            n = mesh.shape[axis_name]
            for what, val in (
                ("venv.num_envs", B),
                ("replay_capacity", args.replay_capacity),
                ("batch_size", args.batch_size),
            ):
                if val % n != 0:
                    raise ValueError(
                        f"{what} ({val}) must divide by mesh axis "
                        f"{axis_name!r} size ({n}) for the fused sharded loop"
                    )
            from scalerl_tpu.agents.r2d2 import make_r2d2_learn_fn

            self._learn_shard = make_r2d2_learn_fn(
                agent.model, agent.optimizer, args, grad_axis=axis_name
            )
            self._fused_iter = None  # built lazily (needs pytree structure)
            self._collect_insert = None
        self._max_priority = 1.0
        self.env_frames = 0
        # observed skipped-update events (guarded learn; sampled at metric
        # boundaries, so this undercounts dense bursts — a diagnostic, not
        # an exact tally)
        self.nonfinite_events = 0
        # PER search method pinned at construction (not at first trace of
        # the fused program), so SCALERL_PER_METHOD / backend changes
        # can't be silently ignored
        from scalerl_tpu.ops.pallas_per import resolve_sample_method

        self._seq_method = resolve_sample_method("auto")

    # ------------------------------------------------------------------
    def init_carry(self, key: jax.Array) -> _CollectCarry:
        B = self.venv.num_envs
        env_state, obs = self.venv.reset(key)
        return _CollectCarry(
            env_state=env_state,
            obs=obs,
            last_action=jnp.zeros(B, jnp.int32),
            reward=jnp.zeros(B, jnp.float32),
            done=jnp.ones(B, jnp.bool_),
            core=self.agent.initial_state(B),
            return_sum=jnp.zeros(B, jnp.float32),
            episode_return=jnp.zeros(B, jnp.float32),
            episode_count=jnp.zeros(B, jnp.float32),
        )

    def _collect_impl(self, params, carry: _CollectCarry, eps, key):
        """One [T+1, B] chunk under eps-greedy; returns the sequence batch
        in replay layout ([B, T1, ...]) plus the ENTERING core state."""
        model = self.agent.model
        T = self.args.rollout_length
        entry_core = carry.core

        def step(c: _CollectCarry, k):
            out, new_core = model.apply(
                params, c.obs[None], c.last_action[None], c.reward[None],
                c.done[None], c.core,
            )
            q = out.q_values[0]  # [B, A]
            greedy = jnp.argmax(q, axis=-1).astype(jnp.int32)
            k_eps, k_rand, k_env = jax.random.split(k, 3)
            B = greedy.shape[0]
            explore = jax.random.uniform(k_eps, (B,)) < eps
            rand_a = jax.random.randint(k_rand, (B,), 0, q.shape[-1])
            action = jnp.where(explore, rand_a, greedy)
            env_state, next_obs, rew, done = self.venv.step(
                c.env_state, action, k_env
            )
            row = (c.obs, c.last_action, c.reward, c.done)
            ep_ret = c.episode_return + rew
            new_c = _CollectCarry(
                env_state=env_state,
                obs=next_obs,
                last_action=action,
                reward=rew,
                done=done,
                core=new_core,
                return_sum=c.return_sum + jnp.where(done, ep_ret, 0.0),
                episode_return=jnp.where(done, 0.0, ep_ret),
                episode_count=c.episode_count + done.astype(jnp.float32),
            )
            return new_c, row

        keys = jax.random.split(key, T)
        carry, rows = jax.lax.scan(step, carry, keys)
        obs_r, act_r, rew_r, done_r = rows
        # rows + the boundary row, then sequence-major for the replay
        fields = {
            "obs": jnp.moveaxis(
                jnp.concatenate([obs_r, carry.obs[None]], axis=0), 0, 1
            ),
            "action": jnp.moveaxis(
                jnp.concatenate([act_r, carry.last_action[None]], axis=0), 0, 1
            ),
            "reward": jnp.moveaxis(
                jnp.concatenate([rew_r, carry.reward[None]], axis=0), 0, 1
            ),
            "done": jnp.moveaxis(
                jnp.concatenate([done_r, carry.done[None]], axis=0), 0, 1
            ),
        }
        return carry, fields, entry_core

    # ------------------------------------------------------------------
    def _collect_insert_impl(self, params, replay, carry, max_prio, eps, key):
        """Warmup phase fused step: collect one chunk and insert it."""
        B = self.venv.num_envs
        carry, fields, entry_core = self._collect_impl(params, carry, eps, key)
        replay = seq_add(
            replay, fields, entry_core, jnp.full((B,), max_prio, jnp.float32)
        )
        return replay, carry

    def _fused_iter_impl(self, agent_state, replay, carry, max_prio, eps, key):
        """One full R2D2 iteration as one XLA program.

        ``max_prio`` rides the program as a traced scalar (the host keeps
        no priority state), so consecutive fused calls chain without any
        host-side reduction between them.
        """
        args = self.args
        B = self.venv.num_envs
        k_c, key = jax.random.split(key)
        carry, fields, entry_core = self._collect_impl(
            agent_state.params, carry, eps, k_c
        )
        replay = seq_add(
            replay, fields, entry_core, jnp.full((B,), max_prio, jnp.float32)
        )
        metrics = {}
        learn_raw = self.agent._learn_raw
        for _ in range(args.train_intensity):  # static, small
            key, k_s = jax.random.split(key)
            f, c, idx, w = seq_sample(
                replay, k_s, args.batch_size,
                alpha=args.per_alpha, beta=args.per_beta,
                method=self._seq_method,
            )
            agent_state, metrics, new_prio = learn_raw(agent_state, f, c, w)
            replay = seq_update_priorities(replay, idx, new_prio)
            max_prio = jnp.maximum(max_prio, jnp.max(new_prio))
        return agent_state, replay, carry, max_prio, metrics

    # ------------------------------------------------------------------
    # mesh-fused path: per-shard bodies + lazy shard_map builder

    def _fused_iter_local(self, agent_state, replay, carry, max_prio, eps, key):
        """Per-shard body of the mesh-fused iteration (inside shard_map).

        ``replay`` is this shard's INDEPENDENT local ring (capacity/S
        slots) fed by its own env lanes — inserts need no communication;
        the learn step psums gradients over ``axis_name`` so the replicated
        ``agent_state`` stays bit-identical across shards."""
        from scalerl_tpu.data.sharded_replay import seq_sample_sharded_local

        args = self.args
        axis = self.axis_name
        n = self.mesh.shape[axis]
        shard = jax.lax.axis_index(axis)
        key = jax.random.fold_in(key, shard)
        k_c, key = jax.random.split(key)
        carry, fields, entry_core = self._collect_impl(
            agent_state.params, carry, eps, k_c
        )
        B_l = fields["action"].shape[0]
        replay = seq_add(
            replay, fields, entry_core, jnp.full((B_l,), max_prio, jnp.float32)
        )
        local_cap = replay.priorities.shape[0]
        gsize = jax.lax.psum(replay.size, axis)
        metrics = {}
        for _ in range(args.train_intensity):  # static, small
            key, k_s = jax.random.split(key)
            f, c, idx, w = seq_sample_sharded_local(
                replay, k_s, args.batch_size // n,
                axes=(axis,), n_shards=n, local_capacity=local_cap,
                alpha=args.per_alpha, beta=args.per_beta, global_size=gsize,
                method=self._seq_method,
            )
            agent_state, metrics, new_prio = self._learn_shard(
                agent_state, f, c, w
            )
            # keep-empty form: a zero-weighted draw from a not-yet-filled
            # slot must not enter the distribution via its |TD| write-back
            replay = seq_update_priorities_keep_empty(
                replay, idx - shard * local_cap, new_prio
            )
            max_prio = jnp.maximum(
                max_prio, jax.lax.pmax(jnp.max(new_prio), axis)
            )
        return agent_state, replay, carry, max_prio, metrics

    def _collect_insert_local(self, params, replay, carry, max_prio, eps, key):
        """Per-shard warmup body: collect a chunk, insert into the local ring."""
        key = jax.random.fold_in(key, jax.lax.axis_index(self.axis_name))
        carry, fields, entry_core = self._collect_impl(params, carry, eps, key)
        B_l = fields["action"].shape[0]
        replay = seq_add(
            replay, fields, entry_core, jnp.full((B_l,), max_prio, jnp.float32)
        )
        return replay, carry

    def _build_sharded_fns(self, carry) -> None:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        axis = self.axis_name

        def leaf_spec(x):
            if getattr(x, "ndim", 0) >= 1:
                return P(axis, *([None] * (x.ndim - 1)))
            return P()  # replay cursors (pos/size) replicate

        replay_spec = jax.tree_util.tree_map(leaf_spec, self.replay)
        carry_spec = jax.tree_util.tree_map(leaf_spec, carry)
        # agent state / params / scalars / metrics: replicated (P() prefix)
        self._fused_iter = jax.jit(
            shard_map(
                self._fused_iter_local,
                mesh=self.mesh,
                in_specs=(P(), replay_spec, carry_spec, P(), P(), P()),
                out_specs=(P(), replay_spec, carry_spec, P(), P()),
                check_vma=False,
            ),
            donate_argnums=(0, 1, 2),
        )
        self._collect_insert = jax.jit(
            shard_map(
                self._collect_insert_local,
                mesh=self.mesh,
                in_specs=(P(), replay_spec, carry_spec, P(), P(), P()),
                out_specs=(replay_spec, carry_spec),
                check_vma=False,
            ),
            donate_argnums=(1, 2),
        )

    # ------------------------------------------------------------------
    def _eps(self, frames: int) -> float:
        """Linear decay 1.0 -> eps_base over the first 4x``warmup_sequences``
        INSERTED sequences, then constant eps_base (single-stream schedule;
        the actor-ladder eps_alpha applies to the host plane's many
        actors, not this one synchronized batch).

        Expressed in the same unit ``frames`` accrues in: each chunk adds
        ``rollout_length * num_envs`` frames and ``num_envs`` sequences, so
        one inserted sequence == ``rollout_length`` accrued frames and the
        horizon is exact for any ``num_envs`` (advisor r3).
        """
        horizon = max(self.args.warmup_sequences * 4 * self.args.rollout_length, 1)
        frac = min(frames / horizon, 1.0)
        return 1.0 + (self.args.eps_base - 1.0) * frac

    def train(self, total_frames: Optional[int] = None) -> Dict[str, float]:
        args = self.args
        total_frames = total_frames or args.max_timesteps
        B = self.venv.num_envs
        frames_per_chunk = args.rollout_length * B
        key = jax.random.PRNGKey(args.seed)
        key, k_init = jax.random.split(key)
        carry = self.init_carry(k_init)
        if self.mesh is not None and self._fused_iter is None:
            self._build_sharded_fns(carry)
        inserted = 0
        metrics: Dict = {}
        start = time.time()
        last_log = 0
        prev_sum = prev_cnt = 0.0
        windowed = float("nan")
        # final-window mark, independent of logger_frequency: the summary's
        # return_windowed covers the LAST quarter of training, never the
        # lifetime mean (which drags the eps=1 random warmup along)
        final_mark = None
        # the running max priority lives ON DEVICE for BOTH paths: it chains
        # through consecutive iterations without any host reduction — a
        # per-step float(jnp.max(...)) read would block the host on every
        # learn step (graftlint JG001); one explicit device_get at the end
        # of train() persists it back to the host mirror
        max_prio = jnp.asarray(self._max_priority, jnp.float32)
        # per-branch first-call flags: compilation may place host constants
        # on device, so only steady-state calls run under the transfer guard
        steady = {"warm": False, "cold": False}
        while self.env_frames < total_frames:
            key, k_c, k_s = jax.random.split(key, 3)
            # eps rides as a device scalar: uploading it here (outside the
            # guard) keeps the guarded fused dispatch free of implicit
            # host->device traffic
            eps = self._eps(self.env_frames)
            eps_dev = jnp.asarray(eps, jnp.float32)
            # count THIS iteration's insert: learning must start on the
            # iteration that reaches warmup (the pre-fusion semantics)
            warm = inserted + B >= args.warmup_sequences
            if self.fused:
                branch = "warm" if warm else "cold"
                guard = (
                    dispatch.steady_state_guard()
                    if steady[branch]
                    else nullcontext()
                )
                with guard:
                    if warm:
                        (
                            self.agent.state, self.replay, carry, max_prio, metrics
                        ) = self._fused_iter(
                            self.agent.state, self.replay, carry, max_prio,
                            eps_dev, k_c,
                        )
                    else:
                        self.replay, carry = self._collect_insert(
                            self.agent.state.params, self.replay, carry,
                            max_prio, eps_dev, k_c,
                        )
                steady[branch] = True
                self.env_frames += frames_per_chunk
                inserted += B
            else:
                carry, fields, entry_core = self._collect(
                    self.agent.state.params, carry, eps_dev, k_c
                )
                prio = jnp.full((B,), max_prio, jnp.float32)
                self.replay = seq_add(self.replay, fields, entry_core, prio)
                self.env_frames += frames_per_chunk
                inserted += B
                if warm:
                    for _ in range(args.train_intensity):
                        key, k_l = jax.random.split(key)
                        f, c, idx, w = seq_sample(
                            self.replay, k_l, args.batch_size,
                            alpha=args.per_alpha, beta=args.per_beta,
                            method=self._seq_method,
                        )
                        metrics, new_prio = self.agent.learn_sequences(f, c, w)
                        self.replay = seq_update_priorities(
                            self.replay, idx, new_prio
                        )
                        # async device-side reduction — no per-step host sync
                        max_prio = jnp.maximum(max_prio, jnp.max(new_prio))
            if final_mark is None and self.env_frames >= 0.75 * total_frames:
                # one batched transfer for the pair (not two blocking reads)
                mark = get_metrics(
                    {"s": jnp.sum(carry.return_sum),
                     "c": jnp.sum(carry.episode_count)}
                )
                final_mark = (mark["s"], mark["c"])
            if self.env_frames - last_log >= args.logger_frequency:
                last_log = self.env_frames
                # episode sums ride the same batched transfer as the learn
                # metrics: ONE device->host round trip per log boundary
                host = get_metrics(
                    {**metrics, "_ret_sum": jnp.sum(carry.return_sum),
                     "_ep_cnt": jnp.sum(carry.episode_count)}
                )
                s = host.pop("_ret_sum")
                c = host.pop("_ep_cnt")
                if host.get("skipped_steps", 0.0) > 0.0:
                    # the guarded learn skipped a non-finite update in the
                    # last fused iteration (flag rides the SAME batched
                    # transfer — no extra host sync to count it)
                    self.nonfinite_events += 1
                if c > prev_cnt:
                    # windowed: episodes completed since the previous log —
                    # the learning signal (the cumulative mean drags the
                    # random-policy prefix along forever)
                    windowed = (s - prev_sum) / (c - prev_cnt)
                    prev_sum, prev_cnt = s, c
                # registry-backed write path off the same host dict (the
                # guard counters fold into train.skipped_steps etc.);
                # per-chunk cadence, compiled out when telemetry is off
                if self._instrument:
                    telemetry.observe_train_metrics(host)
                    reg = telemetry.get_registry()
                    reg.set_gauges(
                        {**host, "return_windowed": windowed, "eps": eps},
                        prefix="train.",
                    )
                    self.logger.log_registry(
                        self.env_frames, step_type="train", include_prefixes=("train.",)
                    )
                if self.is_main_process:
                    self.text_logger.info(
                        f"frames {self.env_frames} | eps {eps:.2f} | "
                        f"return {windowed:.2f}"
                    )
        # persist the device-side running max across train() calls — ONE
        # explicit end-of-run transfer (both paths now keep it on device)
        self._max_priority = float(jax.device_get(max_prio))
        final = get_metrics(
            {**metrics, "_ret_sum": jnp.sum(carry.return_sum),
             "_ep_cnt": jnp.sum(carry.episode_count)}
        )
        s = final.pop("_ret_sum")
        c = final.pop("_ep_cnt")
        mark_s, mark_c = final_mark if final_mark is not None else (0.0, 0.0)
        if c > mark_c:
            windowed = (s - mark_s) / (c - mark_c)
        if final.get("skipped_steps", 0.0) > 0.0:
            self.nonfinite_events += 1
        sps = self.env_frames / max(time.time() - start, 1e-8)
        return {
            **final,
            "env_frames": float(self.env_frames),
            "sps": float(sps),
            "learn_steps": int(self.agent.state.step),
            "return_mean": s / max(c, 1.0),
            "return_windowed": windowed,
            "episodes": c,
            "nonfinite_events": float(self.nonfinite_events),
        }
