"""Ape-X: distributed prioritized experience replay (Horgan et al. 2018).

Parity target: the reference's Ape-X skeleton (``scalerl/algorithms/apex/
apex_train.py:11-93``, ``worker.py``, ``memory.py``) — N actor processes
writing TD-error-prioritized transitions into a shared PER, one learner
sampling with importance weights and feeding updated priorities back — which
is import-broken as shipped (SURVEY.md §2.4).  This is the working,
TPU-shaped version:

- **Actors** are threads each driving their own vector-env slab with
  per-actor epsilon ``eps_i = base^(1 + i/(N-1) * alpha)`` (the Ape-X
  exploration ladder; ``ApexArguments``).  Action selection is central
  batched inference on the device — not per-process CPU nets.
- Actors fold their rollout chunks into **n-step transitions locally**
  (the reference accumulates per-env deques in each actor,
  ``replay_buffer.py:230-273``) and compute **initial priorities** with a
  jitted |TD| function, then enqueue the slab.
- The **learner** thread is the single owner of the device PER state
  (one writer, no locks on HBM): it drains slabs into the prioritized
  buffer (``per_add_with_priorities``), samples with IS weights, runs the
  jitted double-DQN update, and scatters fresh priorities back — all
  device-side, no segment trees (SURVEY.md §7).
- Weights: in-process actors read the learner's latest params directly
  (zero-copy); a versioned ``ParameterServer`` snapshot is exported every
  ``actor_update_frequency`` learn steps for off-host actor fleets.
"""

from __future__ import annotations

import queue
import threading
import time
from contextlib import nullcontext
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from scalerl_tpu.agents.dqn import DQNAgent, make_dqn_learn_fn, make_dqn_priority_fn
from scalerl_tpu.config import ApexArguments
from scalerl_tpu.data.prioritized import PrioritizedReplayBuffer
from scalerl_tpu.runtime import telemetry
from scalerl_tpu.runtime.dispatch import get_metrics
from scalerl_tpu.runtime.param_server import ParameterServer
from scalerl_tpu.runtime.supervisor import (
    CheckpointCadence,
    StallWatchdog,
)
from scalerl_tpu.trainer.base import BaseTrainer
from scalerl_tpu.utils.metrics import EpisodeMetrics
from scalerl_tpu.utils.schedulers import LinearDecayScheduler
from scalerl_tpu.utils.timers import Timings


def fold_n_step(
    obs: np.ndarray,  # [T, W, ...]
    action: np.ndarray,  # [T, W]
    reward: np.ndarray,  # [T, W]
    next_obs: np.ndarray,  # [T, W, ...]
    term: np.ndarray,  # [T, W] bool: episode terminated (no bootstrap)
    trunc: np.ndarray,  # [T, W] bool: episode truncated (bootstrap, no reward leak)
    gamma: float,
    n: int,
) -> Dict[str, np.ndarray]:
    """Fold a rollout chunk into [(T-n+1)*W] n-step transitions (host side).

    Window semantics match ``data.replay.n_step_fold`` extended with
    truncation: rewards accumulate up to and including the first episode
    boundary (termination OR truncation — never across an autoreset into
    the next episode); ``next_obs`` bootstraps from that boundary step
    (for truncation this is the stashed final observation); ``done`` is
    True only for *termination* (a truncated window still bootstraps);
    ``n_steps`` is the realized window length for the ``gamma**n`` discount.
    """
    T, W = reward.shape[:2]
    m = T - n + 1
    if m <= 0:
        raise ValueError(f"rollout of {T} steps cannot fold n_step={n} windows")
    stop = term | trunc  # any episode boundary cuts the window
    stopf = stop.astype(np.float32)
    out_r = np.zeros((m, W), np.float32)
    alive = np.ones((m, W), np.float32)
    last = np.full((m, W), n - 1, np.int64)
    stop_found = np.zeros((m, W), bool)
    for k in range(n):
        out_r += (gamma**k) * alive * reward[k : k + m]
        s_k = stop[k : k + m]
        newly = s_k & ~stop_found
        last[newly] = k
        stop_found |= s_k
        alive *= 1.0 - stopf[k : k + m]
    rows = np.arange(m)[:, None] + last  # [m, W] absolute step index
    cols = np.broadcast_to(np.arange(W), (m, W))
    done = term[rows, cols]  # terminated at the window end (no bootstrap)
    return {
        "obs": obs[:m].reshape((m * W,) + obs.shape[2:]),
        "action": action[:m].reshape(m * W),
        "reward": out_r.reshape(m * W),
        "next_obs": next_obs[rows, cols].reshape((m * W,) + next_obs.shape[2:]),
        "done": done.reshape(m * W),
        "n_steps": (last + 1).astype(np.int32).reshape(m * W),
    }


class _ApexActorThread(threading.Thread):
    """One actor: own env slab, own eps, own RNG; enqueues prioritized slabs."""

    def __init__(self, actor_id: int, trainer: "ApexTrainer", envs) -> None:
        super().__init__(name=f"apex-actor-{actor_id}", daemon=True)
        self.actor_id = actor_id
        self.trainer = trainer
        self.envs = envs
        args = trainer.args
        n_actors = max(args.num_actors, 1)
        frac = actor_id / max(n_actors - 1, 1)
        self.eps = float(args.eps_greedy_base ** (1 + frac * args.eps_greedy_alpha))
        self.key = jax.random.PRNGKey(args.seed * 1000 + actor_id)
        self.timings = Timings()
        self.error: Optional[BaseException] = None

    def run(self) -> None:
        try:
            self._run()
        except BaseException as e:  # noqa: BLE001 - funneled to the learner
            self.error = e
            self.trainer._actor_error(self.actor_id, e)

    def _next_key(self) -> jax.Array:
        self.key, sub = jax.random.split(self.key)
        return sub

    def _run(self) -> None:
        tr = self.trainer
        args = tr.args
        agent = tr.agent
        T = args.rollout_length
        W = getattr(self.envs, "num_envs", 1)
        obs, _ = self.envs.reset(seed=args.seed + 7919 * self.actor_id)
        obs_dtype = np.asarray(obs).dtype

        while not tr._stop.is_set():
            obs_buf = np.zeros((T, W) + obs.shape[1:], obs_dtype)
            act_buf = np.zeros((T, W), np.int32)
            rew_buf = np.zeros((T, W), np.float32)
            next_buf = np.zeros((T, W) + obs.shape[1:], obs_dtype)
            term_buf = np.zeros((T, W), bool)
            trunc_buf = np.zeros((T, W), bool)
            self.timings.reset()
            for t in range(T):
                with tr._dispatch_guard():
                    actions = np.asarray(
                        agent._act(
                            agent.state.params,
                            jnp.asarray(obs, jnp.float32),
                            self.eps,
                            self._next_key(),
                        )
                    )
                next_obs, reward, term, trunc, infos = self.envs.step(actions)
                real_next = np.asarray(next_obs).copy()
                final_obs = infos.get("final_obs") if isinstance(infos, dict) else None
                if final_obs is not None:
                    for i in np.nonzero(infos.get("_final_obs"))[0]:
                        real_next[i] = final_obs[i]
                obs_buf[t] = obs
                act_buf[t] = actions
                rew_buf[t] = reward
                next_buf[t] = real_next
                term_buf[t] = term
                trunc_buf[t] = trunc
                tr.metrics.step(reward, np.logical_or(term, trunc), lane0=self.actor_id * W)
                obs = next_obs
            self.timings.time("rollout")
            slab = fold_n_step(
                obs_buf, act_buf, rew_buf, next_buf, term_buf, trunc_buf,
                args.gamma, args.n_steps,
            )
            self.timings.time("fold")
            # one H2D upload: the device slab feeds both the priority
            # computation and (via the queue) the learner's PER insert
            dev_slab = {
                "obs": jnp.asarray(slab["obs"], jnp.float32),
                "next_obs": jnp.asarray(slab["next_obs"], jnp.float32),
                "action": jnp.asarray(slab["action"]),
                "reward": jnp.asarray(slab["reward"]),
                "done": jnp.asarray(slab["done"]),
                "n_steps": jnp.asarray(slab["n_steps"]),
            }
            with tr._dispatch_guard():
                st = agent.state  # one snapshot: params/target_params stay paired
                prio = tr._priority(
                    st.params,
                    st.target_params,
                    dev_slab["obs"],
                    dev_slab["action"],
                    dev_slab["reward"],
                    dev_slab["next_obs"],
                    dev_slab["done"],
                    dev_slab["n_steps"],
                )
                if tr._mesh_lock is not None:
                    # drain before releasing the lock: a meshed priority
                    # program still in flight while the learner enqueues its
                    # own multi-device program re-opens the ordering hazard
                    prio.block_until_ready()
            self.timings.time("priority")
            # stop-aware put: if the learner exits while the queue is full,
            # a bare put() would deadlock this thread past teardown
            while not tr._stop.is_set():
                try:
                    tr._slab_queue.put((dev_slab, prio), timeout=1.0)
                    break
                except queue.Full:
                    continue
            self.timings.time("enqueue")
            with tr._step_lock:
                tr.global_step += T * W


class ApexTrainer(BaseTrainer):
    """N prioritized actors + one PER learner (``apex_train.py:64-93``)."""

    def __init__(
        self,
        args: ApexArguments,
        agent: DQNAgent,
        make_envs,  # callable (actor_id) -> vector env for that actor
        eval_envs=None,
        run_name: Optional[str] = None,
    ) -> None:
        super().__init__(args, run_name=run_name)
        args.validate()
        if getattr(args, "categorical_dqn", False):
            raise ValueError(
                "categorical_dqn (C51) is not supported by ApexTrainer: its "
                "priority/learn paths are scalar-Q "
                "(make_dqn_priority_fn/make_dqn_learn_fn); use DQNAgent with "
                "OffPolicyTrainer for C51"
            )
        self.agent = agent
        self.eval_envs = eval_envs
        self._actor_envs = [make_envs(i) for i in range(args.num_actors)]
        env0 = self._actor_envs[0]
        self.envs_per_actor = getattr(env0, "num_envs", 1)
        obs_space = env0.single_observation_space

        # folded slabs arrive with their realized window length stored; the
        # buffer row width is one slab, so capacity (in transitions) converts
        # to rows.  n_step=1: windows never span interleaved actor slabs.
        slab_width = (args.rollout_length - args.n_steps + 1) * self.envs_per_actor
        buffer_kw = dict(
            obs_shape=obs_space.shape,
            capacity=max(args.buffer_size // slab_width, 2),
            num_envs=slab_width,
            alpha=args.per_alpha,
            n_step=1,  # transitions are pre-folded by the actors
            gamma=args.gamma,
            extra_fields={"n_steps": ((), jnp.int32)},
        )
        mesh = getattr(agent, "mesh", None)
        if mesh is not None:
            # pod-scale Ape-X (the BASELINE "replay sharded across TPU HBM"
            # row): the PER planes shard over the learner's dp/fsdp axes and
            # the per-shard stratified sample lands already laid out for the
            # mesh learn step — agent._shard_batch's device_put is a no-op
            from scalerl_tpu.data.sharded_replay import ShardedPrioritizedReplay

            if getattr(agent, "_donate_state", False):
                # the mesh learn step donates the train state by default,
                # but actor threads read state.params concurrently (the
                # same hazard the no-donation re-jit of agent._learn below
                # guards) — rebuild the pjit'd learner without donation
                from scalerl_tpu.parallel import enable_offpolicy_mesh

                agent._donate_state = False
                enable_offpolicy_mesh(agent, mesh, donate_state=False)

            self.buffer = ShardedPrioritizedReplay(mesh=mesh, **buffer_kw)
        else:
            self.buffer = PrioritizedReplayBuffer(**buffer_kw)
        # Meshed state makes EVERY jitted call here (actor _act, priority,
        # learn, PER insert/sample) a multi-device program.  XLA runs each
        # device's queue in enqueue order, so two threads dispatching
        # multi-device programs concurrently can enqueue them in different
        # orders on different devices and deadlock the whole client — the
        # exact wedge the seed suite hit in
        # test_apex_sharded_replay_mesh_e2e (actors inside _act, learner
        # inside the pjit'd add_with_priorities, forever).  One lock around
        # every dispatch site serializes enqueue ordering; single-device
        # runs keep the lock-free fast path.
        self._mesh_lock: Optional[threading.Lock] = (
            threading.Lock() if mesh is not None else None
        )
        self._priority = jax.jit(
            make_dqn_priority_fn(agent.network, args.gamma, args.double_dqn)
        )
        # re-jit the agent's learn WITHOUT state donation: actor threads read
        # state.params concurrently, and donation would free those buffers
        # mid-read (DQNAgent defaults to donating for the single-threaded
        # off-policy trainer)
        from scalerl_tpu.parallel.train_step import maybe_guard_nonfinite

        agent._learn = jax.jit(
            # re-apply the all-finite guard: this re-jit replaces the
            # agent's (already guarded) learn, and Ape-X must keep the same
            # skip-non-finite-updates contract
            maybe_guard_nonfinite(
                make_dqn_learn_fn(
                    agent.network,
                    agent.optimizer,
                    gamma=args.gamma,
                    n_step=args.n_steps,
                    double_dqn=args.double_dqn,
                    use_soft_update=args.use_soft_update,
                    soft_update_tau=args.soft_update_tau,
                    target_update_frequency=args.target_update_frequency,
                ),
                args,
            )
        )
        self.per_beta = LinearDecayScheduler(
            args.per_beta, args.per_beta_final, args.max_timesteps
        )
        self.param_server = ParameterServer()
        self.param_server.push(agent.get_weights())

        self._slab_queue: "queue.Queue" = queue.Queue(maxsize=4 * args.num_actors)
        self._stop = threading.Event()
        self._step_lock = threading.Lock()
        self._errors: "queue.Queue" = queue.Queue()
        self.global_step = 0
        self.learn_steps = 0
        self.metrics = EpisodeMetrics(args.num_actors * self.envs_per_actor)
        self.timings = Timings()

    # ------------------------------------------------------------------
    def _dispatch_guard(self):
        """Serialize multi-device dispatch under a mesh (see __init__)."""
        return self._mesh_lock if self._mesh_lock is not None else nullcontext()

    def _actor_error(self, actor_id: int, err: BaseException) -> None:
        self._errors.put((actor_id, err))

    def _drain_slabs(self, block: bool) -> int:
        """Move pending actor slabs into the device PER (single writer)."""
        drained = 0
        while True:
            try:
                slab, prio = self._slab_queue.get(block=block and drained == 0, timeout=1.0)
            except queue.Empty:
                break
            with self._dispatch_guard():
                self.buffer.add_with_priorities(slab, prio)
            self.timings.time("insert")
            drained += 1
            block = False
        return drained

    def train_step(self) -> Dict[str, float]:
        beta = self.per_beta.value(self.global_step)
        self.timings.reset()
        with self._dispatch_guard():
            batch = self.buffer.sample(self.args.batch_size, beta=beta)
            self.timings.time("sample")
            info = self.agent.learn(batch)
            self.timings.time("learn")
            self.buffer.update_priorities(batch["indices"], info["td_abs"] + 1e-6)
            self.timings.time("update_prio")
        info.pop("td_abs", None)
        self.learn_steps += 1
        if self.learn_steps % self.args.actor_update_frequency == 0:
            self.param_server.push(self.agent.get_weights())
        return info

    # -- resume --------------------------------------------------------
    def _resume_pytree(self) -> Dict:
        return {
            "agent": self.agent.state,
            "replay": self.buffer.state,
            "global_step": np.asarray(self.global_step, np.int64),
            "learn_steps": np.asarray(self.learn_steps, np.int64),
        }

    def save_resume(self) -> None:
        self.save_resume_checkpoint(
            self._resume_pytree(), self.global_step, self.learn_steps
        )

    def try_resume(self) -> bool:
        """Restore learner state, the FULL prioritized replay (sharded or
        not — losing it would cost warmup plus every learned priority),
        and counters; re-lays arrays out on the mesh when one is active."""
        state = self.load_resume_checkpoint(self._resume_pytree())
        if state is None:
            return False
        agent_state = state["agent"]
        replay_state = state["replay"]
        mesh_learn = getattr(self.agent, "_learn_mesh", None)
        if mesh_learn is not None:
            agent_state = jax.device_put(agent_state, mesh_learn.state_sharding)
        if hasattr(self.buffer, "_state_sh"):
            replay_state = jax.device_put(replay_state, self.buffer._state_sh)
        self.agent.state = agent_state
        self.buffer.state = replay_state
        self.global_step = int(state["global_step"])
        self.learn_steps = int(state["learn_steps"])
        self.param_server.push(self.agent.get_weights())
        if self.is_main_process:
            self.text_logger.info(
                f"resumed from {self.resume_ckpt_path}: step {self.global_step}"
            )
        return True

    def run_evaluate_episodes(self, n_episodes: Optional[int] = None) -> Dict[str, float]:
        envs = self.eval_envs
        if envs is None:
            return {}
        n_episodes = n_episodes or self.args.eval_episodes
        num_envs = getattr(envs, "num_envs", 1)
        obs, _ = envs.reset(seed=self.args.seed + 100)
        returns: list = []
        ep_ret = np.zeros(num_envs)
        prev_done = np.ones(num_envs, bool)
        while len(returns) < n_episodes:
            with self._dispatch_guard():  # actors dispatch concurrently
                actions = self.agent.predict(obs, done=prev_done)
            obs, reward, term, trunc, _ = envs.step(np.asarray(actions))
            ep_ret += reward
            done = np.logical_or(term, trunc)
            prev_done = done
            for i in np.nonzero(done)[0]:
                returns.append(ep_ret[i])
                ep_ret[i] = 0.0
        rets = np.array(returns[:n_episodes])
        return {"reward_mean": float(rets.mean()), "reward_std": float(rets.std())}

    # ------------------------------------------------------------------
    def run(self) -> Dict[str, float]:
        args = self.args
        if self.resuming:
            self.try_resume()
        # preemption (SIGTERM/SIGINT) -> save_resume at the next loop
        # boundary; stall watchdog dumps all-thread stacks + queue depths
        # when neither env steps nor learn steps advance for the deadline
        guard = self.install_preemption_guard()
        watchdog: Optional[StallWatchdog] = None
        if args.watchdog_timeout_s > 0:
            watchdog = StallWatchdog(args.watchdog_timeout_s, name="apex")
            watchdog.watch("global_step", lambda: self.global_step)
            watchdog.watch("learn_steps", lambda: self.learn_steps)
            watchdog.add_probe("slab_queue_depth", self._slab_queue.qsize)
            watchdog.add_probe("replay_size", lambda: len(self.buffer))
            watchdog.add_probe(
                "actor_errors_pending", lambda: self._errors.qsize()
            )
            watchdog.start()
        actors = [
            _ApexActorThread(i, self, env) for i, env in enumerate(self._actor_envs)
        ]
        for a in actors:
            a.start()

        start = time.time()
        # seed the interval gates from the (possibly resumed) step, or the
        # first iteration immediately fires a log line and a full blocking
        # eval sweep at the restored step
        last_log = self.global_step
        last_eval = self.global_step
        cadence = CheckpointCadence(
            args.save_frequency, args.checkpoint_interval_s, self.global_step
        )
        train_info: Dict[str, float] = {}
        try:
            while self.global_step < args.max_timesteps:
                if watchdog is not None:
                    watchdog.check()
                if guard is not None and guard.triggered:
                    if args.save_model and not args.disable_checkpoint:
                        self.save_resume()
                    break
                if not self._errors.empty():
                    actor_id, err = self._errors.get()
                    raise RuntimeError(f"apex actor {actor_id} crashed") from err
                self._drain_slabs(block=True)
                if len(self.buffer) >= args.warmup_learn_steps:
                    train_info = self.train_step()

                if self.global_step - last_log >= args.logger_frequency:
                    last_log = self.global_step
                    fps = int(self.global_step / max(time.time() - start, 1e-8))
                    summary = self.metrics.summary()
                    # registry-backed write: one batched transfer for any
                    # device scalars, then instruments are the source
                    train_info = get_metrics(train_info)
                    if self._instrument:
                        telemetry.observe_train_metrics(train_info)
                        reg = telemetry.get_registry()
                        reg.set_gauges(train_info, prefix="train.")
                        reg.set_gauges(summary, prefix="train.")
                        reg.set_gauges(
                            {
                                "rpm_size": float(len(self.buffer)),
                                "fps": float(fps),
                                "learn_steps": float(self.learn_steps),
                                "weight_version": float(self.param_server.version),
                            },
                            prefix="train.",
                        )
                        self.logger.log_registry(
                            self.global_step,
                            step_type="train",
                            include_prefixes=("train.",),
                        )
                    if self.is_main_process:
                        ret = summary.get("return_mean", float("nan"))
                        self.text_logger.info(
                            f"step {self.global_step} | fps {fps} | return {ret:.1f} "
                            f"| loss {train_info.get('loss', float('nan')):.4f} "
                            f"| learn {self.learn_steps}"
                        )

                if self.eval_envs is not None and self.global_step - last_eval >= args.eval_frequency:
                    last_eval = self.global_step
                    eval_info = self.run_evaluate_episodes()
                    self.logger.log_test_data(eval_info, self.global_step)

                if (
                    args.save_model
                    and not args.disable_checkpoint
                    and cadence.due(self.global_step)
                ):
                    cadence.mark_saved(self.global_step)
                    self.save_resume()
        finally:
            self._stop.set()
            if watchdog is not None:
                watchdog.stop()
            if guard is not None:
                guard.restore()
            for a in actors:
                a.join(timeout=10.0)
            if args.save_model and not args.disable_checkpoint and self.is_main_process:
                self.agent.save_checkpoint(f"{self.model_save_dir}/ckpt_final")
        return self.metrics.summary()

    def close(self) -> None:
        self._stop.set()
        for envs in self._actor_envs:
            try:
                envs.close()
            except Exception:
                pass
        super().close()
