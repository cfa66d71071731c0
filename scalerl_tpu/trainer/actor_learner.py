"""Actor-learner trainers: host actor plane (SEED-style) and fused device loop.

Parity target: ``ImpalaTrainer`` (``scalerl/algorithms/impala/impala_atari.py:
40-521``), re-architected per SURVEY.md §7:

- **HostActorLearnerTrainer** — CPU actors run *envs only*; every neural-net
  forward (acting inference) is a central jitted batched call on the device
  (SEED-RL topology), unlike the reference where each actor process runs its
  own CPU model copy (``impala_atari.py:196-198``).  Actor threads each
  drive a vector-env slab, fill pinned trajectory slots from a free/full
  ``RolloutQueue``, and the learner thread drains, ships, and updates.
  Weight "publication" is implicit: central inference always reads the
  learner's latest params (behavior lag <= one chunk), and a
  ``ParameterServer`` snapshot is exported for off-host actors.
- **DeviceActorLearnerTrainer** — the fully-fused path for device-native
  envs (``runtime/device_loop.py``); orders of magnitude faster when env
  dynamics compile.

Failure handling parity (SURVEY.md §5): actor exceptions funnel through
``RolloutQueue.report_error`` and re-raise in the learner; teardown joins
with timeouts (reference ladders: ``impala_atari.py:473-494``).
"""

from __future__ import annotations

import queue as queue_mod
import threading
import time
from contextlib import nullcontext
from typing import Dict, Optional

import jax
import numpy as np

from scalerl_tpu.agents.impala import ImpalaAgent
from scalerl_tpu.config import ImpalaArguments
from scalerl_tpu.data.trajectory import TrajectorySpec, batch_to_trajectory
from scalerl_tpu.runtime import telemetry
from scalerl_tpu.runtime.dispatch import get_metrics
from scalerl_tpu.runtime.param_server import ParameterServer
from scalerl_tpu.runtime.rollout_queue import RolloutQueue
from scalerl_tpu.runtime.supervisor import (
    CheckpointCadence,
    StallWatchdog,
)
from scalerl_tpu.trainer.base import BaseTrainer
from scalerl_tpu.utils.metrics import EpisodeMetrics
from scalerl_tpu.utils.profiling import maybe_trace
from scalerl_tpu.utils.timers import Timings


def fill_rollout_slot(
    slot,
    agent,
    envs,
    obs,
    last_action,
    reward,
    done,
    core_state,
    unroll_length: int,
    on_step=None,
    timings: Optional[Timings] = None,
    dispatch_guard=None,
):
    """Write one ``[T+1, B]`` trajectory slot — the protocol shared by the
    thread (SEED) and process (monobeast) actor planes.

    Row convention matches ``data/trajectory.py``: each row holds the model
    *inputs* at that step; row T is model-input-only — the learner reads
    ``logits[:-1]`` and the boundary obs is consumed by the next chunk's
    row 0, so running inference there would advance the LSTM core over
    ``obs_T`` twice (slots are recycled, so its stale logits row is cleared).

    Returns the carried ``(obs, last_action, reward, done, core_state)``.
    ``on_step(reward, done)`` fires after every env step (episode metrics);
    ``timings`` (optional) records the ``model``/``step`` phase split.
    ``dispatch_guard`` (optional, a context-manager factory): entered around
    each central-inference call — the thread planes pass the trainer's mesh
    dispatch guard so actor-thread dispatch cannot interleave multi-device
    program enqueues with the learner's (graftlint JG002).
    """
    _dispatch_guard = dispatch_guard if dispatch_guard is not None else nullcontext
    for i, (c, h) in enumerate(core_state):
        slot[f"core_{i}_c"][:] = np.asarray(c)
        slot[f"core_{i}_h"][:] = np.asarray(h)
    for t in range(unroll_length + 1):
        slot["obs"][t] = obs
        slot["action"][t] = last_action
        slot["reward"][t] = reward
        slot["done"][t] = done
        if timings is not None:
            # separate mark: the obs row memcpy is the dominant write cost
            # at pixel shapes and must not be attributed to "model"
            timings.time("write_row")
        if t == unroll_length:
            slot["logits"][t] = 0.0
            break
        with _dispatch_guard():
            action, logits, core_state = agent.act(
                obs, last_action, reward, done, core_state
            )
        slot["logits"][t] = np.asarray(logits)
        if timings is not None:
            timings.time("model")
        obs, reward, term, trunc, _ = envs.step(np.asarray(action))
        done = np.logical_or(term, trunc)
        reward = np.asarray(reward, np.float32)
        last_action = np.asarray(action, np.int32)
        if on_step is not None:
            on_step(reward, done)
        if timings is not None:
            timings.time("step")
    return obs, last_action, reward, done, core_state


class _ActorThread(threading.Thread):
    """One actor: owns a vector-env slab, fills trajectory slots."""

    def __init__(
        self,
        actor_id: int,
        trainer,
        envs,
        policy=None,
    ) -> None:
        """``policy``: the acting facade (``act`` + ``initial_state``);
        defaults to ``trainer.agent`` (IMPALA central inference).  R2D2
        passes per-actor eps-greedy views so each actor gets its own rung
        of the Ape-X exploration ladder."""
        super().__init__(name=f"actor-{actor_id}", daemon=True)
        self.actor_id = actor_id
        self.trainer = trainer
        self.envs = envs
        self.policy = policy if policy is not None else trainer.agent
        self.timings = Timings()

    def run(self) -> None:
        tr = self.trainer
        q = tr.queue
        while True:
            try:
                self._act_loop()
                return
            except Exception as e:  # noqa: BLE001 - restart or funnel
                if not tr.grant_actor_restart(self.actor_id, e):
                    q.report_error(e)
                    return
                # the env stack is suspect after a crash (a dead subprocess
                # env can't step again): rebuild it from the factory
                try:
                    self.envs.close()
                except Exception:  # noqa: BLE001 - already broken
                    pass
                try:
                    self.envs = tr.env_fns[self.actor_id]()
                except Exception as rebuild_err:  # noqa: BLE001
                    q.report_error(rebuild_err)
                    return

    def _act_loop(self) -> None:
        tr = self.trainer
        agent = self.policy
        # remote policies (serving plane) are host IO: entering the mesh
        # dispatch guard around them would serialize the learner against
        # network latency for no safety gain (the InferenceServer holds
        # the guard around its own device dispatch)
        dispatch_guard = (
            None
            if getattr(agent, "_remote_policy", False)
            else getattr(tr, "_dispatch_guard", None)
        )
        q = tr.queue
        T = tr.args.rollout_length
        B = self.envs.num_envs
        obs, _ = self.envs.reset(seed=tr.args.seed + 1000 * self.actor_id)
        last_action = np.zeros(B, np.int32)
        reward = np.zeros(B, np.float32)
        done = np.ones(B, bool)
        core_state = agent.initial_state(B)
        metrics = tr.episode_metrics[self.actor_id]
        while not tr.stop_event.is_set():
            idx = q.acquire(timeout=1.0)
            if idx is None:
                continue
            self.timings.reset()
            committed = False
            try:
                obs, last_action, reward, done, core_state = fill_rollout_slot(
                    q.slots[idx],
                    agent,  # central batched inference on device
                    self.envs,
                    obs,
                    last_action,
                    reward,
                    done,
                    core_state,
                    T,
                    on_step=metrics.step,
                    timings=self.timings,
                    dispatch_guard=dispatch_guard,
                )
                q.commit(idx)
                committed = True
            except BaseException:
                # crash mid-fill: the acquired slot was never committed —
                # hand it back or the pool shrinks one slot per restart
                # until acquire() starves
                if not committed:
                    q.recycle([idx])
                raise
            self.timings.time("write")
            with tr.frame_lock:
                tr.env_frames += T * B


class HostPlaneMixin:
    """Shared scaffolding for host actor-plane trainers (IMPALA threads,
    R2D2): the elastic-actor restart budget and the agent-state resume
    trio.  ONE implementation — a fix to restart accounting or checkpoint
    shape must not have to be mirrored between planes.

    Expects the trainer to define: ``agent`` / ``env_frames`` /
    ``param_server`` / ``max_actor_restarts`` / ``actor_restarts`` /
    ``_restart_lock`` / ``_mesh_lock`` plus BaseTrainer's resume plumbing.
    """

    def _dispatch_guard(self):
        """Serialize multi-device dispatch when the agent is meshed.

        Same hazard ApexTrainer locks against (the PR 2
        ``test_apex_sharded_replay_mesh_e2e`` deadlock): with
        ``agent.enable_mesh`` active, actor threads' central inference and
        the learner's update are all multi-device programs; two threads
        enqueueing them concurrently can order the per-device queues
        differently and wedge the whole XLA client.  One lock around every
        dispatch site serializes enqueue order; single-device runs keep the
        lock-free fast path (the mesh check is a cheap attribute read).
        """
        if (
            getattr(self.agent, "mesh", None) is not None
            or getattr(self.agent, "_learn_mesh", None) is not None
        ):
            return self._mesh_lock
        return nullcontext()

    def grant_actor_restart(self, actor_id: int, exc: BaseException) -> bool:
        """Consume one unit of the elastic-actor budget; False = fail fast."""
        with self._restart_lock:
            if self.actor_restarts >= self.max_actor_restarts:
                return False
            self.actor_restarts += 1
            used = self.actor_restarts
        if self.is_main_process:
            self.text_logger.warning(
                f"actor {actor_id} crashed ({type(exc).__name__}: {exc}); "
                f"rebuilding its envs (restart {used}/{self.max_actor_restarts})"
            )
        return True

    def _resume_pytree(self) -> Dict:
        return {
            "agent": self.agent.state,
            "env_frames": np.asarray(self.env_frames, np.int64),
        }

    def save_resume(self) -> None:
        self.save_resume_checkpoint(
            self._resume_pytree(), self.env_frames, int(self.agent.state.step)
        )

    def try_resume(self) -> bool:
        """Restore learner state + frame counter (parity: the reference's
        IMPALA 10-min checkpoints, ``impala_atari.py:460-469,496-515`` —
        which it saved but never wired a restore for)."""
        state = self.load_resume_checkpoint(self._resume_pytree())
        if state is None:
            return False
        self.agent.state = state["agent"]
        self.env_frames = int(state["env_frames"])
        self.param_server.push(self.agent.get_weights())
        if self.is_main_process:
            self.text_logger.info(
                f"resumed from {self.resume_ckpt_path}: frames {self.env_frames}"
            )
        return True



def check_queue_depth(args, envs_per_actor: int) -> None:
    """Slot-aware queue floor (the check config.validate cannot do: it
    needs the env fleet shape).  ``num_buffers`` counts SLOTS of
    ``envs_per_actor`` lanes; one learn step drains
    ``batch_size / envs_per_actor`` slots, and queue depth is worst-case
    policy lag in learner steps x drained slots — deeper queues do not add
    throughput once every actor can hold a free slot, they only add
    staleness (the host-plane Breakout stall, round 4)."""
    n_slots = max(args.batch_size // envs_per_actor, 1)
    floor = max(2 * n_slots, args.num_actors)
    if args.num_buffers < floor:
        raise ValueError(
            f"num_buffers ({args.num_buffers} slots of {envs_per_actor} "
            f"lanes) must be at least max(2 * batch_size/envs_per_actor, "
            f"num_actors) = {floor} so the learner can drain a full batch "
            "while every actor holds a slot"
        )


class HostActorLearnerTrainer(HostPlaneMixin, BaseTrainer):
    def __init__(
        self,
        args: ImpalaArguments,
        agent: ImpalaAgent,
        env_fns,  # list of callables, one vector env per actor
        run_name: Optional[str] = None,
        max_actor_restarts: int = 0,
    ) -> None:
        """``max_actor_restarts``: elastic actors (beyond the reference's
        fail-fast funnels).  An actor thread that crashes — typically a
        dead env subprocess — rebuilds its env stack from ``env_fns`` and
        resumes, up to this many times across all actors; the learner sees
        a throughput dip, not a dead run.  0 keeps fail-fast (the crash
        re-raises in the learner via the rollout queue's error funnel)."""
        super().__init__(args, run_name=run_name)
        self.agent = agent
        # dp×mp sharded learner hookup: RLArguments.{mesh_shape,dp_size,
        # mp_size} resolve to agent.enable_mesh before any actor thread
        # starts (idempotent; the mesh dispatch guard below covers the
        # resulting multi-device dispatch sites)
        from scalerl_tpu.parallel.train_step import maybe_enable_mesh_from_args

        maybe_enable_mesh_from_args(agent, args)
        self.env_fns = env_fns
        self.stop_event = threading.Event()
        self.frame_lock = threading.Lock()
        self.env_frames = 0
        self.max_actor_restarts = max_actor_restarts
        self.actor_restarts = 0
        self._restart_lock = threading.Lock()
        # serializes multi-device dispatch under agent.enable_mesh — see
        # HostPlaneMixin._dispatch_guard
        self._mesh_lock = threading.Lock()
        self.param_server = ParameterServer()

        probe_env = env_fns[0]()
        self.envs_per_actor = probe_env.num_envs
        obs_shape = probe_env.single_observation_space.shape
        num_actions = probe_env.single_action_space.n
        self._probe_env = probe_env

        core = agent.initial_state(self.envs_per_actor)
        self.spec = TrajectorySpec(
            unroll_length=args.rollout_length,
            batch_size=self.envs_per_actor,
            obs_shape=obs_shape,
            num_actions=num_actions,
            obs_dtype=jax.numpy.float32 if len(obs_shape) == 1 else jax.numpy.uint8,
            core_state_shapes=tuple(tuple(c.shape) for c, _ in core),
        )
        check_queue_depth(args, self.envs_per_actor)
        self.queue = RolloutQueue(self.spec, num_slots=args.num_buffers)
        self.episode_metrics = [
            EpisodeMetrics(self.envs_per_actor) for _ in range(len(env_fns))
        ]
        self.learn_timings = Timings()

        # actor_mode="serving": the full centralized inference plane — the
        # ONE hot policy lives in an InferenceServer (dynamic batcher,
        # generation tags, SLO telemetry) and actor threads act through
        # RemotePolicyClients over in-process codec links, exactly the wire
        # shape remote env-shell hosts speak over sockets.  The agent
        # doubles as each client's local fallback, so a dead server
        # degrades the run to the thread topology instead of killing it.
        self.inference_server = None
        self._serving_clients: list = []
        if getattr(args, "actor_mode", "threads") == "serving":
            from scalerl_tpu.serving import (
                InferenceServer,
                RemotePolicyClient,
                ServingConfig,
                local_pair,
            )

            self.inference_server = InferenceServer(
                agent,
                ServingConfig.from_args(args),
                dispatch_guard=self._dispatch_guard,
            )
            self.inference_server.start()
            for _ in env_fns:
                client_end, server_end = local_pair()
                self.inference_server.add_connection(server_end)
                self._serving_clients.append(
                    RemotePolicyClient(
                        conn=client_end,
                        fallback=agent,
                        dispatch_guard=self._dispatch_guard,
                    )
                )

    # grant_actor_restart / _resume_pytree / save_resume / try_resume come
    # from HostPlaneMixin (shared with the R2D2 plane)

    def _assemble_batch(self, n_slots: int, timings: Optional[Timings] = None):
        """Drain ``n_slots`` full slots into one device trajectory — the
        single assembly path for both the inline learner loop and the
        prefetch threads."""
        batch, idxs = self.queue.get_batch(n_slots)
        if timings is not None:
            timings.time("dequeue")
        traj = batch_to_trajectory(batch)
        self.queue.recycle(idxs)
        if timings is not None:
            timings.time("device")
        return traj

    def train(self, total_frames: Optional[int] = None) -> Dict[str, float]:
        args = self.args
        total_frames = total_frames or args.total_steps
        if self.resuming:
            self.try_resume()
        actors = []
        for i, fn in enumerate(self.env_fns):
            envs = self._probe_env if i == 0 else fn()
            policy = self._serving_clients[i] if self._serving_clients else None
            actors.append(_ActorThread(i, self, envs, policy=policy))
        self.actors = actors  # exposed for phase-timing inspection (bench)
        # supervision: SIGTERM/SIGINT -> save_resume at the next learn-step
        # boundary; watchdog dumps all-thread stacks + queue occupancy when
        # neither env frames nor learn steps advance for the deadline.
        # Installed after env construction so a failing factory cannot leak
        # signal handlers (the finally below owns the teardown).
        guard = self.install_preemption_guard()
        watchdog: Optional[StallWatchdog] = None
        learn_progress = None
        if args.watchdog_timeout_s > 0:
            watchdog = StallWatchdog(
                args.watchdog_timeout_s, name="host-actor-learner"
            )
            watchdog.watch("env_frames", lambda: self.env_frames)
            learn_progress = watchdog.counter("learn_steps")
            watchdog.add_probe("rollout_queue", self.queue.stats)
            watchdog.add_probe("actor_restarts", lambda: self.actor_restarts)
            watchdog.start()
        for a in actors:
            a.start()

        start = time.time()
        start_frames = self.env_frames  # nonzero after resume
        last_log_frames = start_frames
        # elasticity signals: the autoscaler's documented inputs (rates.fps
        # / rates.learn_steps_per_s, docs/OBSERVABILITY.md) are fed with
        # interval deltas at the log boundary — per-chunk cadence, and
        # telemetry-off compiles the marks out entirely
        fps_meter = learn_meter = None
        if self._instrument:
            _reg = telemetry.get_registry()
            fps_meter = _reg.meter("rates.fps")
            learn_meter = _reg.meter("rates.learn_steps_per_s")
        meter_frames = start_frames
        meter_steps = 0
        cadence = CheckpointCadence(
            args.save_frequency, args.checkpoint_interval_s, start_frames
        )
        n_slots = max(args.batch_size // self.envs_per_actor, 1)
        metrics: Dict = {}
        learn_steps_done = 0  # host-side counter (no device sync)

        # Optional assembly prefetch (wires the reference's num_learners
        # knob, ``impala_atari.py:439-456``): num_learner_threads - 1
        # assembly threads drain slots and build trajectories while the
        # device runs the previous learn step, so the TPU never waits on
        # host batch stitching (the learn step itself stays one thread —
        # it is a single jitted call and parallelizing it adds nothing)
        prefetch_q: Optional[queue_mod.Queue] = None
        assemble_threads: list = []
        if args.num_learner_threads >= 2:
            prefetch_q = queue_mod.Queue(maxsize=2)

            def _put(item) -> bool:
                # bounded put that gives up at shutdown: an unconditional
                # put() would block forever when the main loop exits with
                # the queue full, leaking the thread and a pinned batch
                while True:
                    try:
                        prefetch_q.put(item, timeout=0.5)
                        return True
                    except queue_mod.Full:
                        if self.stop_event.is_set():
                            return False

            def _assemble() -> None:
                try:
                    while not self.stop_event.is_set():
                        if not _put(self._assemble_batch(n_slots)):
                            return
                except BaseException as e:  # noqa: BLE001 — re-raised below
                    _put(e)

            for i in range(args.num_learner_threads - 1):
                t = threading.Thread(
                    target=_assemble, name=f"learner-assemble-{i}", daemon=True
                )
                t.start()
                assemble_threads.append(t)

        def next_traj():
            if prefetch_q is None:
                self.learn_timings.reset()
                return self._assemble_batch(n_slots, timings=self.learn_timings)
            self.learn_timings.reset()
            while True:
                try:
                    item = prefetch_q.get(timeout=0.5)
                    break
                except queue_mod.Empty:
                    if self.stop_event.is_set():
                        raise RuntimeError("rollout queue closed")
            self.learn_timings.time("dequeue")
            if isinstance(item, BaseException):
                raise item
            return item

        try:
            while self.env_frames < total_frames and not self.stop_event.is_set():
                if watchdog is not None:
                    watchdog.check()
                if guard is not None and guard.triggered:
                    # preemption safe point: the previous learn step is
                    # complete, no slot is half-consumed
                    if args.save_model and not args.disable_checkpoint:
                        self.save_resume()
                    break
                traj = next_traj()
                # device metrics stay un-materialized: float() only at log
                # time, so the loop dispatches the next step without a sync.
                # Guarded: actor threads dispatch central inference
                # concurrently, and under enable_mesh both sides are
                # multi-device programs (HostPlaneMixin._dispatch_guard)
                with self._dispatch_guard():
                    metrics = self.agent.learn_device(traj)
                self.learn_timings.time("learn")
                learn_steps_done += 1
                if learn_progress is not None:
                    learn_progress.bump()
                # version bump only — actors do central inference on the
                # live device params; a to_host push would force a full
                # device->host param fetch (a sync) every learn step.  The
                # device-side snapshot copy is itself a program: guard it
                with self._dispatch_guard():
                    self.param_server.push(self.agent.get_weights(), to_host=False)
                    if self.inference_server is not None:
                        # serving plane: monotonic generation bump; every
                        # act reply from here on is tagged with the new
                        # generation (in-flight flushes keep their old tag)
                        self.inference_server.push_params(
                            self.agent.get_weights(),
                            learner_step=learn_steps_done,
                        )

                if (
                    args.save_model
                    and not args.disable_checkpoint
                    and cadence.due(self.env_frames)
                ):
                    cadence.mark_saved(self.env_frames)
                    self.save_resume()

                if self.env_frames - last_log_frames >= args.logger_frequency:
                    last_log_frames = self.env_frames
                    sps = (self.env_frames - start_frames) / max(
                        time.time() - start, 1e-8
                    )
                    rets = [
                        r
                        for m in self.episode_metrics
                        for r in m.episode_returns[-20:]
                    ]
                    ret_mean = float(np.mean(rets)) if rets else float("nan")
                    # one batched device->host transfer for the whole dict
                    # (per-key float() would pay a round trip per metric)
                    host_metrics = get_metrics(metrics)
                    if self.inference_server is not None and self._serving_clients:
                        # generation tags close the loop here: the lag
                        # between the newest push and the oldest client's
                        # last-served generation is the staleness V-trace
                        # is correcting (serving.staleness gauge)
                        self.inference_server.observe_staleness(
                            min(c.generation for c in self._serving_clients)
                        )
                    if self._instrument:
                        if fps_meter is not None:
                            fps_meter.mark(self.env_frames - meter_frames)
                            meter_frames = self.env_frames
                        if learn_meter is not None:
                            learn_meter.mark(learn_steps_done - meter_steps)
                            meter_steps = learn_steps_done
                        telemetry.observe_train_metrics(host_metrics)
                        reg = telemetry.get_registry()
                        reg.set_gauges(
                            {**host_metrics, "sps": sps, "return_mean": ret_mean},
                            prefix="train.",
                        )
                        # registry-backed write: queue occupancy and guard
                        # counters ride alongside the learner metrics
                        self.logger.log_registry(
                            self.env_frames,
                            step_type="train",
                            include_prefixes=("train.", "queue."),
                        )
                    if self.is_main_process:
                        self.text_logger.info(
                            f"frames {self.env_frames} | sps {sps:.0f} | "
                            f"return {ret_mean:.1f} | loss {host_metrics.get('total_loss', float('nan')):.3f}"
                        )
        finally:
            self.stop_event.set()
            if watchdog is not None:
                watchdog.stop()
            if guard is not None:
                guard.restore()
            self.queue.close()
            if self.inference_server is not None:
                # clients first: close() wakes blocked actors, which finish
                # their current slot on the local fallback (no degraded-mode
                # flip, no reconnect churn) and exit on stop_event
                for c in self._serving_clients:
                    c.close()
                self.inference_server.stop()
            # joins run on ONE shared wall-clock budget per group: a wedged
            # thread (env backend stuck in step) must not multiply the
            # teardown by the thread count — preemption budgets are
            # wall-clock, and daemon threads die with the process anyway.
            # After a DIAGNOSED stall the grace shrinks further: the
            # watchdog already proved the threads are wedged, so a long
            # wait buys nothing but a slower failure.
            stalled = watchdog is not None and watchdog.stalled is not None
            deadline = time.monotonic() + (0.5 if stalled else 3.0)
            for t in assemble_threads:
                t.join(timeout=max(0.05, deadline - time.monotonic()))
            if prefetch_q is not None:
                # release device-resident trajectories still queued
                while True:
                    try:
                        prefetch_q.get_nowait()
                    except queue_mod.Empty:
                        break
            deadline = time.monotonic() + (0.5 if stalled else 5.0)
            for a in actors:
                a.join(timeout=max(0.05, deadline - time.monotonic()))
            for a in actors:
                try:
                    a.envs.close()
                except Exception:
                    pass
        if args.save_model and not args.disable_checkpoint:
            self.save_resume()
        sps = (self.env_frames - start_frames) / max(time.time() - start, 1e-8)
        rets = [r for m in self.episode_metrics for r in m.episode_returns]
        return {
            **get_metrics(metrics),
            "env_frames": float(self.env_frames),
            "sps": float(sps),
            "return_mean": float(np.mean(rets[-100:])) if rets else float("nan"),
            "episodes": float(len(rets)),
        }


class DeviceActorLearnerTrainer(BaseTrainer):
    """IMPALA over device-native envs via the fused loop (flagship perf)."""

    def __init__(
        self,
        args: ImpalaArguments,
        agent: ImpalaAgent,
        venv,
        iters_per_call: int = 10,
        mesh=None,
        run_name: Optional[str] = None,
        chunks_in_flight: int = 2,
    ) -> None:
        """``mesh``: run the fused loop data-parallel (Anakin) — env lanes
        sharded over the mesh's ``dp`` axis, params replicated, gradients
        psum-ed inside the fused step.  ``chunks_in_flight``: how many
        fused chunks stay dispatched ahead of the host's (batched) metric
        reads — logging lags the device by ``chunks_in_flight - 1`` chunks
        instead of stalling it; 1 restores the synchronous driver."""
        super().__init__(args, run_name=run_name)
        from scalerl_tpu.runtime.device_loop import DeviceActorLearnerLoop

        self.agent = agent
        self.chunks_in_flight = chunks_in_flight
        # the agent owns the loss hyperparameters — never rebuild from the
        # trainer's args (which may be a different object)
        learn_fn = agent.make_learn_fn(grad_axis="dp" if mesh is not None else None)
        self.loop = DeviceActorLearnerLoop(
            model=agent.model,
            venv=venv,
            learn_fn=learn_fn,
            unroll_length=args.rollout_length,
            iters_per_call=iters_per_call,
            mesh=mesh,
        )

    def _resume_pytree(self) -> Dict:
        return {"agent": self.agent.state, "env_frames": np.asarray(0, np.int64)}

    def train(self, total_frames: Optional[int] = None) -> Dict[str, float]:
        args = self.args
        total_frames = total_frames or args.total_steps
        frames_per_call = (
            args.rollout_length * self.loop.venv.num_envs * self.loop.iters_per_call
        )
        done_frames = 0
        if self.resuming:
            prev = self.load_resume_checkpoint(self._resume_pytree())
            if prev is not None:
                self.agent.state = prev["agent"]
                done_frames = int(prev["env_frames"])
                if self.is_main_process:
                    self.text_logger.info(
                        f"resumed from {self.resume_ckpt_path}: frames {done_frames}"
                    )
        remaining = total_frames - done_frames
        if remaining <= 0:
            # resumed a finished run: nothing to do, don't over-train
            if self.is_main_process:
                self.text_logger.info(
                    f"resume frames {done_frames} >= budget {total_frames}; no-op"
                )
            return {"env_frames": float(done_frames), "sps": 0.0}
        num_calls = max(remaining // frames_per_call, 1)
        key = jax.random.PRNGKey(args.seed + done_frames % 65537)
        carry = self.loop.init_carry(key)
        start = time.time()

        def on_metrics(i: int, m: Dict[str, float]) -> None:
            # offset by done_frames so resumed runs keep logging (the logger
            # gate was restored to the old run's last step) and the tb
            # timeline continues instead of rewinding over the old events
            frames = done_frames + (i + 1) * frames_per_call
            sps = (frames - done_frames) / max(time.time() - start, 1e-8)
            # registry-backed write path: m is already host floats (the
            # driver's one batched transfer per chunk); the driver also
            # feeds train.fps/train.chunks_per_s meters.  Per-chunk cadence;
            # self._instrument compiles the writes out entirely.
            if self._instrument:
                reg = telemetry.get_registry()
                reg.set_gauges({**m, "sps": sps}, prefix="train.")
                self.logger.log_registry(
                    frames, step_type="train", include_prefixes=("train.",)
                )
            if self.is_main_process and (i % 10 == 0 or i == num_calls - 1):
                self.text_logger.info(
                    f"frames {frames} | sps {sps:.0f} | return {m.get('return_mean', float('nan')):.2f}"
                )

        # supervision: a preemption signal stops dispatch at the next chunk
        # boundary (in-flight chunks drain and count); the watchdog's
        # progress counter is bumped by the loop per dispatched chunk
        guard = self.install_preemption_guard()
        watchdog: Optional[StallWatchdog] = None
        progress = None
        if args.watchdog_timeout_s > 0:
            watchdog = StallWatchdog(
                args.watchdog_timeout_s, name="device-actor-learner"
            )
            progress = watchdog.counter("fused_chunks")
            watchdog.start()
        try:
            # --profile-dir: device+host trace around the fused run; the
            # driver's per-chunk step_marker aligns chunks in the viewer
            with maybe_trace(getattr(args, "profile_dir", "") or None):
                state, carry, metrics = self.loop.run(
                    self.agent.state, carry, key, num_calls, on_metrics=on_metrics,
                    chunks_in_flight=self.chunks_in_flight,
                    progress=progress,
                    should_stop=(lambda: guard.triggered) if guard is not None else None,
                    instrument=self._instrument,
                )
        finally:
            if watchdog is not None:
                watchdog.stop()
            if guard is not None:
                guard.restore()
        self.agent.state = state
        self.carry = carry  # the env lanes as the last chunk left them
        # chunks_done < num_calls after a preemption: checkpoint the frames
        # actually trained, not the requested budget, so resume restores
        # matching counters
        chunks_done = int(metrics.pop("chunks_done", num_calls))
        frames = done_frames + chunks_done * frames_per_call
        if args.save_model and not args.disable_checkpoint:
            self.save_resume_checkpoint(
                {"agent": state, "env_frames": np.asarray(frames, np.int64)},
                frames,
                int(state.step),
            )
        metrics["env_frames"] = float(frames)
        metrics["sps"] = (frames - done_frames) / max(time.time() - start, 1e-8)
        return metrics
