"""Process-actor IMPALA: monobeast-topology actors over the C++ shm ring.

The reference's IMPALA runs each actor as a *process* with its own CPU model
copy (``scalerl/algorithms/impala/impala_atari.py:153-220,420-434``) — the
torchbeast/monobeast topology, where V-trace exists precisely to correct the
actor-side policy lag.  The thread-based ``HostActorLearnerTrainer``
(SEED-style central inference) covers the other topology; this trainer covers
the reference's, with two upgrades the reference lacked:

- rollout hand-off is the lock-free C++ shared-memory slot ring
  (``runtime/shm_ring.py`` / ``csrc/shm_ring.cpp``), not pickled
  ``SimpleQueue`` tensors — actors write trajectory slots through zero-copy
  numpy views;
- actors are **spawned**, not forked (fork-after-JAX deadlocks in XLA's
  thread pools), and each pins its own single-process CPU JAX backend for
  local inference, so actors scale GIL-free across host cores while the
  learner keeps the accelerator.

Weight sync mirrors the reference's ``actor_model.load_state_dict`` pub
(``impala_atari.py:348``) as a versioned pull over a pipe: actors request
``{"kind": "params", "have": v}`` between chunks and the learner's weight
service replies with the newest numpy pytree (or ``None`` if current).
Failure handling: actor exceptions funnel back as ``{"kind": "error"}``
messages and re-raise in the learner; teardown closes the ring (the shared
stop flag), then joins with timeouts (``impala_atari.py:473-494`` ladder).
"""

from __future__ import annotations

import multiprocessing as mp
import threading
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from scalerl_tpu.config import ImpalaArguments
from scalerl_tpu.fleet.transport import PipeConnection, send_recv, wait_readable
from scalerl_tpu.runtime import telemetry
from scalerl_tpu.runtime.param_server import ParameterServer
from scalerl_tpu.runtime.shm_ring import ShmRolloutRing, SlotSpec
from scalerl_tpu.runtime.supervisor import (
    CheckpointCadence,
    StallWatchdog,
)
from scalerl_tpu.trainer.base import BaseTrainer
from scalerl_tpu.utils.logging import get_logger

logger = get_logger(__name__)


@dataclass
class _ProcActorConfig:
    actor_id: int
    args: ImpalaArguments
    obs_shape: Tuple[int, ...]
    num_actions: int
    obs_dtype_name: str
    envs_per_actor: int
    seed: int
    atari: bool = False


def _proc_actor_main(conn: PipeConnection, cfg: _ProcActorConfig, ring: ShmRolloutRing) -> None:
    """Actor process: vector env + local CPU policy + shm slot writes."""
    import os
    import sys

    failed = False

    # Pin a single-device CPU backend before any JAX device use: this is a
    # fresh spawned interpreter whose parent (the learner) holds the chip,
    # and a chip belongs to one process — the actor must never open it,
    # whatever JAX_PLATFORMS the environment carries.
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
    import jax

    jax.config.update("jax_platforms", "cpu")

    from scalerl_tpu.agents.impala import ImpalaAgent
    from scalerl_tpu.envs import make_vect_envs
    from scalerl_tpu.trainer.actor_learner import fill_rollout_slot

    try:
        obs_dtype = np.dtype(cfg.obs_dtype_name)
        agent = ImpalaAgent(
            cfg.args,
            obs_shape=cfg.obs_shape,
            num_actions=cfg.num_actions,
            obs_dtype=obs_dtype,
            key=jax.random.PRNGKey(cfg.seed),
        )
        # the project factory, not raw gym.make: same DeepMind Atari wrapper
        # stack and SAME_STEP autoreset semantics as the thread actor plane —
        # the learner must see identical trajectory boundary conventions
        # whichever --actor-mode produced the slots
        envs = make_vect_envs(
            cfg.args.env_id,
            num_envs=cfg.envs_per_actor,
            seed=cfg.seed,
            async_envs=False,  # one env pool per actor process already
            atari=cfg.atari,
        )
        B = cfg.envs_per_actor
        T = cfg.args.rollout_length
        obs, _ = envs.reset(seed=cfg.seed)
        last_action = np.zeros(B, np.int32)
        reward = np.zeros(B, np.float32)
        done = np.ones(B, bool)
        core_state = agent.initial_state(B)
        version = -1
        ep_ret = np.zeros(B, np.float64)
        returns: List[float] = []

        def on_step(rew: np.ndarray, dn: np.ndarray) -> None:
            nonlocal ep_ret
            ep_ret += rew
            for b in np.nonzero(dn)[0]:
                returns.append(float(ep_ret[b]))
                ep_ret[b] = 0.0

        while not ring.closed:
            # pull newest weights (None reply = already current)
            try:
                reply = send_recv(conn, {"kind": "params", "have": version})
            except (EOFError, OSError, ConnectionError):
                break
            if reply is not None:
                version = int(reply["version"])
                agent.set_weights(reply["weights"])
            idx = ring.acquire(timeout=1.0)
            if idx is None:
                continue
            try:
                slot = ring.slot(idx)
                returns.clear()
                obs, last_action, reward, done, core_state = fill_rollout_slot(
                    slot, agent, envs, obs, last_action, reward, done,
                    core_state, T, on_step=on_step,
                )
                slot["meta"][0] = cfg.actor_id
                slot["meta"][1] = version
            except BaseException:
                # funneled failure mid-fill: hand the slot back before the
                # error propagates, or each elastic restart strands one of
                # num_buffers slots until the ring starves (mirror of the
                # thread plane's q.recycle on crash)
                slot = None  # drop views first so detach() can close later
                ring.release(idx)
                raise
            ring.commit(idx)
            slot = None  # release shm views now: a live view at loop exit
            # keeps the mapping exported and detach() cannot close it
            if returns:
                try:
                    conn.send({"kind": "stats", "actor_id": cfg.actor_id,
                               "returns": list(returns)})
                except (BrokenPipeError, OSError):
                    break
        envs.close()
    except KeyboardInterrupt:
        pass
    except (EOFError, OSError, ConnectionError):
        # benign ONLY at shutdown (the learner closed the ring/pipe under
        # us).  Outside shutdown this is a real failure — e.g. an env
        # backend raising OSError — and exiting 0 silently here would give
        # the elastic learner neither an error message nor a nonzero exit
        # to react to (it treats exit 0 as a clean departure)
        if not ring.closed:
            import traceback

            failed = True
            try:
                conn.send({"kind": "error", "actor_id": cfg.actor_id,
                           "traceback": traceback.format_exc()})
            except Exception:  # noqa: BLE001 — pipe may be the casualty
                pass
    except Exception:  # noqa: BLE001 - funneled to the learner
        import traceback

        failed = True
        try:
            conn.send({"kind": "error", "actor_id": cfg.actor_id,
                       "traceback": traceback.format_exc()})
        except Exception:
            pass
    finally:
        ring.detach()
        try:
            conn.close()
        except Exception:
            pass
    if failed:
        sys.exit(1)  # nonzero: never classified as a clean departure


class ProcessActorLearnerTrainer(BaseTrainer):
    """IMPALA with GIL-free actor processes (reference topology, shm ring)."""

    def __init__(
        self,
        args: ImpalaArguments,
        agent,
        envs_per_actor: Optional[int] = None,
        run_name: Optional[str] = None,
        max_actor_restarts: int = 0,
    ) -> None:
        """``max_actor_restarts``: elastic actors — an actor that fails is
        respawned (same actor id/seed/config, fresh pipe) up to this many
        times across the run instead of failing the learner.

        Contract: recovery is guaranteed only for *funneled* failures (the
        actor caught its exception and sent ``{"kind": "error"}`` — env
        crashes, OOM in the actor's Python, etc.); the actor releases its
        acquired-but-uncommitted ring slot before the error propagates, so
        the ring stays whole.  A hard-killed actor (SIGKILL mid-ring-push)
        is respawned best-effort, but a producer that died between
        claiming and publishing a ring cell wedges the lock-free ring for
        every later consumer at that position — no user-space recovery
        exists for that, by the nature of lock-free shared memory.  0
        (default) keeps fail-fast."""
        super().__init__(args, run_name=run_name)
        self.agent = agent
        # args.num_envs is the TOTAL env-lane count (CLI semantics shared
        # with the thread backend); each actor process drives its share
        self.envs_per_actor = envs_per_actor or max(
            args.num_envs // args.num_actors, 1
        )
        from scalerl_tpu.trainer.actor_learner import check_queue_depth

        # slot-aware ring floor (the learner pops batch_size/envs_per_actor
        # full slots per step; a shallower ring starves it forever)
        check_queue_depth(args, self.envs_per_actor)
        self.param_server = ParameterServer()
        self.returns: List[float] = []
        self.env_frames = 0
        self._stop = threading.Event()
        self._actor_error: List[str] = []
        self.max_actor_restarts = max_actor_restarts
        self.actor_restarts = 0
        self.procs: List[mp.process.BaseProcess] = []
        self.conns: List[PipeConnection] = []
        self._actor_of: Dict[PipeConnection, int] = {}
        self._cfgs: List[_ProcActorConfig] = []
        self._dying: Dict[int, float] = {}  # actor_id -> recheck deadline

        T1 = args.rollout_length + 1
        B = self.envs_per_actor
        core = agent.initial_state(B)
        fields: Dict[str, Tuple[Tuple[int, ...], np.dtype]] = {
            "obs": ((T1, B) + tuple(agent.obs_shape), np.dtype(self._obs_dtype_name())),
            "action": ((T1, B), np.dtype(np.int32)),
            "reward": ((T1, B), np.dtype(np.float32)),
            "done": ((T1, B), np.dtype(bool)),
            "logits": ((T1, B, agent.num_actions), np.dtype(np.float32)),
            "meta": ((2,), np.dtype(np.float64)),
        }
        for i, (c, h) in enumerate(core):
            fields[f"core_{i}_c"] = (tuple(c.shape), np.dtype(np.float32))
            fields[f"core_{i}_h"] = (tuple(h.shape), np.dtype(np.float32))
        self._core_leaves = len(core)
        self.ring = ShmRolloutRing(SlotSpec(fields), num_slots=args.num_buffers)
        # a failed g++ build degrades to the Python ring with a warning
        # (native/build.py); say which one this run is actually on
        logger.info(
            "rollout ring: %s",
            "native (csrc/shm_ring.cpp)" if self.ring.native
            else "pure-Python fallback",
        )
        self._weight_thread = threading.Thread(
            target=self._weight_service, daemon=True
        )

    def _obs_dtype_name(self) -> str:
        return "uint8" if len(self.agent.obs_shape) == 3 else "float32"

    # -- weight / stats / error service --------------------------------
    def _grant_restart(self) -> bool:
        if self.actor_restarts >= self.max_actor_restarts:
            return False
        self.actor_restarts += 1
        return True

    def _drop_conn(self, conn: PipeConnection, reason: str) -> None:
        """A connection died: respawn its actor (elastic) or record the
        failure (fail-fast).  Clean shutdown drops silently."""
        if conn in self.conns:
            self.conns.remove(conn)
        actor_id = self._actor_of.pop(conn, None)
        if actor_id is None or self._stop.is_set():
            return
        proc = self.procs[actor_id]
        if proc.is_alive():
            # pipe EOF'd while the process is still tearing down (the
            # actor closes its conn in `finally` before interpreter exit):
            # PARK it for the service loop to recheck — forgetting it here
            # would yield neither restart nor error, and the learner would
            # starve waiting on a producer that no longer exists
            self._dying[actor_id] = time.monotonic() + 30.0
            return
        self._handle_actor_death(actor_id, reason, proc.exitcode)

    def _handle_actor_death(self, actor_id: int, reason: str, exitcode) -> None:
        if exitcode == 0:
            # clean exit outside shutdown: the actor decided it was done
            # (ring closed under it); nothing to recover, nothing to raise
            return
        if self._grant_restart():
            logger.warning(
                "actor process %d died (%s, exit %s); respawning "
                "(restart %d/%d)",
                actor_id, reason, exitcode,
                self.actor_restarts, self.max_actor_restarts,
            )
            self._spawn_actor(actor_id)
        else:
            self._actor_error.append(
                f"actor {actor_id} died ({reason}, exit {exitcode})"
            )

    def _check_dying(self) -> None:
        """Recheck parked actors (pipe gone, process was still alive)."""
        for actor_id, deadline in list(self._dying.items()):
            proc = self.procs[actor_id]
            if not proc.is_alive():
                del self._dying[actor_id]
                self._handle_actor_death(actor_id, "pipe dead", proc.exitcode)
            elif time.monotonic() > deadline:
                del self._dying[actor_id]
                self._actor_error.append(
                    f"actor {actor_id}: pipe closed but process still "
                    "alive after 30s (hung teardown)"
                )

    def _weight_service(self) -> None:
        while not self._stop.is_set():
            self._check_dying()
            if not self.conns:
                self._stop.wait(0.05)
                continue
            ready, dead = wait_readable(self.conns, timeout=0.1)
            for conn in dead:
                self._drop_conn(conn, "pipe dead")
            for conn in ready:
                try:
                    msg = conn.recv()
                except (EOFError, OSError, ConnectionError, ValueError):
                    self._drop_conn(conn, "recv failed")
                    continue
                if msg is None:
                    continue
                if msg["kind"] == "params":
                    weights, version = self.param_server.pull(int(msg["have"]))
                    try:
                        conn.send(
                            None
                            if weights is None
                            else {"version": version, "weights": weights}
                        )
                    except (BrokenPipeError, OSError):
                        continue
                elif msg["kind"] == "stats":
                    self.returns.extend(float(r) for r in msg["returns"])
                elif msg["kind"] == "error":
                    actor_id = int(msg["actor_id"])
                    if self._grant_restart():
                        logger.warning(
                            "actor %d failed; respawning (restart %d/%d):\n%s",
                            actor_id, self.actor_restarts,
                            self.max_actor_restarts, msg["traceback"],
                        )
                        # no blocking join here: it would stall weight/stats
                        # service for every OTHER actor while the errored
                        # process tears down; _spawn_actor retires the old
                        # pipe, and mp reaps the finished child on the next
                        # Process creation
                        self._spawn_actor(actor_id)
                    else:
                        self._actor_error.append(
                            f"actor {actor_id}:\n{msg['traceback']}"
                        )

    def _spawn_actor(self, i: int) -> None:
        # retire any previous pipe registered for this actor slot
        for c, a in list(self._actor_of.items()):
            if a == i:
                self._actor_of.pop(c, None)
                if c in self.conns:
                    self.conns.remove(c)
        parent, child = self._ctx.Pipe(duplex=True)
        proc = self._ctx.Process(
            target=_proc_actor_main,
            args=(PipeConnection(child), self._cfgs[i], self.ring),
            daemon=True,
        )
        proc.start()
        child.close()
        if i < len(self.procs):
            self.procs[i] = proc
        else:
            self.procs.append(proc)
        conn = PipeConnection(parent)
        self.conns.append(conn)
        self._actor_of[conn] = i

    def start_actors(self) -> None:
        # spawn, not fork: the learner has JAX initialized (see the
        # envs/vector/async_vec.py hazard note)
        self._ctx = mp.get_context("spawn")
        env_id = self.args.env_id
        atari = env_id.startswith("ALE/") or "NoFrameskip" in env_id
        for i in range(self.args.num_actors):
            self._cfgs.append(
                _ProcActorConfig(
                    actor_id=i,
                    args=self.args,
                    obs_shape=tuple(self.agent.obs_shape),
                    num_actions=self.agent.num_actions,
                    obs_dtype_name=self._obs_dtype_name(),
                    envs_per_actor=self.envs_per_actor,
                    seed=self.args.seed + 7919 * i,
                    atari=atari,
                )
            )
            self._spawn_actor(i)
        self._weight_thread.start()

    # -- resume (parity with HostActorLearnerTrainer) ------------------
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
        state = self.load_resume_checkpoint(self._resume_pytree())
        if state is None:
            return False
        self.agent.state = state["agent"]
        self.env_frames = int(state["env_frames"])
        if self.is_main_process:
            self.text_logger.info(
                f"resumed from {self.resume_ckpt_path}: frames {self.env_frames}"
            )
        return True

    # -- learner -------------------------------------------------------
    def _pop_batch(self, n_slots: int) -> Optional[List[int]]:
        idxs: List[int] = []
        while len(idxs) < n_slots:
            if self._actor_error:
                for i in idxs:
                    self.ring.release(i)
                raise RuntimeError(
                    "actor process failed:\n" + "\n".join(self._actor_error)
                )
            # verified pop: a torn/corrupt slot (producer killed mid-write)
            # is detected by its checksum, released, and skipped
            idx = self.ring.pop_full_verified(timeout=1.0)
            if idx is None:
                if self.ring.closed or self._stop.is_set():
                    for i in idxs:
                        self.ring.release(i)
                    return None
                continue
            idxs.append(idx)
        return idxs

    def _batch_to_host(self, idxs: List[int]) -> Dict[str, np.ndarray]:
        views = [self.ring.slot(i) for i in idxs]
        batch: Dict[str, np.ndarray] = {}
        for name in views[0]:
            if name == "meta":
                continue
            axis = 0 if name.startswith("core_") else 1
            batch[name] = np.concatenate([v[name] for v in views], axis=axis)
        self._lag = float(
            np.mean([self.param_server.version - v["meta"][1] for v in views])
        )
        return batch

    def train(self, total_frames: Optional[int] = None) -> Dict[str, float]:
        from scalerl_tpu.data.trajectory import batch_to_trajectory

        args = self.args
        total_frames = total_frames or args.total_steps
        frames_per_slot = args.rollout_length * self.envs_per_actor
        n_slots = max(args.batch_size // self.envs_per_actor, 1)
        if self.resuming:
            self.try_resume()
        self.param_server.push(self.agent.get_weights())
        if not self.procs:
            self.start_actors()
        # supervision: preemption saves at the next slot boundary; watchdog
        # dumps stacks + ring occupancy when frames stop advancing (a wedged
        # actor fleet or a dead weight service both freeze this counter)
        guard = self.install_preemption_guard()
        watchdog: Optional[StallWatchdog] = None
        if args.watchdog_timeout_s > 0:
            watchdog = StallWatchdog(
                args.watchdog_timeout_s, name="process-actor-learner"
            )
            watchdog.watch("env_frames", lambda: self.env_frames)
            watchdog.add_probe("shm_ring", self.ring.stats)
            watchdog.add_probe("actor_restarts", lambda: self.actor_restarts)
            watchdog.add_probe(
                "actors_alive",
                lambda: sum(1 for p in self.procs if p.is_alive()),
            )
            watchdog.start()
        start = time.time()
        start_frames = self.env_frames  # nonzero after resume
        last_log = start_frames
        cadence = CheckpointCadence(
            args.save_frequency, args.checkpoint_interval_s, start_frames
        )
        metrics: Dict[str, float] = {}
        self._lag = float("nan")
        try:
            while self.env_frames < total_frames:
                if watchdog is not None:
                    watchdog.check()
                if guard is not None and guard.triggered:
                    if args.save_model and not args.disable_checkpoint:
                        self.save_resume()
                    break
                idxs = self._pop_batch(n_slots)
                if idxs is None:
                    break
                batch = self._batch_to_host(idxs)  # copies out of the slots
                for i in idxs:
                    self.ring.release(i)
                traj = batch_to_trajectory(batch)
                metrics = self.agent.learn(traj)
                self.param_server.push(self.agent.get_weights())
                self.env_frames += n_slots * frames_per_slot

                if (
                    args.save_model
                    and not args.disable_checkpoint
                    and cadence.due(self.env_frames)
                ):
                    cadence.mark_saved(self.env_frames)
                    self.save_resume()

                if self.env_frames - last_log >= args.logger_frequency:
                    last_log = self.env_frames
                    sps = (self.env_frames - start_frames) / max(
                        time.time() - start, 1e-8
                    )
                    ret = (
                        float(np.mean(self.returns[-50:]))
                        if self.returns
                        else float("nan")
                    )
                    # registry-backed write: ring + guard counters ride
                    # along.  Lazy import: actor children must pin their
                    # platform BEFORE anything imports jax (dispatch does)
                    from scalerl_tpu.runtime.dispatch import get_metrics

                    host_info = get_metrics(metrics)
                    if self._instrument:
                        telemetry.observe_train_metrics(host_info)
                        reg = telemetry.get_registry()
                        reg.set_gauges(
                            {**host_info, "sps": sps, "return_mean": ret,
                             "weights_lag": self._lag},
                            prefix="train.",
                        )
                        self.logger.log_registry(
                            self.env_frames,
                            step_type="train",
                            include_prefixes=("train.", "ring."),
                        )
                    if self.is_main_process:
                        self.text_logger.info(
                            f"frames {self.env_frames} | sps {sps:.0f} | "
                            f"return {ret:.1f} | lag {self._lag:.1f}"
                        )
        finally:
            if watchdog is not None:
                watchdog.stop()
            if guard is not None:
                guard.restore()
            self.stop()
        if args.save_model and not args.disable_checkpoint:
            self.save_resume()
        sps = (self.env_frames - start_frames) / max(time.time() - start, 1e-8)
        return {
            **metrics,
            "env_frames": float(self.env_frames),
            "sps": float(sps),
            "return_mean": float(np.mean(self.returns[-100:]))
            if self.returns
            else float("nan"),
            "episodes": float(len(self.returns)),
        }

    def stop(self) -> None:
        self.ring.close()
        self._stop.set()
        if self._weight_thread.is_alive():
            self._weight_thread.join(timeout=2.0)
        # close parent pipe ends BEFORE joining: an actor that entered
        # send_recv just as the weight service exited is blocked in recv();
        # EOF unblocks it, otherwise every such actor burns the join timeout
        # and gets terminate()d mid-teardown
        for c in self.conns:
            try:
                c.close()
            except Exception:
                pass
        self.conns.clear()
        for p in self.procs:
            p.join(timeout=5.0)
        for p in self.procs:
            if p.is_alive():
                p.terminate()
                p.join(timeout=2.0)
        self.ring.unlink()
