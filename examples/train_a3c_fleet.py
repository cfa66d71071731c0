"""Async distributed A3C over the worker fleet (the Ray-variant counterpart).

Parity target: ``scalerl/algorithms/a3c/ray_a3c.py:27-127`` — the reference's
cluster-wide A3C: remote actors each roll out under the latest weights they
have, compute GRADIENTS locally, and a central driver applies them
asynchronously and republishes weights.  This is that exact protocol over
the framework's own fleet layer (``scalerl_tpu/fleet``) instead of Ray:

- **workers** (fleet worker processes, one persistent JAX-on-CPU runtime
  each) pull a task + the newest published weights, unroll ``T`` steps of
  their vector env, compute the A2C gradient on that rollout, and upload
  it (flat-binary codec, batched by the gather tier);
- **the server** applies each arriving gradient to the shared Adam state
  the moment it arrives (no barrier — gradients computed on slightly
  stale weights are applied as-is, the Hogwild/Ray-A3C semantics, made
  race-free by message passing), then republishes a new weight version;
  workers pick it up on their next task.

Unlike :mod:`scalerl_tpu.trainer.on_policy` (the sync-batched A2C runtime,
SURVEY §7 step 8), this topology scales across HOSTS: point workers at a
``WorkerServer(listen=True)`` and they connect over TCP
(``RemoteCluster`` / ``connect_worker``) — no shared memory, no Ray.

Run: ``python examples/train_a3c_fleet.py [--num-workers 2]
[--total-frames 100000]``
"""

from __future__ import annotations

import sys
import time
from pathlib import Path
from typing import Dict

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import numpy as np

# worker-process-local cache: one env + one jitted grad fn per worker,
# built on first task and reused for the process lifetime
_WORKER_STATE: Dict = {}


def _a3c_grad_runner(task, weights, worker_id):
    """Fleet runner: rollout under ``weights`` -> A2C gradient.

    Built lazily per worker process (fresh spawn: pin the CPU backend
    BEFORE first JAX use — a chip belongs to one process, and workers must
    never be the one that opens it).
    """
    import jax

    if "grad_fn" not in _WORKER_STATE:
        jax.config.update("jax_platforms", "cpu")
        from scalerl_tpu.agents.a3c import a3c_loss, build_model
        from scalerl_tpu.config import A3CArguments
        from scalerl_tpu.envs import make_jax_vec_env

        args = A3CArguments(
            hidden_sizes=str(task["hidden_sizes"]),
            gamma=float(task["gamma"]),
            gae_lambda=float(task["gae_lambda"]),
            value_loss_coef=float(task["value_loss_coef"]),
            entropy_coef=float(task["entropy_coef"]),
        )
        venv = make_jax_vec_env(task["env_id"], int(task["num_envs"]))
        # derive shapes from the env the worker actually built — a
        # mismatched hardcode would surface as an opaque XLA shape error
        # deep inside the jitted scan
        model = build_model(
            args, obs_shape=venv.observation_shape,
            num_actions=venv.num_actions,
        )

        def rollout_and_grad(params, env_state, obs, last_action, reward,
                             done, ep_ret, key, unroll):
            """One [T+1, B] on-policy chunk + grad, all one jitted fn.

            Row 0 is the CARRIED boundary state (the previous chunk's
            bootstrap row), and the scan steps exactly ``unroll`` times —
            the OnPolicyTrainer overlap convention, so no transition is
            ever dropped between chunks and frames == T * B exactly.
            """
            import jax.numpy as jnp

            from scalerl_tpu.data.trajectory import Trajectory

            B = obs.shape[0]
            row0 = (obs, last_action, reward, done)

            def step(carry, _):
                env_state, obs, last_action, reward, done, ep_ret, key = carry
                key, akey, skey = jax.random.split(key, 3)
                out, _ = model.apply(
                    params, obs[None], last_action[None], reward[None],
                    done[None], (),
                )
                action = jax.random.categorical(akey, out.policy_logits[0])
                env_state, nobs, nrew, ndone = venv.step(env_state, action, skey)
                nrew = nrew.astype(jnp.float32)
                ep_ret = ep_ret + nrew
                ep_done_ret = jnp.where(ndone, ep_ret, 0.0)
                ep_ret = jnp.where(ndone, 0.0, ep_ret)
                carry = (env_state, nobs, action.astype(jnp.int32),
                         nrew, ndone, ep_ret, key)
                row = (nobs, action.astype(jnp.int32), nrew, ndone)
                return carry, (row, ep_done_ret, ndone.astype(jnp.float32))

            carry = (env_state, obs, last_action, reward, done, ep_ret, key)
            carry, ((obs_s, act_s, rew_s, done_s), ep_rets, ep_dones) = (
                jax.lax.scan(step, carry, None, length=unroll)
            )
            obs_t = jnp.concatenate([row0[0][None], obs_s])
            act_t = jnp.concatenate([row0[1][None], act_s])
            rew_t = jnp.concatenate([row0[2][None], rew_s])
            done_t = jnp.concatenate([row0[3][None], done_s])
            traj = Trajectory(
                obs=obs_t, action=act_t, reward=rew_t, done=done_t,
                logits=jnp.zeros(
                    (unroll + 1, B, venv.num_actions), jnp.float32
                ),  # unused by a3c_loss
                core_state=(),
            )
            (loss, metrics), grads = jax.value_and_grad(
                a3c_loss, has_aux=True
            )(
                params, model, traj,
                gamma=args.gamma, gae_lambda=args.gae_lambda,
                value_loss_coef=args.value_loss_coef,
                entropy_coef=args.entropy_coef,
            )
            return carry, grads, loss, jnp.sum(ep_rets), jnp.sum(ep_dones)

        _WORKER_STATE["fn"] = jax.jit(
            rollout_and_grad, static_argnames=("unroll",)
        )
        key = jax.random.PRNGKey(int(task["seed"]) * 4096 + 1000 + worker_id)
        env_state, obs = venv.reset(key)
        B = int(task["num_envs"])
        import jax.numpy as jnp

        _WORKER_STATE["carry"] = (
            env_state, obs, jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.float32),
            jnp.ones(B, bool), jnp.zeros(B, jnp.float32), key,
        )
        _WORKER_STATE["grad_fn"] = True

    params = jax.tree_util.tree_map(np.asarray, weights)
    carry, grads, loss, ret_sum, ep_count = _WORKER_STATE["fn"](
        params, *_WORKER_STATE["carry"], unroll=int(task["unroll"])
    )
    _WORKER_STATE["carry"] = carry
    T, B = int(task["unroll"]), int(task["num_envs"])
    return {
        "role": "rollout",
        "grads": jax.tree_util.tree_map(np.asarray, grads),
        "loss": float(loss),
        "frames": T * B,
        "return_sum": float(ret_sum),
        "episode_count": float(ep_count),
    }


def train_a3c_fleet(
    num_workers: int = 2,
    total_frames: int = 100_000,
    num_envs: int = 4,
    unroll: int = 32,
    learning_rate: float = 3e-3,
    hidden_sizes: str = "128,128",
    entropy_coef: float = 0.01,
    seed: int = 0,
    on_window=None,
) -> Dict[str, float]:
    """Drive the async-gradient A3C fleet on CartPole; return summary.

    ``on_window(frames, windowed_return)`` fires every ~20 applied grads.
    """
    import jax

    from scalerl_tpu.utils.platform import jax_runtime_initialized

    # pin CPU only while the process has no backend yet: this driver is a
    # host-topology example, but repointing jax_platforms globally would
    # poison every later experiment sharing the process (a --tpu curves
    # run).  Workers always pin their own fresh processes.
    if not jax_runtime_initialized():
        jax.config.update("jax_platforms", "cpu")
    import optax

    from scalerl_tpu.agents.a3c import build_model, make_a3c_optimizer
    from scalerl_tpu.config import A3CArguments
    from scalerl_tpu.fleet import FleetConfig, LocalCluster, WorkerServer

    args = A3CArguments(
        hidden_sizes=hidden_sizes, learning_rate=learning_rate,
        entropy_coef=entropy_coef, seed=seed,
    )
    model = build_model(args, obs_shape=(4,), num_actions=2)
    optimizer = make_a3c_optimizer(args)
    import jax.numpy as jnp

    obs0 = jnp.zeros((1, num_envs, 4), jnp.float32)
    params = model.init(
        jax.random.PRNGKey(seed), obs0, jnp.zeros((1, num_envs), jnp.int32),
        jnp.zeros((1, num_envs), jnp.float32), jnp.zeros((1, num_envs), bool), (),
    )
    opt_state = optimizer.init(params)

    @jax.jit
    def apply_grads(params, opt_state, grads):
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    frames_per_task = unroll * num_envs
    n_tasks = max(total_frames // frames_per_task, 1)
    task_template = {
        "role": "rollout", "env_id": "CartPole-v1", "num_envs": num_envs,
        "unroll": unroll, "hidden_sizes": hidden_sizes, "seed": seed,
        "gamma": args.gamma,
        "gae_lambda": args.gae_lambda,
        "value_loss_coef": args.value_loss_coef,
        "entropy_coef": entropy_coef,
    }
    issued = {"n": 0}
    import threading

    lock = threading.Lock()

    def task_source():
        with lock:
            if issued["n"] >= n_tasks:
                return None
            issued["n"] += 1
        return dict(task_template, param_version=server.params.version)

    config = FleetConfig(num_workers=num_workers, workers_per_gather=2,
                         upload_batch=1)
    server = WorkerServer(config, task_source)
    server.publish(jax.device_get(params))
    server.start(listen=False)
    cluster = LocalCluster(server, config, _a3c_grad_runner)
    cluster.start()

    t0 = time.time()
    frames = 0
    applied = 0
    idle = 0
    ret_sum = ep_count = 0.0
    prev_sum = prev_cnt = 0.0
    windowed = 0.0
    try:
        while applied < n_tasks:
            r = server.get_result(timeout=1.0)
            if r is None:
                if not server.worker_errors.empty():
                    err = server.worker_errors.get()
                    raise RuntimeError(
                        f"fleet worker failed: {err.get('error')}"
                    )
                idle += 1
                if idle >= 120:
                    break  # workers went quiet for ~2 min: surface what we have
                continue
            idle = 0
            grads = jax.tree_util.tree_map(jnp.asarray, r["grads"])
            params, opt_state = apply_grads(params, opt_state, grads)
            applied += 1
            frames += r["frames"]
            ret_sum += r["return_sum"]
            ep_count += r["episode_count"]
            # async republish: workers see the new version on next task
            server.publish(jax.device_get(params))
            if applied % 20 == 0:
                if ep_count > prev_cnt:
                    windowed = (ret_sum - prev_sum) / (ep_count - prev_cnt)
                    prev_sum, prev_cnt = ret_sum, ep_count
                if on_window is not None:
                    on_window(frames, windowed)
    finally:
        cluster.join()
        server.stop()
    # final window: episodes since the last %20 tick must not be dropped
    # (short runs would otherwise report 0.0 regardless of learning), and
    # the curve hook must see it too — a crossing in the tail would
    # otherwise record passed=False with final_return over the threshold
    if ep_count > prev_cnt:
        windowed = (ret_sum - prev_sum) / (ep_count - prev_cnt)
        if on_window is not None:
            on_window(frames, windowed)
    wall = time.time() - t0
    return {
        "applied_updates": applied,
        "env_frames": frames,
        "windowed_return": round(windowed, 2),
        "weight_version": server.params.version,
        "wall_s": round(wall, 1),
        "fps": round(frames / max(wall, 1e-9), 1),
    }


def main() -> None:
    import argparse

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--num-workers", type=int, default=2)
    p.add_argument("--total-frames", type=int, default=100_000)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()
    summary = train_a3c_fleet(
        num_workers=args.num_workers, total_frames=args.total_frames,
        seed=args.seed,
        on_window=lambda f, w: print(f"frames {f} | return {w:.1f}", flush=True),
    )
    print("summary:", summary)


if __name__ == "__main__":
    main()
