"""Distributed DQN over the actor fleet: the Gorila/HandyRL topology, live.

The capability the reference vendored but never wired
(``scalerl/hpc/worker.py`` + ``parameter_server.py`` — import-broken as
shipped, SURVEY.md §2.1): a central learner hands out rollout tasks, a
worker fleet (local pipes here; ``RemoteCluster`` from other hosts) runs
eps-greedy episodes with CPU numpy inference on versioned weight snapshots,
and episode transitions stream back — batched + compressed — into the
device-side replay the TPU learner samples from.  Weights republish every
``publish_every`` learn steps.

Usage:
    python examples/train_fleet_dqn.py --episodes 200 --num-workers 4
"""

from __future__ import annotations

import argparse
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

ENV_ID = "CartPole-v1"
OBS_DIM, NUM_ACTIONS = 4, 2


def numpy_qnet(weights, obs: np.ndarray) -> np.ndarray:
    """CPU forward of the plain (non-dueling) QNet MLP param pytree."""
    x = obs.astype(np.float32)
    layers = sorted(weights["params"].keys(), key=lambda k: int(k.split("_")[1]))
    for i, name in enumerate(layers):
        layer = weights["params"][name]
        x = x @ layer["kernel"] + layer["bias"]
        if i < len(layers) - 1:
            x = np.maximum(x, 0.0)
    return x


def episode_runner(task, weights, worker_id):
    """One eps-greedy CartPole episode on the fleet worker's CPU."""
    import gymnasium as gym

    env = gym.make(ENV_ID)
    seed = int(task["seed"])
    rng = np.random.default_rng(seed)
    eps = float(task.get("eps", 0.1))
    obs, _ = env.reset(seed=seed)
    obs_l, act_l, rew_l, next_l, done_l = [], [], [], [], []
    done = False
    while not done and len(act_l) < 500:
        if weights is None or rng.random() < eps:
            a = int(rng.integers(NUM_ACTIONS))
        else:
            a = int(np.argmax(numpy_qnet(weights, obs[None])[0]))
        nxt, r, term, trunc, _ = env.step(a)
        obs_l.append(obs)
        act_l.append(a)
        rew_l.append(float(r))
        next_l.append(nxt)
        done_l.append(bool(term))
        obs = nxt
        done = term or trunc
    env.close()
    return {
        "obs": np.asarray(obs_l, np.float32),
        "action": np.asarray(act_l, np.int32),
        "reward": np.asarray(rew_l, np.float32),
        "next_obs": np.asarray(next_l, np.float32),
        "done": np.asarray(done_l, np.bool_),
        "episode_return": float(np.sum(rew_l)),
    }


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--episodes", type=int, default=200)
    parser.add_argument("--num-workers", type=int, default=4)
    parser.add_argument("--batch-size", type=int, default=64)
    parser.add_argument("--publish-every", type=int, default=10)
    parser.add_argument("--eps", type=float, default=0.2)
    # "cpu" keeps the learner off the chip; pass "auto" (or "tpu") to put
    # the learner on the accelerator
    parser.add_argument("--platform", default="cpu")
    args = parser.parse_args()

    from scalerl_tpu.utils.platform import setup_platform

    setup_platform(args.platform)

    import jax

    from scalerl_tpu.agents.dqn import DQNAgent
    from scalerl_tpu.config import DQNArguments
    from scalerl_tpu.data.replay import ReplayBuffer
    from scalerl_tpu.fleet import FleetConfig, LocalCluster, WorkerServer

    agent = DQNAgent(
        DQNArguments(hidden_sizes=(128, 128), learning_rate=1e-3),
        obs_shape=(OBS_DIM,),
        action_dim=NUM_ACTIONS,
    )
    replay = ReplayBuffer(obs_shape=(OBS_DIM,), capacity=50_000, num_envs=1)

    lock = threading.Lock()
    counter = {"i": 0}
    server_box = {}

    def task_source():
        with lock:
            if counter["i"] >= args.episodes:
                return None
            counter["i"] += 1
            return {
                "role": "rollout",
                "seed": counter["i"],
                "eps": args.eps,
                "param_version": server_box["s"].params.version,
            }

    config = FleetConfig(
        num_workers=args.num_workers, workers_per_gather=4, upload_batch=2
    )
    server = WorkerServer(config, task_source)
    server_box["s"] = server
    server.publish(jax.tree_util.tree_map(np.asarray, agent.get_weights()))
    server.start()
    cluster = LocalCluster(server, config, episode_runner)
    cluster.start()

    episodes = 0
    learn_steps = 0
    returns = []
    metrics = {}
    # host staging: insert fixed-size chunks so the device add compiles once
    CHUNK = 64
    pending = {k: [] for k in ("obs", "action", "reward", "next_obs", "done")}

    def flush_pending() -> None:
        while len(pending["action"]) >= CHUNK:
            chunk = {k: np.asarray(v[:CHUNK]) for k, v in pending.items()}
            for k in pending:
                del pending[k][:CHUNK]
            replay.save_chunk(**chunk)

    t0 = time.time()
    while episodes < args.episodes:
        result = server.get_result(timeout=1.0)
        if result is None:
            continue
        episodes += 1
        returns.append(result["episode_return"])
        for k in pending:
            pending[k].extend(list(result[k]))
        flush_pending()
        if len(replay) >= args.batch_size:
            for _ in range(2):
                metrics = agent.learn(replay.sample(args.batch_size))
                learn_steps += 1
            if learn_steps % args.publish_every < 2:
                server.publish(
                    jax.tree_util.tree_map(np.asarray, agent.get_weights())
                )
        if episodes % 20 == 0:
            recent = float(np.mean(returns[-20:]))
            print(
                f"episodes {episodes} | return(20) {recent:.1f} | "
                f"learn_steps {learn_steps} | weight v{server.params.version} | "
                f"loss {metrics.get('loss', float('nan')):.4f}",
                flush=True,
            )

    cluster.join()
    server.stop()
    dt = time.time() - t0
    print(
        f"done: {episodes} episodes in {dt:.1f}s | "
        f"final return(20) {np.mean(returns[-20:]):.1f} | "
        f"first return(20) {np.mean(returns[:20]):.1f}"
    )


if __name__ == "__main__":
    main()
