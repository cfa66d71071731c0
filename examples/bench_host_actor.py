"""Host actor-plane throughput: env-frames/sec of ``HostActorLearnerTrainer``.

The SEED-style host path — CPU vector envs, central batched inference on the
device, free/full rollout slots, V-trace learner — is what real Gym/Atari
training uses, so its frames/sec is measured here end to end (actors + learner
together, not env stepping alone — ``examples/bench_env_throughput.py`` covers
that).  Parity: the reference measured env stacks in
``examples/test_env_throughput.py:16-606`` but never its own IMPALA trainer;
its self-reported SPS (``impala_atari.py:470-471``) was never recorded.

Two configs:

  cartpole   [4]-float obs, MLP torso — control-dominated, measures pipeline
             overhead (queue, inference dispatch, learner)
  pixels     [84,84,4]-uint8 obs, AtariNet conv torso — bandwidth/compute
             shaped like real Atari (frames pre-rendered per cell so env
             stepping is an array lookup, not the bottleneck)

Prints one JSON line per config.

Usage: python examples/bench_host_actor.py [cartpole pixels] [--frames 40000]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if "--cpu" in sys.argv:
    # pin before any backend init (same effect as JAX_PLATFORMS=cpu): the
    # flag must win even on a machine whose environment points at a chip
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
    import jax

    jax.config.update("jax_platforms", "cpu")

import numpy as np


from scalerl_tpu.envs.synthetic_gym import PixelRingEnv  # noqa: E402 — kept importable here


def bench_host(kind: str, num_actors: int, envs_per_actor: int, frames: int,
               mode: str = "threads") -> dict:
    import gymnasium as gym

    from scalerl_tpu.agents.impala import ImpalaAgent
    from scalerl_tpu.config import ImpalaArguments
    from scalerl_tpu.envs import make_vect_envs
    from scalerl_tpu.trainer.actor_learner import HostActorLearnerTrainer
    from scalerl_tpu.trainer.process_actor_learner import (
        ProcessActorLearnerTrainer,
    )

    pixels = kind == "pixels"
    args = ImpalaArguments(
        env_id="PixelRing-v0" if pixels else "CartPole-v1",
        rollout_length=20 if pixels else 16,
        batch_size=2 * envs_per_actor,
        num_actors=num_actors,
        num_buffers=max(4 * envs_per_actor, 2 * num_actors + 2, 32),
        use_lstm=False,
        hidden_size=512 if pixels else 64,
        logger_backend="none",
        logger_frequency=10**9,
        save_model=False,
        max_timesteps=frames,
        num_envs=num_actors * envs_per_actor,
    )
    if pixels:
        env_fns = [
            (
                lambda: gym.vector.SyncVectorEnv(
                    [PixelRingEnv for _ in range(envs_per_actor)]
                )
            )
            for _ in range(num_actors)
        ]
        obs_shape, num_actions = (84, 84, 4), 6
        obs_dtype = np.uint8
    else:
        env_fns = [
            (
                lambda i=i: make_vect_envs(
                    "CartPole-v1", num_envs=envs_per_actor, seed=i, async_envs=False
                )
            )
            for i in range(num_actors)
        ]
        obs_shape, num_actions = (4,), 2
        obs_dtype = np.float32
    agent = ImpalaAgent(args, obs_shape=obs_shape, num_actions=num_actions, obs_dtype=obs_dtype)

    # Warm the jitted act/learn paths before the timed window: the first
    # learn call compiles for tens of seconds on CPU, during which actors
    # free-run and the measured fps reflects the compile window, not the
    # steady-state pipeline (observed: learn_steps == 1 for a whole budget).
    import jax.numpy as jnp

    from scalerl_tpu.data.trajectory import Trajectory

    T, Bl, Ba = args.rollout_length, args.batch_size, envs_per_actor
    warm = Trajectory(
        obs=jnp.zeros((T + 1, Bl) + obs_shape, obs_dtype),
        action=jnp.zeros((T + 1, Bl), jnp.int32),
        reward=jnp.zeros((T + 1, Bl), jnp.float32),
        done=jnp.zeros((T + 1, Bl), bool),
        logits=jnp.zeros((T + 1, Bl, num_actions), jnp.float32),
        core_state=agent.initial_state(Bl),
    )
    agent.learn(warm)
    agent.act(
        np.zeros((Ba,) + obs_shape, obs_dtype),
        np.zeros(Ba, np.int32),
        np.zeros(Ba, np.float32),
        np.ones(Ba, bool),
        agent.initial_state(Ba),
    )

    if mode == "processes":
        # monobeast topology: spawned actor processes with local CPU
        # inference over the C++ shm ring — the path that scales across
        # host cores (each actor is GIL-free and backend-independent)
        trainer = ProcessActorLearnerTrainer(
            args, agent, envs_per_actor=envs_per_actor
        )
    else:
        trainer = HostActorLearnerTrainer(args, agent, env_fns)
    warm_steps = int(agent.state.step)
    t0 = time.time()
    result = trainer.train(total_frames=frames)
    wall = time.time() - t0
    out = {
        "metric": f"host_actor_plane_fps_{kind}",
        "value": round(result["sps"], 1),
        "unit": "env-frames/sec (actors+learner, end to end)",
        "mode": mode,
        "num_actors": num_actors,
        "envs_per_actor": envs_per_actor,
        "frames": int(result["env_frames"]),
        "wall_s": round(wall, 1),
        "learn_steps": int(agent.state.step) - warm_steps,
    }
    # phase split (thread mode): actor model/step/write + learner
    # dequeue/learn mean seconds — the bottleneck analysis in
    # docs/PERFORMANCE.md reads these, not guesses
    if mode == "threads" and getattr(trainer, "actors", None):
        phases = {
            f"actor_{k}_ms": round(v * 1e3, 3)
            for k, v in trainer.actors[0].timings.means().items()
        }
        phases.update(
            {
                f"learner_{k}_ms": round(v * 1e3, 3)
                for k, v in trainer.learn_timings.means().items()
            }
        )
        out["phase_means"] = phases
    trainer.close()
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("kinds", nargs="*", default=["cartpole", "pixels"])
    ap.add_argument("--num-actors", type=int, default=2)
    ap.add_argument("--sweep", type=str, default="",
                    help="comma list of actor counts; one JSON line each "
                         "(overrides --num-actors), e.g. --sweep 1,2,4,8")
    ap.add_argument("--mode", choices=["threads", "processes"], default="threads",
                    help="threads = SEED central inference; processes = "
                         "monobeast spawned actors over the C++ shm ring")
    ap.add_argument("--envs-per-actor", type=int, default=8)
    ap.add_argument("--frames", type=int, default=40_000)
    ap.add_argument("--pixel-frames", type=int, default=0,
                    help="frame budget for the pixels config (default frames/4)")
    ap.add_argument("--cpu", action="store_true",
                    help="pin the CPU backend (handled at import; kept for --help)")
    args = ap.parse_args()
    counts = (
        [int(c) for c in args.sweep.split(",") if c]
        if args.sweep
        else [args.num_actors]
    )
    for kind in args.kinds or ["cartpole", "pixels"]:
        frames = args.frames if kind == "cartpole" else (
            args.pixel_frames or args.frames // 4
        )
        for n in counts:
            print(
                json.dumps(
                    bench_host(kind, n, args.envs_per_actor, frames, mode=args.mode)
                ),
                flush=True,
            )


if __name__ == "__main__":
    main()
