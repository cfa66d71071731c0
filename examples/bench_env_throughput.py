"""Env-throughput benchmark: fps of the framework's env/collector stacks.

Parity target: ``examples/test_env_throughput.py`` in the reference (:16-606)
— a harness comparing vectorized env stacks and logging frames/sec.  Stacks
compared here:

  sync-gym         in-process loop over N gymnasium envs
  async-gym        gymnasium AsyncVectorEnv (subprocess, pickled obs)
  shm-single       AsyncMultiAgentVecEnv + SingleAgentAdapter (shared plane)
  shm-multi        AsyncMultiAgentVecEnv over the built-in 2-agent toy env
  jax-vec          JAX-native vectorized env stepped under jit
  jax-scan         chunk of jax-vec steps fused in one lax.scan dispatch

``--env pixel`` runs the single-agent stacks on the SAME 84x84x4 uint8
env (``PixelRing-v0`` / ``SyntheticPixelEnv``) instead of CartPole —
the head-to-head the reference's harness runs against TorchRL collectors
(``examples/test_env_throughput.py:16-606``): at pixel shapes the obs
transport dominates, which is exactly what the shared-memory plane
(dtype-matched RawArray writes, no pickling) exists to win.

Usage: python examples/bench_env_throughput.py [--num-envs 8] [--steps 1000]
       [--env cartpole|pixel] [--stacks ...] [--json out.json]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _make_cartpole():
    # module-level: under auto-spawn (JAX live in this process after the
    # jax-vec stack runs) the factory must pickle into env workers
    import gymnasium as gym

    return gym.make("CartPole-v1")


def _make_pixel():
    # registration happens inside the factory so spawn-started workers
    # (fresh interpreters, empty gym registry) can build it too
    import gymnasium as gym

    from scalerl_tpu.envs.synthetic_gym import register_synthetic_envs

    register_synthetic_envs()
    return gym.make("PixelRing-v0")


_GYM_FACTORY = {"cartpole": _make_cartpole, "pixel": _make_pixel}
_JAX_ENV_ID = {"cartpole": "CartPole-v1", "pixel": "SyntheticPixel-v0"}


def bench_sync_gym(num_envs: int, steps: int, env_kind: str = "cartpole") -> float:
    envs = [_GYM_FACTORY[env_kind]() for _ in range(num_envs)]
    for i, e in enumerate(envs):
        e.reset(seed=i)
    t0 = time.perf_counter()
    for _ in range(steps):
        for e in envs:
            _, _, term, trunc, _ = e.step(e.action_space.sample())
            if term or trunc:
                e.reset()
    dt = time.perf_counter() - t0
    for e in envs:
        e.close()
    return steps * num_envs / dt


def bench_async_gym(num_envs: int, steps: int, env_kind: str = "cartpole") -> float:
    import gymnasium as gym

    from scalerl_tpu.utils.platform import safe_mp_context

    # the reference's default transport: subprocess workers, pipe commands
    # (obs ride gymnasium's own shared memory when dtypes allow).  Spawn
    # context when JAX is live in this process — forking after XLA starts
    # its thread pools clones held mutexes and deadlocks the workers
    vec = gym.vector.AsyncVectorEnv(
        [_GYM_FACTORY[env_kind]] * num_envs, context=safe_mp_context()
    )
    vec.reset(seed=0)
    actions = np.zeros(num_envs, np.int64)
    t0 = time.perf_counter()
    for _ in range(steps):
        vec.step(actions)
    dt = time.perf_counter() - t0
    vec.close()
    return steps * num_envs / dt


def bench_shm_single(num_envs: int, steps: int, env_kind: str = "cartpole") -> float:
    from scalerl_tpu.envs import make_shared_vec_envs

    vec = make_shared_vec_envs(_GYM_FACTORY[env_kind], num_envs)
    vec.reset(seed=0)
    actions = {"agent_0": np.zeros(num_envs, np.int64)}
    t0 = time.perf_counter()
    for _ in range(steps):
        vec.step(actions)
    dt = time.perf_counter() - t0
    vec.close()
    return steps * num_envs / dt


def bench_shm_multi(num_envs: int, steps: int, env_kind: str = "cartpole") -> float:
    from scalerl_tpu.envs import PursuitToyEnv, make_multi_agent_vec_env

    vec = make_multi_agent_vec_env(PursuitToyEnv, num_envs)
    vec.reset(seed=0)
    actions = {
        "chaser": np.ones(num_envs, np.int64),
        "runner": np.zeros(num_envs, np.int64),
    }
    t0 = time.perf_counter()
    for _ in range(steps):
        vec.step(actions)
    dt = time.perf_counter() - t0
    vec.close()
    # count agent-steps to compare fairly with single-agent stacks
    return steps * num_envs * 2 / dt


def bench_jax_vec(num_envs: int, steps: int, env_kind: str = "cartpole") -> float:
    import jax

    from scalerl_tpu.envs import make_jax_vec_env

    env = make_jax_vec_env(_JAX_ENV_ID[env_kind], num_envs)
    key = jax.random.PRNGKey(0)
    state, obs = env.reset(key)
    actions = np.zeros(num_envs, np.int32)
    state, *_ = env.step(state, actions, key)  # compile outside the timer
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for _ in range(steps):
        state, *_ = env.step(state, actions, key)
    jax.block_until_ready(state)
    dt = time.perf_counter() - t0
    return steps * num_envs / dt


def bench_jax_scan(
    num_envs: int, steps: int, env_kind: str = "cartpole", chunk: int = 64
) -> float:
    """The TPU-idiomatic shape: a chunk of env steps fused in one
    ``lax.scan`` dispatch, so host↔device latency amortizes over ``chunk``
    steps instead of being paid per step."""
    import jax
    import jax.numpy as jnp

    from scalerl_tpu.envs import make_jax_vec_env

    env = make_jax_vec_env(_JAX_ENV_ID[env_kind], num_envs)
    num_actions = env.num_actions
    key = jax.random.PRNGKey(0)
    state, obs = env.reset(key)

    @jax.jit
    def rollout_chunk(state, key):
        def body(carry, _):
            state, key = carry
            key, akey, skey = jax.random.split(key, 3)
            action = jax.random.randint(akey, (num_envs,), 0, num_actions)
            state, obs, reward, done = env.step(state, action, skey)
            return (state, key), reward

        (state, key), rewards = jax.lax.scan(
            body, (state, key), None, length=chunk
        )
        return state, key, rewards.sum()

    state, key, _ = rollout_chunk(state, key)  # compile outside the timer
    jax.block_until_ready(state)
    n_chunks = max(1, steps // chunk)
    t0 = time.perf_counter()
    for _ in range(n_chunks):
        state, key, _ = rollout_chunk(state, key)
    jax.block_until_ready(state)
    dt = time.perf_counter() - t0
    return n_chunks * chunk * num_envs / dt


STACKS = {
    "sync-gym": bench_sync_gym,
    "async-gym": bench_async_gym,
    "shm-single": bench_shm_single,
    "shm-multi": bench_shm_multi,
    "jax-vec": bench_jax_vec,
    "jax-scan": bench_jax_scan,
}


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--num-envs", type=int, default=8)
    parser.add_argument("--steps", type=int, default=500)
    parser.add_argument("--stacks", nargs="*", default=list(STACKS))
    parser.add_argument(
        "--env", default="cartpole", choices=("cartpole", "pixel"),
        help="pixel = same 84x84x4 uint8 env across stacks (obs-transport "
        "head-to-head); shm-multi is cartpole-toy-only and is skipped",
    )
    parser.add_argument("--json", default=None, help="also write results to this path")
    # the jax stacks touch the default backend; "cpu" pins them off the
    # chip (which would then belong to this process), "auto" benches the
    # accelerator when there is one
    parser.add_argument("--platform", default="auto")
    args = parser.parse_args()

    if args.platform != "auto":
        # only pin on request: "auto" must not force backend init here, or
        # a gym-stacks-only run would take the chip without using it (the
        # jax stacks init the backend lazily)
        from scalerl_tpu.utils.platform import setup_platform

        setup_platform(args.platform)
    print(f"env throughput: env={args.env} num_envs={args.num_envs} steps={args.steps}")
    stacks = []
    for s in args.stacks:
        if args.env == "pixel" and s == "shm-multi":
            print(f"  {s:<12} SKIPPED (cartpole-toy-only stack)")
            continue
        stacks.append(s)
    results = {}
    for name in stacks:
        try:
            fps = STACKS[name](args.num_envs, args.steps, args.env)
        except Exception as exc:  # a missing optional dep skips one stack
            print(f"  {name:<12} SKIPPED ({type(exc).__name__}: {exc})")
            continue
        results[name] = fps
        print(f"  {name:<12} {fps:>12,.0f} env-frames/sec")
    if results:
        best = max(results, key=results.get)
        print(f"best: {best} at {results[best]:,.0f} fps")
    if args.json:
        import json

        with open(args.json, "w") as f:
            json.dump(
                {"env": args.env, "num_envs": args.num_envs,
                 "steps": args.steps, "fps": results}, f, indent=2,
            )


if __name__ == "__main__":
    main()
