"""IMPALA training entry point.

Parity target: ``examples/test_impala_atari.py`` (which is import-broken in
the reference, SURVEY.md §2.4 — this one runs).  Two backends:

- ``--env-backend jax``  : fused on-device actor-learner loop (flagship
  throughput path; CartPole-v1 or SyntheticPixel-v0).
- ``--env-backend gym``  : host actors + device learner.  ``--actor-mode
  threads`` (default) runs SEED-RL topology (central batched inference);
  ``--actor-mode process`` runs monobeast topology (spawned actor processes
  with local CPU inference over the C++ shm ring — the reference's
  ``impala_atari.py`` architecture, GIL-free across host cores);
  ``--actor-mode serving`` runs the full centralized inference plane
  (``scalerl_tpu/serving/``): actors act through ``RemotePolicyClient``
  against an ``InferenceServer`` holding the one hot policy, with dynamic
  batching, generation-tagged params, and a latency SLO printed at the end
  (docs/DISTRIBUTED.md §4; knobs ``--serve-max-batch``,
  ``--serve-max-wait-ms``, ``--serve-max-pending``).

Usage::

    python examples/train_impala.py --env-backend jax --env-id CartPole-v1 \
        --max-timesteps 500000
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.numpy as jnp

from scalerl_tpu.agents.impala import ImpalaAgent
from scalerl_tpu.config import ImpalaArguments, parse_args
from scalerl_tpu.envs import make_jax_vec_env, make_vect_envs


def main(argv=None):
    """Train from ``argv`` (default ``sys.argv[1:]``); returns
    ``(trainer, final metrics)`` so a caller — ``chip_smoke.py`` — can check
    the run it just drove."""
    args = parse_args(ImpalaArguments, argv)
    from scalerl_tpu.utils.platform import setup_platform

    print("backend:", setup_platform(args.platform))

    if args.env_backend == "jax":
        from scalerl_tpu.trainer.actor_learner import DeviceActorLearnerTrainer

        mesh = None
        if args.mesh_shape:
            # Anakin: env lanes sharded over dp, grads psum-ed in the
            # fused step (the only axis that makes sense for this path)
            from scalerl_tpu.parallel import make_mesh

            mesh = make_mesh(args.mesh_shape)
            non_dp = [a for a in mesh.axis_names if a != "dp" and mesh.shape[a] > 1]
            if non_dp:
                raise SystemExit(
                    "the fused jax backend shards data-parallel only: use "
                    f'--mesh-shape "dp=N" (got {args.mesh_shape!r})'
                )
        venv = make_jax_vec_env(args.env_id, num_envs=args.num_envs)
        agent = ImpalaAgent(
            args,
            obs_shape=venv.observation_shape,
            num_actions=venv.num_actions,
            obs_dtype=venv.env.observation_dtype,
        )
        trainer = DeviceActorLearnerTrainer(args, agent, venv, mesh=mesh)
    else:
        from scalerl_tpu.trainer.actor_learner import HostActorLearnerTrainer

        envs_per_actor = max(args.num_envs // args.num_actors, 1)
        atari = args.env_id.startswith("ALE/") or "NoFrameskip" in args.env_id
        env_fns = [
            (
                lambda i=i: make_vect_envs(
                    args.env_id,
                    num_envs=envs_per_actor,
                    seed=args.seed + i,
                    async_envs=envs_per_actor > 1,
                    atari=atari,
                )
            )
            for i in range(args.num_actors)
        ]
        # probe spaces with ONE plain env — the trainer builds (and keeps)
        # its own vector probe, so spawning a second subprocess pool just to
        # read two space attributes would double the expensive env startup
        from scalerl_tpu.envs import make_gym_env

        probe = make_gym_env(args.env_id, seed=args.seed, atari=atari)()
        obs_shape = probe.observation_space.shape
        num_actions = probe.action_space.n
        probe.close()
        agent = ImpalaAgent(
            args,
            obs_shape=obs_shape,
            num_actions=num_actions,
            obs_dtype=jnp.uint8 if len(obs_shape) == 3 else jnp.float32,
        )
        if args.mesh_shape:
            # shard the learn step over the mesh; batches arrive host-side
            # here (unlike the fused jax backend), so this is the path that
            # exercises dp/fsdp/tp sharding with real envs
            agent.enable_mesh(args.mesh_shape)
        if args.actor_mode == "process":
            from scalerl_tpu.trainer.process_actor_learner import (
                ProcessActorLearnerTrainer,
            )

            trainer = ProcessActorLearnerTrainer(args, agent)
        else:
            trainer = HostActorLearnerTrainer(args, agent, env_fns)

    try:
        result = trainer.train(total_frames=args.total_steps)
        print("final:", {k: round(float(v), 3) for k, v in result.items()})
        if getattr(trainer, "inference_server", None) is not None:
            slo = trainer.inference_server.slo()
            print("serving SLO:", {k: round(float(v), 3) for k, v in slo.items()})
        if args.save_model and not args.disable_checkpoint:
            path = agent.save_checkpoint(os.path.join(trainer.model_save_dir, "ckpt_final"))
            print("checkpoint:", path)
    finally:
        trainer.close()
    return trainer, result


if __name__ == "__main__":
    main()
