"""Traffic driver ``fused_loop``: the fused classic actor-learner loop,
closed loop, one dispatch after another.

Builds the ``DeviceActorLearnerTrainer`` exactly as
``examples/train_impala.py`` builds it from the same arguments, and drives
its ``loop.run(..., on_metrics=..., should_stop=...)`` once: the first
``warmup_chunks`` chunks are set-up (the first compiles), the window opens
when the last of them has been read back, and every later chunk counts
when its metric read completes.  Parameters (``workloads/<cell>.json``):
``argv`` (program arguments beside the configuration's own), ``num_envs``,
``iters_per_dispatch``, ``chunks_in_flight``, ``warmup_chunks``.
"""

from __future__ import annotations

import math
from types import SimpleNamespace

import numpy as np


def build(ctx):
    import jax
    from scalerl_tpu.agents.impala import ImpalaAgent
    from scalerl_tpu.config import ImpalaArguments, parse_args
    from scalerl_tpu.envs import make_jax_vec_env
    from scalerl_tpu.trainer.actor_learner import DeviceActorLearnerTrainer

    from harness import ROOT

    p = ctx.params
    argv = (
        list(p["argv"]) + ctx.reference.program_argv(ctx.config)
        + ["--num-envs", str(p["num_envs"]), "--seed", str(ctx.seed),
           "--platform", "cpu" if ctx.rehearse else "tpu",
           "--work-dir", str(ROOT / "work_dirs" / "bench")]
    )
    args = parse_args(ImpalaArguments, argv)
    venv = make_jax_vec_env(args.env_id, num_envs=args.num_envs)
    agent = ImpalaAgent(
        args, obs_shape=venv.observation_shape, num_actions=venv.num_actions,
        obs_dtype=venv.env.observation_dtype,
    )
    trainer = DeviceActorLearnerTrainer(
        args, agent, venv, iters_per_call=p["iters_per_dispatch"],
        chunks_in_flight=p["chunks_in_flight"],
    )
    key = jax.random.PRNGKey(ctx.seed)
    carry = trainer.loop.init_carry(key)
    frames_per_chunk = args.rollout_length * args.num_envs * p["iters_per_dispatch"]
    ctx.log(f"trainer built; {frames_per_chunk} frames per dispatch")
    return SimpleNamespace(
        trainer=trainer, agent=agent, key=key, carry=carry,
        frames_per_chunk=frames_per_chunk,
    )


def run(ctx, st):
    p = ctx.params
    warm = int(p["warmup_chunks"])
    seen = {"chunks": 0, "in_window": 0, "stop": False, "last": {}}

    def on_metrics(i, m):
        # fires when chunk i's one batched metric read has completed
        seen["chunks"] += 1
        seen["last"] = m
        if ctx.t_close is not None:
            return  # chunks still in flight when the window closed
        if i + 1 < warm:
            return
        if i + 1 == warm:
            ctx.open_window()
            ctx.spans.roll("bench.chunk_wait")
            return
        ctx.spans.roll("bench.chunk_wait")
        seen["in_window"] += 1
        if ctx.tick({"chunks": seen["in_window"]}):
            ctx.spans.end_rolling()
            ctx.close_window({"chunks": seen["in_window"]})
            seen["stop"] = True

    state, carry, metrics = st.trainer.loop.run(
        st.agent.state, st.carry, st.key, 10**9, on_metrics=on_metrics,
        chunks_in_flight=st.trainer.chunks_in_flight,
        should_stop=lambda: seen["stop"],
        instrument=st.trainer._instrument,
    )
    st.agent.state = state
    st.final = metrics
    frames = seen["in_window"] * st.frames_per_chunk
    return {
        "attempted": int(metrics["chunks_done"]),
        "failed": int(metrics["nonfinite_chunks"]),
        "end_to_end": {"env_frames_per_s": frames / ctx.window_s},
        "counters": {
            "chunks_in_window": seen["in_window"],
            "frames_in_window": frames,
            "frames_per_chunk": st.frames_per_chunk,
            "chunks_read": seen["chunks"],
        },
    }


def check(ctx, st, result):
    """Seeded frames through the program's model against the plain
    reference, then the run's own counts and flags."""
    import jax.numpy as jnp

    notes = {}
    rng = np.random.default_rng(ctx.seed)
    n = 64
    frames = jnp.asarray(rng.integers(0, 256, (n, 84, 84, 4), dtype=np.uint8))
    action = jnp.asarray(rng.integers(0, ctx.config["num_actions"], (n,)).astype(np.int32))
    reward = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    params = st.agent.state.params
    ref_logits, ref_base = ctx.reference.forward(params, frames, action, reward, ctx.config)
    out, _core = st.agent.model.apply(
        params, frames[None], action[None], reward[None], jnp.zeros((1, n), bool), (),
    )
    err_logits = float(jnp.max(jnp.abs(out.policy_logits[0] - ref_logits)))
    err_base = float(jnp.max(jnp.abs(out.baseline[0] - ref_base)))
    scale = float(jnp.max(jnp.abs(ref_logits)))
    notes.update(logits_max_err=err_logits, baseline_max_err=err_base, logits_scale=scale)
    # the torso computes in bfloat16 (8 mantissa bits, 4e-3 relative per
    # rounding) through four layers against a float32 reference: about one
    # hundredth of the outputs' scale, which grows as the run trains.  A
    # dropped layer or an 8-bit torso is far outside; float32 far inside.
    tol = float(ctx.params["logits_rtol"]) * max(1.0, scale, float(jnp.max(jnp.abs(ref_base))))
    ok = err_logits <= tol and err_base <= tol
    final = st.final
    counters = result["counters"]
    ok = ok and math.isfinite(final["total_loss"])
    ok = ok and final["nonfinite_chunks"] == 0.0
    ok = ok and final.get("skipped_steps", 0.0) == 0.0
    ok = ok and final.get("nonfinite_grads", 0.0) == 0.0
    ok = ok and counters["chunks_read"] == int(final["chunks_done"])
    ok = ok and counters["frames_in_window"] == (
        counters["chunks_in_window"] * counters["frames_per_chunk"]
    )
    notes.update(total_loss=final["total_loss"], chunks_done=final["chunks_done"])
    return ok, notes
