"""IMPALA-family curves: fused device loop, host actor plane, V-trace lag proof."""

from __future__ import annotations

import time

import jax
import numpy as np

from curves.common import OUT_DIR, _first_crossing, _run_fused_to_threshold, _tb_logger


def impala_synthetic(
    size: int = 24,
    num_states: int = 4,
    num_actions: int = 4,
    episode_length: int = 64,
    max_frames: int = 500_000,
    threshold_frac: float = 0.85,
    seed: int = 0,
    log=None,
):
    """Fused device-loop IMPALA on synthetic pixels to near-optimal return.

    Optimal return == episode_length (reward 1 per step under the correct
    obs-conditioned action); threshold is ``threshold_frac`` of optimal,
    measured over the episodes completed since the previous fused call.
    """
    from scalerl_tpu.envs.jax_envs.synthetic import SyntheticPixelEnv

    env = SyntheticPixelEnv(
        size=size,
        num_states=num_states,
        num_actions=num_actions,
        episode_length=episode_length,
    )
    return _run_fused_to_threshold(
        "impala_synthetic",
        env,
        f"SyntheticPixelEnv({size}x{size}x4, {num_states} states)",
        threshold=threshold_frac * episode_length,
        optimal_return=episode_length,
        max_frames=max_frames,
        learning_rate=6e-4,
        seed=seed,
        log=log,
    )


def impala_synthetic_northstar(
    max_frames: int = 30_000_000,
    sticky_prob: float = 0.25,
    threshold_frac: float = 0.85,
    num_envs: int = 256,
    seed: int = 0,
    log=None,
):
    """The exact bench configuration as a LEARNING configuration (VERDICT
    r2 #7): fused device-loop IMPALA at the full north-star shape —
    84x84x4 uint8 frames, 16 states, 6 actions, AtariNet-512 torso — with
    ALE-style sticky actions so the dynamics are stochastic and a policy
    cannot exploit determinism.

    Threshold accounting: with sticky probability p, even the optimal
    policy's chosen action is replaced by the previous action ~p of the
    time, and a repeated action is wrong at the next cell (the correct-
    action map never repeats across consecutive cells), so expected
    optimal return ~= (1-p) * episode_length.  The bar is
    ``threshold_frac`` of that; random play scores ~episode_length/6.

    Intended for accelerator runs (~tens of seconds at TPU fused-loop
    rates); on CPU this would take hours — run it on the chip.
    """
    from scalerl_tpu.envs.jax_envs.synthetic import SyntheticPixelEnv

    episode_length = 128
    env = SyntheticPixelEnv(
        size=84, stack=4, num_actions=6, num_states=16,
        episode_length=episode_length, sticky_prob=sticky_prob,
    )
    effective_optimal = (1.0 - sticky_prob) * episode_length
    return _run_fused_to_threshold(
        "impala_synthetic_northstar",
        env,
        f"SyntheticPixelEnv(84x84x4, 16 states, sticky={sticky_prob})",
        threshold=threshold_frac * effective_optimal,
        optimal_return=round(effective_optimal, 1),
        max_frames=max_frames,
        learning_rate=6e-4,
        num_envs=num_envs,
        hidden_size=512,
        seed=seed,
        log=log,
    )


def impala_catch(
    size: int = 24,
    max_frames: int = 600_000,
    threshold: float = 0.85,
    seed: int = 0,
    log=None,
):
    """Fused device-loop IMPALA on Catch — the flagship learning evidence:
    spatio-temporal pixel control (track a falling ball, single delayed
    terminal reward), the smallest Pong-shaped task (BASELINE.md's ALE
    north star is unavailable in this image).  Threshold 0.85 ~= 92.5%
    catch rate (returns are +-1 per episode)."""
    from scalerl_tpu.envs import JaxCatch

    return _run_fused_to_threshold(
        "impala_catch",
        JaxCatch(size=size),
        f"JaxCatch({size}x{size}, device-native)",
        threshold=threshold,
        optimal_return=1.0,
        max_frames=max_frames,
        learning_rate=1e-3,
        seed=seed,
        log=log,
    )


# ----------------------------------------------------------------------
def impala_cartpole(
    num_actors: int = 2,
    envs_per_actor: int = 8,
    max_frames: int = 400_000,
    threshold: float = 400.0,
    seed: int = 0,
):
    """Host actor plane (SEED-style central inference) to a CartPole
    return threshold; doubles as the host-path throughput measurement."""
    from scalerl_tpu.agents.impala import ImpalaAgent
    from scalerl_tpu.config import ImpalaArguments
    from scalerl_tpu.envs import make_vect_envs
    from scalerl_tpu.trainer.actor_learner import HostActorLearnerTrainer

    args = ImpalaArguments(
        env_id="CartPole-v1",
        rollout_length=16,
        batch_size=16,
        num_actors=num_actors,
        num_buffers=32,
        use_lstm=False,
        hidden_size=64,
        learning_rate=2e-3,
        entropy_cost=0.01,
        gamma=0.99,
        seed=seed,
        logger_backend="tensorboard",
        logger_frequency=5_000,
        work_dir=str(OUT_DIR),
        project="",
        save_model=False,
        max_timesteps=max_frames,
    )
    args.validate()
    agent = ImpalaAgent(args, obs_shape=(4,), num_actions=2, obs_dtype=np.float32)
    env_fns = [
        (
            lambda i=i: make_vect_envs(
                "CartPole-v1", num_envs=envs_per_actor, seed=seed + i, async_envs=False
            )
        )
        for i in range(num_actors)
    ]
    trainer = HostActorLearnerTrainer(args, agent, env_fns, run_name="impala_cartpole")
    t0 = time.time()
    result = trainer.train(total_frames=max_frames)
    wall = time.time() - t0
    hit_frames = _first_crossing(trainer.tb_log_dir, "train/return_mean", threshold)
    trainer.close()
    return {
        "experiment": "impala_cartpole",
        "env": "CartPole-v1",
        "algo": "IMPALA (host actor plane, central inference)",
        "threshold": threshold,
        "final_return": round(result.get("return_mean", float("nan")), 2),
        "frames": int(trainer.env_frames),
        "frames_to_threshold": hit_frames,
        "wall_s": round(wall, 1),
        "fps": round(result.get("sps", float("nan")), 1),
        "passed": hit_frames is not None,
    }


# ----------------------------------------------------------------------


def run_lagged_arm(
    force_on_policy_rhos: bool,
    pull_every: int = 5,
    iters: int = 240,
    seed: int = 0,
    on_window=None,
) -> float:
    """One arm of the off-policy-lag proof; returns the final windowed
    return.  THE shared harness — ``tests/test_offpolicy_lag.py`` asserts
    over it and ``impala_offpolicy_lag`` records it, so the calibrated
    setup cannot drift between the test and the curve.

    Behavior weights refresh only every ``pull_every`` learner steps
    through a real ``ParameterServer`` (the host planes' weight-pull
    cadence), so rollouts are collected 0..pull_every-1 updates stale.
    ``force_on_policy_rhos`` replaces the behavior logits with the target
    policy's own — log-rhos become exactly 0 (V-trace told the data is
    on-policy) and nothing else changes.  ``on_window(frames, windowed)``
    fires every 20 updates.
    """
    from scalerl_tpu.agents.impala import ImpalaAgent, make_impala_learn_fn
    from scalerl_tpu.config import ImpalaArguments
    from scalerl_tpu.envs import make_jax_vec_env
    from scalerl_tpu.runtime.device_loop import DeviceActorLearnerLoop
    from scalerl_tpu.runtime.param_server import ParameterServer

    args = ImpalaArguments(
        env_id="CartPole-v1", rollout_length=16, batch_size=16,
        use_lstm=False, hidden_size=64, logger_backend="none",
        learning_rate=1e-2, entropy_cost=0.01, gamma=0.99,
    )
    venv = make_jax_vec_env("CartPole-v1", num_envs=16)
    agent = ImpalaAgent(
        args, obs_shape=(4,), num_actions=2,
        obs_dtype=jax.numpy.float32, key=jax.random.PRNGKey(seed),
    )
    learn = jax.jit(make_impala_learn_fn(agent.model, agent.optimizer, args))
    loop = DeviceActorLearnerLoop(
        model=agent.model, venv=venv, learn_fn=learn,
        unroll_length=args.rollout_length, iters_per_call=1,
    )
    unroll = jax.jit(loop._unroll)
    model = agent.model

    @jax.jit
    def learn_rho1(state, traj):
        out, _ = model.apply(
            state.params, traj.obs, traj.action, traj.reward, traj.done,
            traj.core_state,
        )
        logits = jax.lax.stop_gradient(out.policy_logits)
        logits = logits.at[-1].set(0.0)  # row T convention: unused, zero
        return learn(state, traj.replace(logits=logits))

    server = ParameterServer()
    server.push(jax.device_get(agent.state.params))
    state = agent.state
    behavior_params = None
    key = jax.random.PRNGKey(seed + 1)
    carry = loop.init_carry(key)
    prev_sum = prev_cnt = 0.0
    windowed = 0.0
    for i in range(iters):
        if i % pull_every == 0:
            w, _v = server.pull(have_version=-1)
            behavior_params = jax.tree_util.tree_map(jax.numpy.asarray, w)
        key, sub = jax.random.split(key)
        carry, traj = unroll(behavior_params, carry, sub)
        state, _m = (
            learn_rho1(state, traj) if force_on_policy_rhos
            else learn(state, traj)
        )
        server.push(jax.device_get(state.params))
        if (i + 1) % 20 == 0:
            s = float(jax.numpy.sum(carry.return_sum))
            c = float(jax.numpy.sum(carry.episode_count))
            if c > prev_cnt:
                windowed = (s - prev_sum) / (c - prev_cnt)
                prev_sum, prev_cnt = s, c
            if on_window is not None:
                on_window((i + 1) * args.rollout_length * 16, windowed)
    return windowed


def impala_offpolicy_lag(
    pull_every: int = 5,
    iters: int = 240,
    seed: int = 0,
    log=None,
):
    """Off-policy-lag proof as a recorded curve (VERDICT r2 #4): the two
    arms of :func:`run_lagged_arm` share seeds; the gap between them is
    the measured value of V-trace.  Assertion form:
    ``tests/test_offpolicy_lag.py``."""
    logger = log or _tb_logger("impala_offpolicy_lag")
    t0 = time.time()
    threshold = 25.0  # calibrated: vtrace ~50, rho1 ~9.4 (random ~9.4)
    crossing = {"frames": None}

    def log_vtrace(f, w):
        if crossing["frames"] is None and w >= threshold:
            crossing["frames"] = f
        logger.log_train_data({"return_windowed_vtrace": w}, f)

    vtrace_ret = run_lagged_arm(
        False, pull_every, iters, seed, on_window=log_vtrace
    )
    rho1_ret = run_lagged_arm(
        True, pull_every, iters, seed,
        on_window=lambda f, w: logger.log_train_data(
            {"return_windowed_rho1": w}, f
        ),
    )
    wall = time.time() - t0
    logger.close()
    frames = 2 * iters * 16 * 16
    return {
        "experiment": "impala_offpolicy_lag",
        "env": f"CartPole-v1 (behavior weights {pull_every} steps stale)",
        "algo": "IMPALA V-trace vs rho=1 ablation",
        "threshold": threshold,
        "optimal_return": 500.0,
        "final_return": round(vtrace_ret, 1),
        "rho1_ablation_return": round(rho1_ret, 1),
        "frames": frames,
        # the vtrace arm's actual windowed-return crossing, observed by
        # the logging callback (None if the threshold was never crossed)
        "frames_to_threshold": crossing["frames"],
        "wall_s": round(wall, 1),
        "fps": round(frames / wall, 1),
        "passed": bool(vtrace_ret >= threshold and rho1_ret < vtrace_ret / 1.8),
    }


# ----------------------------------------------------------------------


def impala_recall_lstm(
    size: int = 16,
    delay: int = 6,
    max_frames: int = 400_000,
    threshold: float = 0.8,
    seed: int = 0,
):
    """Recurrent learning evidence: delayed-recall on the fused device loop.

    The cue flashes in frame 0 only and the rewarded action happens
    ``delay`` blank frames later, so a memoryless policy is pinned at
    ``2/num_actions - 1 = -0.5`` expected return — crossing ``threshold``
    proves the done-masked LSTM carry learns end to end (the Catch /
    Synthetic curves use feed-forward torsos and cannot show this).  A
    feed-forward control arm runs the same config at the LSTM arm's frame
    budget; its ceiling-at-chance return lands in the summary row.
    """
    from scalerl_tpu.envs import JaxRecall

    env = JaxRecall(size=size, delay=delay, num_cues=4)
    label = f"JaxRecall({size}x{size}, delay={delay}, device-native)"
    common = dict(
        threshold=threshold, optimal_return=1.0, learning_rate=1e-3,
        num_envs=32, unroll=8, iters_per_call=5, seed=seed,
        hidden_size=64, entropy_cost=0.02,
    )
    row = _run_fused_to_threshold(
        "impala_recall_lstm", env, label, max_frames=max_frames,
        use_lstm=True,
        algo_label="IMPALA conv+LSTM (fused device loop); FF control at chance",
        **common,
    )
    # control: same config, no memory, matched to the LSTM arm's budget
    ff = _run_fused_to_threshold(
        "impala_recall_ff_control", env, label, max_frames=row["frames"],
        use_lstm=False, algo_label="FF control", **common,
    )
    row["ff_control_return"] = ff["final_return"]
    row["passed"] = bool(row["passed"] and ff["final_return"] < 0.0)
    return row


# ----------------------------------------------------------------------


# ----------------------------------------------------------------------


def impala_breakout(
    size: int = 10,
    max_frames: int = 2_000_000,
    threshold: float = 20.0,
    seed: int = 0,
    log=None,
):
    """Fused device-loop IMPALA on device-native Breakout — the flagship
    wall-clock-to-score task (VERDICT r3 missing #3: ALE ROMs absent, so
    this MinAtar-style game is the strongest stand-in for the Pong row).
    Calibration (tests/test_envs.py): a scripted ball-tracker averages ~62
    per episode, random play ~0.4 — threshold 20 is far beyond any
    control-free policy."""
    from scalerl_tpu.envs import JaxBreakout

    return _run_fused_to_threshold(
        "impala_breakout",
        JaxBreakout(size=size),
        f"JaxBreakout({size}x{size}, device-native)",
        threshold=threshold,
        optimal_return=62.0,  # scripted-tracker calibration
        max_frames=max_frames,
        learning_rate=1e-3,
        seed=seed,
        log=log,
    )


def run_host_breakout_arm(
    arm: str,
    num_actors: int = 2,
    envs_per_actor: int = 8,
    batch_size: int = 16,
    rollout_length: int = 20,
    num_buffers: int | None = None,
    entropy_cost: float = 0.01,
    entropy_cost_end: float | None = None,
    entropy_anneal_frames: int = 0,
    force_on_policy_rhos: bool = False,
    max_frames: int = 1_500_000,
    threshold: float = 20.0,
    seed: int = 0,
    work_dir=None,
    run_name: str | None = None,
):
    """THE host-plane Breakout recipe, parameterized — shared by the
    recorded baseline (:func:`impala_breakout_host`) and every arm of the
    ablation matrix (``examples/curves/host_ablation.py``), so the
    "same protocol" claim is one code path, not two that can drift.

    ``force_on_policy_rhos``: the off-policy-lag proof's rho=1 trick
    (:func:`run_lagged_arm`) applied to the live plane — behavior logits
    are recomputed under the CURRENT params before each update, making
    V-trace's rho/c clipping inert.
    """
    from scalerl_tpu.agents.impala import ImpalaAgent
    from scalerl_tpu.config import ImpalaArguments
    from scalerl_tpu.envs import make_vect_envs
    from scalerl_tpu.envs.synthetic_gym import register_synthetic_envs
    from scalerl_tpu.trainer.actor_learner import HostActorLearnerTrainer

    register_synthetic_envs()
    n_slots = max(batch_size // envs_per_actor, 1)
    if num_buffers is None:
        # minimal slot queue: depth IS worst-case policy lag (the old
        # 2*batch_size floor compared slots to lanes — 16x too deep)
        num_buffers = max(2 * n_slots, num_actors)
    args = ImpalaArguments(
        env_id="BreakoutGym-v0",
        rollout_length=rollout_length,
        batch_size=batch_size,
        num_actors=num_actors,
        num_buffers=num_buffers,
        use_lstm=False,
        hidden_size=256,
        learning_rate=1e-3,
        entropy_cost=entropy_cost,
        entropy_cost_end=entropy_cost_end,
        entropy_anneal_frames=entropy_anneal_frames,
        gamma=0.99,
        seed=seed,
        logger_backend="tensorboard",
        logger_frequency=10_000,
        work_dir=str(work_dir if work_dir is not None else OUT_DIR),
        project="",
        save_model=False,
        max_timesteps=max_frames,
    )
    args.validate()
    agent = ImpalaAgent(args, obs_shape=(10, 10, 1), num_actions=3, obs_dtype=np.uint8)
    if force_on_policy_rhos:
        model, base_learn = agent.model, agent._learn

        @jax.jit
        def learn_rho1(state, traj):
            out, _ = model.apply(
                state.params, traj.obs, traj.action, traj.reward,
                traj.done, traj.core_state,
            )
            logits = jax.lax.stop_gradient(out.policy_logits)
            logits = logits.at[-1].set(0.0)  # row T convention: unused
            return base_learn(state, traj.replace(logits=logits))

        agent._learn = learn_rho1
    env_fns = [
        (
            lambda i=i: make_vect_envs(
                "BreakoutGym-v0", num_envs=envs_per_actor, seed=seed + i,
                async_envs=False,
            )
        )
        for i in range(num_actors)
    ]
    trainer = HostActorLearnerTrainer(
        args, agent, env_fns, run_name=run_name or f"host_breakout_{arm}"
    )
    t0 = time.time()
    result = trainer.train(total_frames=max_frames)
    wall = time.time() - t0
    hit_frames = _first_crossing(trainer.tb_log_dir, "train/return_mean", threshold)
    trainer.close()
    return {
        "arm": arm,
        "geometry": f"{num_actors}x{envs_per_actor} lanes, B={batch_size}, "
        f"T={rollout_length}, buffers={num_buffers}",
        "entropy": (
            f"{entropy_cost}->{entropy_cost_end} over {entropy_anneal_frames}"
            if entropy_cost_end is not None
            else f"{entropy_cost}"
        ),
        "rho1": force_on_policy_rhos,
        "threshold": threshold,
        "final_return": round(result.get("return_mean", float("nan")), 2),
        "frames": int(trainer.env_frames),
        "frames_to_threshold": hit_frames,
        "wall_s": round(wall, 1),
        "fps": round(result.get("sps", float("nan")), 1),
        "passed": hit_frames is not None,
    }


def run_fused_lagged_breakout(
    arm: str,
    pull_every: int = 2,
    max_frames: int = 1_500_000,
    threshold: float = 20.0,
    seed: int = 0,
):
    """The lag-isolation arm of the host-plane ablation: the FUSED device
    loop on JaxBreakout, but unrolling under a STALE behavior snapshot
    refreshed every ``pull_every`` learner steps (the
    :func:`run_lagged_arm` harness at Breakout scale).

    ``pull_every=1`` reproduces the fused loop exactly (behavior == params
    at every chunk start — the structural on-policyness of
    ``DeviceActorLearnerLoop``); ``pull_every=2`` is one chunk of lag,
    the host plane's floor.  Everything else (env, net, hyperparameters,
    V-trace, geometry B=16/T=20) is identical to ``impala_breakout`` —
    so any learning gap between pull_every=1 and 2 is attributable to
    lag alone.
    """
    from scalerl_tpu.agents.impala import ImpalaAgent, make_impala_learn_fn
    from scalerl_tpu.config import ImpalaArguments
    from scalerl_tpu.envs import JaxBreakout
    from scalerl_tpu.envs.jax_envs.base import JaxVecEnv

    from curves.common import _tb_logger
    from scalerl_tpu.runtime.device_loop import DeviceActorLearnerLoop

    B, T = 16, 20
    args = ImpalaArguments(
        use_lstm=False, hidden_size=256, rollout_length=T, batch_size=B,
        learning_rate=1e-3, entropy_cost=0.01, gamma=0.99, max_timesteps=0,
    )
    env = JaxBreakout(size=10)
    venv = JaxVecEnv(env, num_envs=B)
    agent = ImpalaAgent(
        args, obs_shape=env.observation_shape, num_actions=env.num_actions,
        key=jax.random.PRNGKey(seed),
    )
    learn = jax.jit(make_impala_learn_fn(agent.model, agent.optimizer, args))
    loop = DeviceActorLearnerLoop(
        model=agent.model, venv=venv, learn_fn=learn,
        unroll_length=T, iters_per_call=1,
    )
    unroll = jax.jit(loop._unroll)
    # timestamped: a --force re-run must not stack its event file into the
    # prior run's dir (same hazard the host arms avoid the same way)
    logger = _tb_logger(f"host_ablation_{arm}_{int(time.time())}")

    state = agent.state
    behavior = state.params  # device-side snapshot; no host round-trip
    key = jax.random.PRNGKey(seed + 1)
    carry = loop.init_carry(key)
    frames_per_iter = T * B
    iters = max_frames // frames_per_iter
    prev_sum = prev_cnt = 0.0
    windowed = 0.0
    hit_frames = None
    t0 = time.time()
    for i in range(iters):
        if i % pull_every == 0:
            behavior = state.params
        key, sub = jax.random.split(key)
        carry, traj = unroll(behavior, carry, sub)
        state, _m = learn(state, traj)
        if (i + 1) % 50 == 0:
            s = float(jax.numpy.sum(carry.return_sum))
            c = float(jax.numpy.sum(carry.episode_count))
            if c > prev_cnt:
                windowed = (s - prev_sum) / (c - prev_cnt)
                prev_sum, prev_cnt = s, c
            frames = (i + 1) * frames_per_iter
            logger.log_train_data({"return_windowed": windowed}, frames)
            if hit_frames is None and windowed >= threshold:
                hit_frames = frames
    wall = time.time() - t0
    logger.close()
    frames = iters * frames_per_iter
    return {
        "arm": arm,
        "geometry": f"fused device loop, B={B}, T={T}, "
        f"behavior refreshed every {pull_every} updates",
        "entropy": f"{args.entropy_cost}",
        "rho1": False,
        "threshold": threshold,
        "final_return": round(windowed, 2),
        "frames": frames,
        "frames_to_threshold": hit_frames,
        "wall_s": round(wall, 1),
        "fps": round(frames / wall, 1),
        "passed": hit_frames is not None,
    }


def impala_breakout_84(
    max_frames: int = 4_000_000,
    threshold: float = 20.0,
    num_envs: int = 32,
    seed: int = 0,
    log=None,
):
    """The flagship wall-clock-to-score protocol at ALE PIXEL SCALE
    (VERDICT r4 #6): the same 10x10 Breakout dynamics rendered at
    84x84x4 uint8 (nearest-neighbor upscale — ALE Breakout is likewise a
    small machine state rendered big), AtariNet-256 conv torso, fused
    device loop.  Same threshold-20 bar as ``impala_breakout``; the fps
    column now prices the conv stack at the BASELINE.md Pong-row shape.

    Sized for the TPU (fused-loop throughput on the chip: not measured
    yet, see PERF.md).  On CPU run with a small --max-frames for a trend
    check, not to threshold."""
    from scalerl_tpu.envs import JaxBreakout

    return _run_fused_to_threshold(
        "impala_breakout_84",
        JaxBreakout(size=10, stack=4, render_size=84),
        "JaxBreakout(10x10 dynamics at 84x84x4, device-native)",
        threshold=threshold,
        optimal_return=62.0,  # scripted-tracker calibration (dynamics unchanged)
        max_frames=max_frames,
        learning_rate=1e-3,
        num_envs=num_envs,
        seed=seed,
        log=log,
    )


def impala_breakout_host(
    num_actors: int = 2,
    envs_per_actor: int = 8,
    max_frames: int = 2_000_000,
    threshold: float = 20.0,
    seed: int = 0,
):
    """Host actor plane (SEED-style central inference) on the numpy twin
    of Breakout — the same wall-clock-to-score protocol on the CPU-env
    topology, so both planes have a recorded time-to-threshold.  Delegates
    to :func:`run_host_breakout_arm` (the single shared recipe).

    History: seven round-4/5 runs at T=20 plateaued at the one-bounce
    rally level (2-5.6) while the fused loop crossed 20 at ~1M frames.
    Round 5's ablation matrix (``examples/curves/host_ablation.py``,
    table in docs/LEARNING_CURVES.md) isolated chunk-scale behavior
    staleness as the cause — one chunk of T=20 lag collapses even the
    fused loop to the same plateau — and with T=10 this recipe CROSSES:
    threshold 20 at ~847k frames, final return 45.0 at 2M (recorded)."""
    row = run_host_breakout_arm(
        "baseline",
        num_actors=num_actors,
        envs_per_actor=envs_per_actor,
        # T=10: the round-5 ablation isolated the unroll-chunk length as
        # THE cause of the old T=20 plateau (bt_T10 crossed at 827k frames
        # where seven T=20 runs plateaued at 2-5.6; docs/LEARNING_CURVES.md
        # ablation table) — short chunks halve worst-case behavior
        # staleness and double the update rate per frame
        rollout_length=10,
        max_frames=max_frames,
        threshold=threshold,
        seed=seed,
        run_name="impala_breakout_host",
    )
    return {
        "experiment": "impala_breakout_host",
        "env": "BreakoutGym-v0 (numpy twin)",
        "algo": "IMPALA (host actor plane, central inference)",
        "threshold": row["threshold"],
        "optimal_return": 62.0,
        "final_return": row["final_return"],
        "frames": row["frames"],
        "frames_to_threshold": row["frames_to_threshold"],
        "wall_s": row["wall_s"],
        "fps": row["fps"],
        "passed": row["passed"],
    }


def impala_pong_ale(
    num_actors: int = 8,
    envs_per_actor: int = 4,
    max_frames: int = 30_000_000,
    threshold: float = 18.0,
    seed: int = 0,
):
    """BASELINE.md's primary metric — wall-clock to Pong score 18 — gated
    on ALE ROM presence (absent from this image): returns a skipped row
    immediately when unavailable, runs the full recipe the moment ROMs
    exist (reference entry: ``scalerl/algorithms/impala/impala_atari.py:
    403-494``)."""
    row = {
        "experiment": "impala_pong_ale",
        "env": "ALE/Pong-v5",
        "algo": "IMPALA (host actor plane, DeepMind Atari stack)",
        "threshold": threshold,
        "optimal_return": 21.0,
        "final_return": None,
        "frames": 0,
        "frames_to_threshold": None,
        "wall_s": 0.0,
        "fps": 0.0,
        "passed": False,
    }
    try:
        import gymnasium as gym

        gym.make("ALE/Pong-v5").close()
    except Exception as e:  # noqa: BLE001 — any failure means no ROMs
        row["skipped"] = f"ALE unavailable: {type(e).__name__}: {e}"[:200]
        return row

    from scalerl_tpu.agents.impala import ImpalaAgent
    from scalerl_tpu.config import ImpalaArguments
    from scalerl_tpu.envs import make_vect_envs
    from scalerl_tpu.trainer.actor_learner import HostActorLearnerTrainer

    args = ImpalaArguments(
        env_id="ALE/Pong-v5",
        rollout_length=20,
        batch_size=32,
        num_actors=num_actors,
        num_buffers=64,
        use_lstm=True,
        hidden_size=256,
        learning_rate=6e-4,
        entropy_cost=0.01,
        gamma=0.99,
        seed=seed,
        logger_backend="tensorboard",
        logger_frequency=100_000,
        work_dir=str(OUT_DIR),
        project="",
        save_model=True,
        max_timesteps=max_frames,
    )
    args.validate()
    agent = ImpalaAgent(
        args, obs_shape=(84, 84, 4), num_actions=6, obs_dtype=np.uint8
    )
    env_fns = [
        (
            lambda i=i: make_vect_envs(
                "ALE/Pong-v5", num_envs=envs_per_actor, seed=seed + i,
                atari=True,  # full DeepMind wrapper stack (envs/atari.py)
            )
        )
        for i in range(num_actors)
    ]
    trainer = HostActorLearnerTrainer(args, agent, env_fns, run_name="impala_pong_ale")
    t0 = time.time()
    result = trainer.train(total_frames=max_frames)
    wall = time.time() - t0
    hit_frames = _first_crossing(trainer.tb_log_dir, "train/return_mean", threshold)
    trainer.close()
    row.update(
        final_return=round(result.get("return_mean", float("nan")), 2),
        frames=int(trainer.env_frames),
        frames_to_threshold=hit_frames,
        wall_s=round(wall, 1),
        fps=round(result.get("sps", float("nan")), 1),
        passed=hit_frames is not None,
    )
    return row
