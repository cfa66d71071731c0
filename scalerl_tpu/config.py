"""Argument schemas for every algorithm family, plus a dataclass-driven CLI.

Capability parity with the reference's config system
(``scalerl/algorithms/rl_args.py:8-362``: ``RLArguments`` / ``DQNArguments`` /
``A3CArguments`` dataclasses with ``metadata={'help': ...}`` parsed by tyro at
``examples/test_dqn.py:18``), with two deliberate fixes:

1. The reference's IMPALA/Ape-X read many fields that were never declared on
   any dataclass (``impala_atari.py:56,72,303,308,325-327,375,412,502`` read
   ``use_lstm``/``num_buffers``/``reward_clipping``/``discounting``/
   ``baseline_cost``/``entropy_cost``/``total_steps``/``output_dir``/
   ``disable_checkpoint`` off a bare ``RLArguments``).  Here every algorithm
   has a complete schema (``ImpalaArguments``, ``ApexArguments``) and a
   ``validate()`` hook, so config drift is a constructor error, not a crash
   three processes deep.
2. tyro is not a dependency: ``parse_args`` generates an argparse CLI directly
   from dataclass fields (type, default, and ``metadata={'help': ...}`` when a
   field declares it), so entry scripts keep the ``--field value`` surface of
   the reference examples.
"""

from __future__ import annotations

import argparse
import dataclasses
from dataclasses import dataclass, fields
from typing import Optional, Sequence, Type, TypeVar

T = TypeVar("T")


@dataclass
class RLArguments:
    """Common arguments shared by every algorithm family.

    Parity target: ``scalerl/algorithms/rl_args.py:8-159``.
    """

    # Project / run identity
    project: str = "scalerl_tpu"
    algo_name: str = "dqn"
    seed: int = 42

    # Device / mesh topology (TPU-native replacement for the reference's
    # ``device: cuda`` + accelerate YAML, rl_args.py:25 + accelerate_config.yaml)
    platform: str = "auto"  # auto | tpu | cpu
    num_devices: int = 0  # 0 = all visible devices
    mesh_shape: Optional[str] = None  # e.g. "dp=8" or "dp=4,mp=2"
    use_bfloat16: bool = True

    # Sharded big-model learner (parallel/logical.py, docs/PERFORMANCE.md
    # "Sharded learner"): mp_size > 1 shards the policy's heads/mlp/vocab/
    # expert dims over the named `mp` mesh axis so policies too big for one
    # chip's HBM train anyway; dp_size 0 = every remaining device
    # (n_devices // mp_size).  The trainer families resolve these through
    # maybe_enable_mesh_from_args; an explicit mesh_shape wins over both.
    mp_size: int = 1
    dp_size: int = 0
    # Policy architecture override for the actor-learner families:
    # "transformer" | "moe" pick the mp-shardable adapters
    # (models/transformer_policy.py); "auto" keeps the conv/MLP zoo.
    policy_arch: str = "auto"
    d_model: int = 128
    n_layers: int = 2
    n_heads: int = 4
    moe_experts: int = 8
    moe_hidden: int = 256
    # bf16 params + compute with fp32 optimizer state (the sharded-learner
    # mixed-precision layout: parallel.train_step.fp32_optimizer_state).
    # Only honored by the mp-shardable architectures.
    bf16_params: bool = False

    # Environment
    env_id: str = "CartPole-v1"
    num_envs: int = 8
    capture_video: bool = False
    env_backend: str = "gym"  # gym | jax (device-native envs)

    # Replay / rollout
    buffer_size: int = 10000
    batch_size: int = 32
    rollout_length: int = 20
    warmup_learn_steps: int = 500

    # Optimisation
    learning_rate: float = 1e-3
    gamma: float = 0.99
    max_grad_norm: float = 40.0

    # Training loop
    max_timesteps: int = 100_000
    train_frequency: int = 10
    eval_episodes: int = 5
    eval_frequency: int = 1000
    logger_frequency: int = 500

    # Actors
    num_actors: int = 4

    # Logging / checkpointing
    work_dir: str = "work_dirs"
    logger_backend: str = "tensorboard"  # tensorboard | wandb | none
    save_model: bool = True
    save_frequency: int = 10_000
    disable_checkpoint: bool = False
    # Path to a previous run directory (the one holding model_dir/tb_log) to
    # resume from: restores train state, replay cursors, and logger counters
    # (parity: tensorboard.py:65-82 / wandb.py:104-160 restore_data, which
    # the reference had but its trainers never surfaced as a flag).
    resume: str = ""

    # Supervision (runtime/supervisor.py)
    # Wall-clock resume-save cadence alongside the frame-gated
    # save_frequency: whichever fires first triggers save_resume, bounding
    # work lost to a preemption on slow-frame runs.  <= 0 disables the
    # wall-clock gate.
    checkpoint_interval_s: float = 600.0
    # How many displaced resume checkpoints to retain (resume.prev,
    # resume.prev2, ...); load falls back through the chain when the latest
    # is corrupt/partial.  0 keeps only the latest (no fallback).
    checkpoint_keep_last: int = 1
    # Stall watchdog deadline: if no trainer progress counter advances for
    # this many seconds, dump all-thread stacks + queue/ring occupancy and
    # fail fast (or invoke a recovery callback).  <= 0 disables.
    watchdog_timeout_s: float = 0.0
    # SIGTERM/SIGINT trigger save_resume at the next safe point and a clean
    # exit (TPU preemption safety); a second signal force-quits.
    handle_preemption: bool = True

    # Observability (runtime/telemetry.py, utils/profiling.py)
    # Device+host trace directory: when set, trainers/bench wrap their
    # measure loops in jax.profiler traces (utils.profiling.maybe_trace)
    # with a step_marker per fused chunk so device streams line up against
    # telemetry spans in the trace viewer.  Empty disables tracing.
    profile_dir: str = ""
    # Telemetry export directory: when set, a background loop writes
    # periodic JSONL snapshots (telemetry.jsonl) and a Prometheus-style
    # text exposition file (metrics.prom) from the process registry.
    # Empty defaults to <run_dir>/telemetry when telemetry_interval_s > 0.
    telemetry_dir: str = ""
    # Export cadence in seconds; <= 0 disables the export loop entirely.
    telemetry_interval_s: float = 30.0

    # Numerical fault tolerance (parallel/train_step.py, runtime/chaos.py)
    # All-finite update guard: a learn step whose result contains NaN/Inf is
    # skipped (lax.cond inside the jitted step — no extra dispatch) and
    # counted in the batched metrics as skipped_steps/nonfinite_grads.  The
    # token learner refuses a step whose loss or gradient norm is not finite,
    # before the update (no candidate state, no cond), under the same names.
    nonfinite_guard: bool = True
    # Guard amortization: run the (single fused-reduction) all-finite check
    # only on learn steps where state.step % K == 0.  K=1 (default)
    # preserves check-every-step semantics; K>1 makes the guard's cost
    # ~1/K per step — a divergence is still caught within K-1 steps, which
    # the tripwire's consecutive-skip window tolerates.  The env fast-off
    # SCALERL_NONFINITE_GUARD=0 compiles the guard out entirely instead.
    # The token learner (agents/token_ppo.py) does not read it: its guard is
    # a select inside the update, decided from the loss and the gradient
    # norm, and judges every step.
    nonfinite_check_every: int = 1
    # Divergence tripwire: after this many CONSECUTIVE skipped learn steps
    # the trainer restores agent state from the last good resume checkpoint
    # (falling back through the .prev chain).  <= 0 disables rollback; the
    # guard still skips individual bad steps.
    divergence_rollback_steps: int = 0

    # Elastic fleet (runtime/autoscaler.py + fleet dynamic admission/drain)
    # Autoscaler control loop over the DCN actor fleet: reads the telemetry
    # plane's tuning triad (actor fps vs learner steps/s vs queue occupancy)
    # plus the bounded-admission shed counters, and issues scale-up /
    # drain decisions through the cluster executor — with hysteresis and a
    # cooldown so heartbeat jitter never flaps the fleet.  Off by default;
    # the fleet entry scripts wire it when enabled.
    autoscale: bool = False
    # Hard floor: a preemption wave dropping the fleet below this is
    # backfilled immediately (no hysteresis, no cooldown).
    autoscale_min_workers: int = 1
    # Hard ceiling for scale-up decisions.
    autoscale_max_workers: int = 32
    # Evaluation cadence of the control loop, seconds.
    autoscale_interval_s: float = 5.0
    # Hold window after any scale action (spawn/drain take seconds to bite;
    # re-acting on pre-action signals is how fleets flap).
    autoscale_cooldown_s: float = 30.0
    # Consecutive same-direction pressure verdicts required before acting
    # (scale-down requires one more than scale-up).
    autoscale_hysteresis: int = 2
    # Generation-tier guard (disaggregated sequence RL): consumed data
    # staler than this many learner steps (the unified staleness gauge)
    # is scale-up pressure on the generation fleet.  0 disables the rule.
    autoscale_max_staleness: float = 0.0
    # Serving-tier capacity rules (the router's replica fleet,
    # serving/router.py): aggregate p95 past the up threshold adds a
    # replica; under the down threshold drains one.  Opposite semantics
    # from the actor-fleet p95 guard — configure per autoscaler instance.
    # 0 disables either side.
    autoscale_serving_up_p95_ms: float = 0.0
    autoscale_serving_down_p95_ms: float = 0.0

    # Pallas kernels (ops/pallas_vtrace.py, ops/pallas_per.py): route the
    # V-trace target computation and the PER priority/sum-tree update
    # through the fused TPU kernels (interpret-mode on CPU for parity
    # tests).  Off by default: the XLA reference ops are the baseline the
    # kernels are bit-tolerance-tested against.
    use_pallas: bool = False

    def validate(self) -> None:
        if self.batch_size <= 0:
            raise ValueError(f"batch_size must be positive, got {self.batch_size}")
        if self.num_envs <= 0:
            raise ValueError(f"num_envs must be positive, got {self.num_envs}")
        if self.buffer_size < self.batch_size:
            raise ValueError(
                f"buffer_size ({self.buffer_size}) must be >= batch_size "
                f"({self.batch_size})"
            )
        if self.nonfinite_check_every < 1:
            raise ValueError(
                "nonfinite_check_every must be >= 1, got "
                f"{self.nonfinite_check_every}"
            )
        if self.mp_size < 1:
            raise ValueError(f"mp_size must be >= 1, got {self.mp_size}")
        if self.dp_size < 0:
            raise ValueError(f"dp_size must be >= 0, got {self.dp_size}")
        if self.policy_arch not in ("auto", "transformer", "moe"):
            raise ValueError(
                "policy_arch must be auto | transformer | moe, got "
                f"{self.policy_arch!r}"
            )
        if self.autoscale_min_workers < 0:
            raise ValueError(
                "autoscale_min_workers must be >= 0, got "
                f"{self.autoscale_min_workers}"
            )
        if self.autoscale_max_workers < self.autoscale_min_workers:
            raise ValueError(
                f"autoscale_max_workers ({self.autoscale_max_workers}) must "
                f"be >= autoscale_min_workers ({self.autoscale_min_workers})"
            )
        if self.autoscale and self.autoscale_interval_s <= 0:
            raise ValueError(
                "autoscale_interval_s must be positive with autoscale on, "
                f"got {self.autoscale_interval_s}"
            )
        if self.autoscale_hysteresis < 1:
            raise ValueError(
                "autoscale_hysteresis must be >= 1, got "
                f"{self.autoscale_hysteresis}"
            )
        if (
            self.autoscale_serving_up_p95_ms > 0
            and self.autoscale_serving_down_p95_ms
            >= self.autoscale_serving_up_p95_ms
        ):
            raise ValueError(
                "autoscale_serving_down_p95_ms "
                f"({self.autoscale_serving_down_p95_ms}) must be < "
                "autoscale_serving_up_p95_ms "
                f"({self.autoscale_serving_up_p95_ms})"
            )


@dataclass
class DQNArguments(RLArguments):
    """DQN family options. Parity target: ``rl_args.py:163-315``."""

    algo_name: str = "dqn"
    # Architecture flags
    double_dqn: bool = True
    dueling_dqn: bool = False
    noisy_dqn: bool = False
    noisy_std: float = 0.5
    # Categorical (C51) distributional head (parity: rl_args.py:201-226 —
    # declared there, implemented here)
    categorical_dqn: bool = False
    num_atoms: int = 51
    v_min: float = 0.0
    v_max: float = 200.0
    hidden_sizes: str = "128,128"
    # Exploration schedule
    eps_greedy_start: float = 1.0
    eps_greedy_end: float = 0.05
    eps_greedy_scheduler: str = "linear"  # linear | piecewise
    exploration_fraction: float = 0.5
    # Learning-rate schedule
    lr_scheduler: str = "none"  # none | linear | multistep
    min_learning_rate: float = 1e-5
    # Target network
    target_update_frequency: int = 100
    soft_update_tau: float = 0.005
    use_soft_update: bool = True
    # Replay variants
    use_per: bool = False
    per_alpha: float = 0.6
    per_beta: float = 0.4
    per_beta_final: float = 1.0
    n_steps: int = 1

    def validate(self) -> None:
        super().validate()
        if self.n_steps < 1:
            raise ValueError(f"n_steps must be >= 1, got {self.n_steps}")
        if not (0.0 <= self.per_alpha <= 1.0):
            raise ValueError(f"per_alpha must be in [0, 1], got {self.per_alpha}")
        if self.categorical_dqn:
            if self.num_atoms < 2:
                raise ValueError(f"num_atoms must be >= 2, got {self.num_atoms}")
            if not self.v_max > self.v_min:
                raise ValueError(
                    f"v_max ({self.v_max}) must exceed v_min ({self.v_min})"
                )


@dataclass
class A3CArguments(RLArguments):
    """A3C/A2C options. Parity target: ``rl_args.py:319-362``.

    The Hogwild shared-gradient design (``parallel_a3c.py:221-233``) does not
    map to XLA; the TPU build runs synchronous batched advantage actor-critic
    over the same actor fleet, so the knobs here govern that runtime.
    """

    algo_name: str = "a3c"
    num_workers: int = 8
    # the unroll is the inherited ``rollout_length`` field (default 20)
    value_loss_coef: float = 0.5
    entropy_coef: float = 0.01
    gae_lambda: float = 1.0
    hidden_sizes: str = "128,128"  # MLP torso (flat obs)
    use_lstm: bool = True  # pixel obs: conv+LSTM (a3c/utils/atari_model.py:57-144)
    hidden_size: int = 256  # pixel obs: LSTM width (reference LSTMCell(256))
    max_episode_steps: int = 500
    max_grad_norm: float = 50.0  # reference clip(50), parallel_a3c.py:368
    # running mean/std obs normalization (atari_env.py:87-122) and
    # normalized-columns head init (atari_model.py:9-24)
    normalize_obs: bool = False
    normalized_init: bool = False


@dataclass
class SACArguments(RLArguments):
    """SAC options (beyond-parity: continuous control).

    The reference declares continuous-capable actor/critic MLPs in its
    network zoo (``network.py:27-67``) but ships no continuous-action
    algorithm; SAC (Haarnoja et al. 2018) completes that story: squashed-
    Gaussian actor, clipped double-Q critics, automatic entropy
    temperature, soft target updates — the whole update one jitted program
    over device-replay batches.
    """

    algo_name: str = "sac"
    env_id: str = "Pendulum-v1"  # continuous algo -> continuous default env
    hidden_sizes: str = "256,256"
    # Soft target update
    soft_update_tau: float = 0.005
    # Entropy temperature: alpha auto-tunes toward target entropy
    # (= -action_dim * target_entropy_scale)
    auto_alpha: bool = True
    init_alpha: float = 0.2
    target_entropy_scale: float = 1.0
    alpha_learning_rate: float = 3e-4
    actor_learning_rate: float = 3e-4  # critics use the base learning_rate
    # Replay (uniform or PER, sharing the DQN pipeline fields)
    use_per: bool = False
    per_alpha: float = 0.6
    per_beta: float = 0.4
    per_beta_final: float = 1.0
    n_steps: int = 1

    def validate(self) -> None:
        super().validate()
        if not 0.0 < self.soft_update_tau <= 1.0:
            raise ValueError(
                f"soft_update_tau must be in (0, 1], got {self.soft_update_tau}"
            )
        if self.init_alpha <= 0.0:
            raise ValueError(f"init_alpha must be positive, got {self.init_alpha}")
        if self.n_steps < 1:
            raise ValueError(f"n_steps must be >= 1, got {self.n_steps}")


@dataclass
class TD3Arguments(RLArguments):
    """TD3 options (beyond-parity continuous control, companion to SAC):
    deterministic tanh actor + exploration noise, clipped double-Q,
    target policy smoothing, delayed actor/target updates."""

    algo_name: str = "td3"
    env_id: str = "Pendulum-v1"
    hidden_sizes: str = "256,256"
    soft_update_tau: float = 0.005
    policy_delay: int = 2
    explore_noise_std: float = 0.1  # fraction of action scale
    target_noise_std: float = 0.2
    target_noise_clip: float = 0.5
    actor_learning_rate: float = 3e-4
    use_per: bool = False
    per_alpha: float = 0.6
    per_beta: float = 0.4
    per_beta_final: float = 1.0
    n_steps: int = 1

    def validate(self) -> None:
        super().validate()
        if self.policy_delay < 1:
            raise ValueError(
                f"policy_delay must be >= 1, got {self.policy_delay}"
            )
        if not 0.0 < self.soft_update_tau <= 1.0:
            raise ValueError(
                f"soft_update_tau must be in (0, 1], got {self.soft_update_tau}"
            )
        if self.n_steps < 1:
            raise ValueError(f"n_steps must be >= 1, got {self.n_steps}")


@dataclass
class R2D2Arguments(RLArguments):
    """R2D2 options (beyond-parity: recurrent replay distributed DQN,
    Kapturowski et al. 2019 — the Ape-X lineage the reference's README
    cites without a recurrent member).

    Sequences of ``rollout_length`` steps are stored with the actor's
    entering LSTM state; the learner burns in the first ``burn_in`` rows
    (no gradient) to de-stale the stored state, trains Q on the rest with
    n-step double-Q targets under the h-rescaling, and feeds back
    per-sequence priorities ``eta * max|td| + (1 - eta) * mean|td|``.
    """

    algo_name: str = "r2d2"
    # Model
    use_lstm: bool = True
    hidden_size: int = 256
    lstm_layers: int = 1
    dueling_dqn: bool = True
    # Sequence pipeline (actor side = the host actor plane's [T+1, B] slots)
    rollout_length: int = 20
    burn_in: int = 8
    num_actors: int = 2
    num_buffers: int = 16
    # Exploration: per-actor eps ladder (Ape-X convention)
    eps_base: float = 0.4
    eps_alpha: float = 7.0
    # Learning
    n_steps: int = 3
    batch_size: int = 16  # sequences per update
    replay_capacity: int = 2048  # sequences
    warmup_sequences: int = 64
    train_intensity: int = 1  # learn steps per inserted slot batch
    target_update_frequency: int = 400
    # PER over sequences
    per_alpha: float = 0.6
    per_beta: float = 0.4
    priority_eta: float = 0.9
    # Value rescaling h(x) = sign(x)(sqrt(|x|+1)-1) + eps*x
    value_rescale_eps: float = 1e-3

    def validate(self) -> None:
        super().validate()
        if not 0 <= self.burn_in < self.rollout_length:
            raise ValueError(
                f"burn_in ({self.burn_in}) must be in [0, rollout_length="
                f"{self.rollout_length})"
            )
        if self.n_steps < 1:
            raise ValueError(f"n_steps must be >= 1, got {self.n_steps}")
        if self.burn_in + self.n_steps >= self.rollout_length + 1:
            raise ValueError(
                "rollout_length must leave at least one trainable row: need "
                f"burn_in ({self.burn_in}) + n_steps ({self.n_steps}) <= "
                f"rollout_length ({self.rollout_length})"
            )
        if not 0.0 <= self.priority_eta <= 1.0:
            raise ValueError(
                f"priority_eta must be in [0, 1], got {self.priority_eta}"
            )


@dataclass
class PPOArguments(RLArguments):
    """PPO options (beyond-parity algorithm family).

    The reference ships A3C/DQN/Ape-X/IMPALA and lists DD-PPO in its
    architecture bibliography (``README.md:21-53``) without implementing it;
    this schema drives the PPO agent (``agents/ppo.py``) on the same
    on-policy runtime as A3C.  Data-parallel PPO over a mesh
    (``agent.enable_mesh``) is the DD-PPO topology: every chip runs the
    full epochs x minibatches schedule with gradients all-reduced per
    minibatch step.

    Learning-rate convention: losses use the repo-wide SUM over [T, b]
    (see ``agents/ppo.py:ppo_loss``), not the per-element mean of SB3/
    baselines PPO — so the effective gradient scale grows with
    ``rollout_length`` and lanes per minibatch, and published PPO lrs
    (3e-4 etc.) must be divided by the minibatch element count (or
    retuned) when transferring configs.
    """

    algo_name: str = "ppo"
    num_workers: int = 8
    # Clipped-surrogate objective
    clip_range: float = 0.2
    clip_range_vf: float = 0.0  # 0 disables value clipping
    ppo_epochs: int = 4
    num_minibatches: int = 4  # minibatches per epoch, split over env lanes
    gae_lambda: float = 0.95
    value_loss_coef: float = 0.5
    entropy_coef: float = 0.01
    normalize_advantage: bool = True
    # "sum" (repo convention, gradient scale grows with minibatch elements)
    # or "mean" (SB3/baselines convention: published lrs transfer as-is)
    loss_reduction: str = "sum"
    # Model (same zoo as A3C: MLP for flat obs, conv[+LSTM] for pixels)
    hidden_sizes: str = "128,128"
    use_lstm: bool = False
    hidden_size: int = 256
    max_episode_steps: int = 500
    max_grad_norm: float = 0.5
    normalize_obs: bool = False
    normalized_init: bool = False

    def validate(self) -> None:
        super().validate()
        if self.num_minibatches <= 0:
            raise ValueError(
                f"num_minibatches must be positive, got {self.num_minibatches}"
            )
        if self.num_workers % self.num_minibatches != 0:
            raise ValueError(
                "minibatches split over env lanes (full sequences, so LSTM "
                f"carries stay valid): num_workers ({self.num_workers}) must "
                f"divide by num_minibatches ({self.num_minibatches})"
            )
        if self.loss_reduction not in ("sum", "mean"):
            raise ValueError(
                f"loss_reduction must be 'sum' or 'mean', got {self.loss_reduction!r}"
            )
        if self.ppo_epochs <= 0:
            raise ValueError(f"ppo_epochs must be positive, got {self.ppo_epochs}")


@dataclass
class ImpalaArguments(RLArguments):
    """IMPALA options: the complete schema the reference never declared.

    Every field the reference's trainer reads off ``args``
    (``impala_atari.py:44-515``) exists here.
    """

    algo_name: str = "impala"
    # Model
    use_lstm: bool = True
    hidden_size: int = 512
    # Compute dtype for the conv/dense torso ("float32" | "bfloat16").
    # bfloat16 feeds the MXU at full rate; params and the V-trace math stay
    # float32 (standard mixed precision)
    compute_dtype: str = "float32"
    # Rollout pipeline
    rollout_length: int = 80
    num_actors: int = 8
    # host actor topology: "threads" = SEED-style central inference
    # (HostActorLearnerTrainer); "process" = monobeast-style actor processes
    # with local CPU inference over the shm ring (the reference's topology,
    # impala_atari.py:153-220); "serving" = the full centralized inference
    # plane (scalerl_tpu/serving/): actors act through RemotePolicyClient
    # against an InferenceServer holding the one hot policy, with dynamic
    # batching, generation-tagged params, and latency SLO telemetry
    actor_mode: str = "threads"
    # Inference-plane knobs (ServingConfig.from_args; only read when
    # actor_mode="serving" or by the standalone server entrypoints):
    # flush a serve batch at this many pending env lanes ...
    serve_max_batch: int = 64
    # ... or once the oldest pending request has waited this long
    serve_max_wait_ms: float = 5.0
    # bounded admission: shed act requests beyond this queue depth instead
    # of letting the queue (and therefore latency + policy lag) grow
    # without bound; 0 disables shedding
    serve_max_pending: int = 256
    num_buffers: int = 32  # free/full queue depth (impala_atari.py:72)
    num_learner_threads: int = 1
    batch_size: int = 8
    # Loss (the discount is the inherited ``gamma`` field — no duplicate knob)
    reward_clipping: str = "abs_one"  # abs_one | none
    baseline_cost: float = 0.5
    entropy_cost: float = 0.01
    # optional linear entropy anneal: cost goes entropy_cost ->
    # entropy_cost_end over entropy_anneal_frames env frames (None/0 =
    # constant, the reference's behavior).  High-early/low-late keeps
    # exploration alive through a long incubation (the Breakout rally
    # plateau) without paying a permanently noisy policy
    entropy_cost_end: Optional[float] = None
    entropy_anneal_frames: int = 0
    vtrace_rho_clip: float = 1.0
    vtrace_c_clip: float = 1.0
    # Optimiser (RMSProp parity, impala_atari.py:313-320)
    learning_rate: float = 6e-4
    rmsprop_alpha: float = 0.99
    rmsprop_eps: float = 0.01
    rmsprop_momentum: float = 0.0
    max_grad_norm: float = 40.0
    # Run (the frame budget is the inherited ``max_timesteps`` field; the
    # wall-clock save cadence is the inherited ``checkpoint_interval_s``,
    # default 600 s — the reference's 10-minute IMPALA checkpoints)
    max_timesteps: int = 30_000_000

    # Reference-vocabulary aliases (read-only; the CLI flags are --gamma and
    # --max-timesteps — one knob per quantity, no config drift)
    @property
    def discounting(self) -> float:
        return self.gamma

    @property
    def total_steps(self) -> int:
        return self.max_timesteps

    def validate(self) -> None:
        super().validate()
        # num_buffers counts SLOTS (each slot holds one actor's vector-env
        # lanes) while batch_size counts LANES; the reference's constructor
        # check (impala_atari.py:74-77, num_buffers >= 2*batch_size) compares
        # like units because monobeast's batch_size counts rollouts/slots.
        # Porting that formula verbatim here silently forced queues ~16x
        # deeper than needed (32 slots for a 2-slot learn batch), and queue
        # depth IS worst-case policy lag — the host plane's Breakout arm
        # stalled on exactly this.  The slot-aware floor
        # (num_buffers >= max(2 * batch_size/envs_per_actor, num_actors))
        # needs the runtime env fleet shape, so the trainers enforce it;
        # here only the shape-independent minimum holds.
        if self.num_buffers < max(2, self.num_actors):
            raise ValueError(
                "num_buffers (slot count) must be at least "
                "max(2, num_actors) "
                f"(got {self.num_buffers}, num_actors={self.num_actors})"
            )
        if self.actor_mode not in ("threads", "process", "serving"):
            raise ValueError(
                "actor_mode must be threads | process | serving, got "
                f"{self.actor_mode!r}"
            )
        if self.serve_max_batch < 1:
            raise ValueError(
                f"serve_max_batch must be >= 1, got {self.serve_max_batch}"
            )
        if self.serve_max_wait_ms < 0:
            raise ValueError(
                f"serve_max_wait_ms must be >= 0, got {self.serve_max_wait_ms}"
            )
        if self.serve_max_pending < 0:
            raise ValueError(
                f"serve_max_pending must be >= 0, got {self.serve_max_pending}"
            )


@dataclass
class ImpactArguments(ImpalaArguments):
    """IMPACT options (arxiv 1912.00167): clipped target networks + a
    circular surrogate buffer on the IMPALA actor plane.

    The sample-efficiency counterweight to the sharded big-model learner:
    as the learner step gets heavier (mp-sharded transformer/MoE), the
    async actors fall behind — IMPACT keeps the chips busy by replaying
    each trajectory chunk ``replay_times`` times from a circular buffer,
    while a slow-moving *target network* anchors the surrogate objective
    (PPO-style ratio clip against the target policy, V-trace corrections
    computed target-vs-behavior) so the extra replays don't destabilize
    training the way raw IMPALA replays would.
    """

    algo_name: str = "impact"
    # learner steps between target-network refreshes (pi_target <- pi)
    target_update_frequency: int = 16
    # how many learner updates each inserted chunk participates in
    replay_times: int = 2
    # circular surrogate buffer depth, in trajectory chunks
    surrogate_capacity: int = 16
    # PPO-style clip width for the pi/pi_target surrogate ratio
    impact_clip: float = 0.3

    def validate(self) -> None:
        super().validate()
        if self.target_update_frequency < 1:
            raise ValueError(
                "target_update_frequency must be >= 1, got "
                f"{self.target_update_frequency}"
            )
        if self.replay_times < 1:
            raise ValueError(
                f"replay_times must be >= 1, got {self.replay_times}"
            )
        if self.surrogate_capacity < 1:
            raise ValueError(
                f"surrogate_capacity must be >= 1, got {self.surrogate_capacity}"
            )
        if not 0.0 < self.impact_clip < 1.0:
            raise ValueError(
                f"impact_clip must be in (0, 1), got {self.impact_clip}"
            )


@dataclass
class ApexArguments(DQNArguments):
    """Ape-X distributed prioritized replay options.

    The reference's Ape-X skeleton (``apex/apex_train.py``) reads ad-hoc
    attributes; this is the declared schema.
    """

    algo_name: str = "apex"
    use_per: bool = True
    num_actors: int = 4
    actor_update_frequency: int = 100  # publish a weight snapshot every N learn steps
    priority_update_frequency: int = 1
    eps_greedy_base: float = 0.4
    eps_greedy_alpha: float = 7.0  # per-actor eps = base ** (1 + i/(N-1) * alpha)

    def validate(self) -> None:
        super().validate()
        if self.rollout_length < self.n_steps:
            raise ValueError(
                f"rollout_length ({self.rollout_length}) must be >= n_steps "
                f"({self.n_steps}): actors fold n-step windows inside each chunk"
            )


@dataclass
class GenRLArguments(RLArguments):
    """Token-level sequence-RL options (the ``genrl/`` plane).

    One generation *round* = generate ``genrl_batch`` sequences with the
    KV-cached engine, score them with the task's rule-based reward, pack
    them into the prioritized sequence replay, sample
    ``genrl_sample_batch`` sequences, and take one token-PPO learn step.
    Model size rides the shared ``d_model``/``n_layers``/``n_heads``
    fields; the dp×mp sharded learner rides ``dp_size``/``mp_size``.
    """

    algo_name: str = "token_ppo"
    learning_rate: float = 3e-3
    max_grad_norm: float = 1.0

    # Vocabulary / sequence geometry.  Prompt and response lengths pad up
    # power-of-two bucket ladders inside the engine; the transformer's
    # max_len is derived as (prompt bucket + response bucket).
    vocab_size: int = 16
    prompt_len: int = 4  # the task's maximum true prompt length
    max_new_tokens: int = 4
    eos_token: int = -1  # < 0: fixed-length responses (no early stop)

    # Block family of the token model (models/transformer.py BlockSpec):
    # "gpt2" = LayerNorm, learned positions, GELU MLP (the default);
    # "olmoe" = RMSNorm, rotary positions, RMSNorm on q and k, and in
    # place of the MLP a router over ``moe_experts`` SwiGLU experts of
    # width ``moe_hidden`` with the ``moe_experts_per_token`` most
    # probable kept, dropless.  The sizes below are read by the families
    # that have them; ``head_dim`` 0 means d_model // n_heads.
    # "longcat" = the shortcut-connected double layer: two latent (MLA)
    # attentions of the five ``mla_*`` sizes and two dense SwiGLU FFNs of
    # width ``ffn_hidden`` in a row, with one router over ``moe_experts``
    # computed experts and ``moe_zero_experts`` identity ones beside them;
    # its picks weigh ``moe_routed_scaling`` x their probability.  Of the
    # computed experts this program holds ``moe_experts_held`` (0: all)
    # from ``moe_first_expert`` on, one rank's share of an expert-parallel
    # deployment: picks of the others add nothing here.
    # "joyai" = a stack of more than one kind of layer: ``dense_layers``
    # leading plain layers of one latent (MLA) attention and a dense SwiGLU
    # FFN of width ``ffn_hidden``, then plain layers of the same attention
    # and a router over ``moe_experts`` experts (``moe_scoring``: each
    # output's sigmoid or a softmax; the picks' scores renormalised under
    # ``moe_norm_topk_prob`` and scaled by ``moe_routed_scaling``) of which
    # ``moe_experts_held`` are held, beside ``moe_shared_experts``
    # always-on ones; and ``mtp_layers`` (0 | 1) multi-token-prediction
    # modules, which the packed learner runs and trains with weight
    # ``mtp_loss_coef`` and generation never builds.
    # "nemotron_h" = a stack of single-mixer layers (``x + Mixer(N(x))``)
    # laid out by ``layer_pattern``, a character a layer: ``M`` a Mamba-2
    # mixer of the ``ssm_*`` sizes, ``E`` the routed experts beside a
    # shared expert of ``moe_shared_width``, ``*`` attention with
    # ``kv_heads`` key/value heads under ``n_heads`` query heads and no
    # position signal, ``-`` a dense FFN of ``ffn_hidden``;
    # ``moe_expert_act`` relu2 makes every expert ``relu(h W_up)^2 W_down``
    # (no gate).  ``n_layers`` is the pattern's length.  The generation
    # engine keeps a Mamba layer's recurrent state by lane beside the KV
    # pages, so such a model is admitted by local prefill and group fork
    # alone: no prefix-cache hit is served and speculation is refused.
    # "qwen3_next" = plain layers (mixer, then routed FFN) whose mixer is
    # full attention at every ``full_attention_interval``-th layer and a
    # Gated DeltaNet at the others: a linear-attention layer whose state,
    # a ``ssm_state x ssm_head_dim`` matrix a value head, is updated by a
    # gated delta rule and kept by lane as a Mamba layer's is (the
    # ``ssm_*`` sizes: ``ssm_heads`` value heads, ``ssm_groups`` key
    # heads).  Its attention has ``kv_heads`` key/value heads, a sigmoid
    # output gate carried by the query projection, an RMSNorm over each
    # head of q and k and a rotary over a head's first ``rotary_dim``
    # features; its RMSNorm scales are stored zero-centred; its router is
    # a softmax over ``moe_experts`` with ``moe_experts_held`` held, beside
    # a shared expert of ``moe_shared_width`` behind a sigmoid scalar gate.
    # "zaya" = plain layers of compressed convolutional attention (CCA)
    # and routed experts: ``n_heads`` query heads over ``kv_heads`` of
    # ``head_dim`` whose queries and keys are convolved over the tokens
    # before them (a depthwise convolution of ``cca_time0`` taps, then one
    # of ``cca_time1`` that mixes a head's channels), L2-normalised and
    # rotated over a head's first ``rotary_dim`` features, and half of
    # whose values are the previous token's; a router that is an MLP of
    # width ``router_hidden`` on a state handed up from the layer before,
    # which picks ``moe_experts_per_token`` of ``moe_experts`` experts of
    # ``moe_hidden``; a learned scale and bias on both sides of every
    # residual add.  The engine keeps the convolutions' window by lane
    # beside the layer's own KV pages, so such a model too is admitted by
    # local prefill and group fork alone.
    # "xing4" = joyai's stack (``dense_layers`` leading dense layers, then
    # MLA and a sigmoid router beside a shared expert) on a residual
    # stream of ``hc_mult`` rows a token (manifold-constrained
    # hyper-connections): every sublayer reads a learned mix of the rows
    # and writes back by an ``hc_mult x hc_mult`` matrix made doubly
    # stochastic by ``hc_sinkhorn_iters`` Sinkhorn iterations (``hc_eps``
    # in the denominators, the logit clipped to ``hc_clamp_min`` ..
    # ``hc_clamp_max`` before the exponential); its rotary is YaRN's where
    # ``rope_factor`` > 1 (``rope_original_max`` positions, ``rope_beta_*``,
    # ``rope_mscale*``).  The stream is an activation: the cache is
    # joyai's, prefix cache and fork stay on.  It takes no ``mtp_layers``.
    block_family: str = "gpt2"
    head_dim: int = 0
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    moe_experts_per_token: int = 2
    moe_norm_topk_prob: bool = False
    mla_q_lora_rank: int = 0
    mla_kv_lora_rank: int = 0
    mla_qk_nope_head_dim: int = 0
    mla_qk_rope_head_dim: int = 0
    mla_v_head_dim: int = 0
    ffn_hidden: int = 0
    moe_zero_experts: int = 0
    moe_routed_scaling: float = 1.0
    moe_experts_held: int = 0
    moe_first_expert: int = 0
    dense_layers: int = 0
    moe_shared_experts: int = 0
    moe_scoring: str = "softmax"
    layer_pattern: str = ""
    kv_heads: int = 0
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 128
    moe_expert_act: str = "swiglu"
    moe_shared_width: int = 0
    full_attention_interval: int = 0
    rotary_dim: int = 0
    cca_time0: int = 0
    cca_time1: int = 0
    router_hidden: int = 0
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 0
    hc_eps: float = 1e-6
    hc_clamp_min: float = -30.0
    hc_clamp_max: float = 30.0
    rope_factor: float = 1.0  # 1: plain rotary
    rope_original_max: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    mtp_layers: int = 0
    mtp_loss_coef: float = 0.1
    # weight of the router's load-balancing loss in the learner's total
    # (agents/token_ppo.py); only a routed family has the term
    router_aux_loss_coef: float = 0.01

    # Sampling (the behavior distribution — stored logprobs are under
    # EXACTLY this distribution, temperature and top-k included).
    temperature: float = 1.0
    top_k: int = 0

    # Token-PPO objective.
    clip_range: float = 0.2
    value_cost: float = 0.5
    entropy_cost: float = 0.01
    # KL-to-reference penalty (the frozen initial params); 0 disables the
    # anchor forward entirely (compiled out, not skipped at runtime).
    kl_cost: float = 0.0
    adv_norm: bool = True

    # Round geometry / replay.
    genrl_rounds: int = 200
    genrl_batch: int = 32  # sequences generated per round
    genrl_sample_batch: int = 32  # sequences per learn step
    genrl_buffer_sequences: int = 64  # sequence-replay capacity
    # Publish a param generation to the engine every N learn steps (1 =
    # per-step, the near-on-policy default; higher values trade staleness
    # for fewer device-side snapshot copies).
    genrl_push_every: int = 1
    # Decode-loop fusion: scan | unroll | auto (backend-resolved, the PR 6
    # iter_mode verdict — unroll on XLA:CPU, scan on TPU/GPU).
    genrl_iter_mode: str = "auto"

    # The one generation engine: the persistent continuous-batching
    # engine (paged KV, macro-steps, admission into freed lanes).  The
    # field accepts only "continuous"; it is kept because the benchmark's
    # rollout drivers and chip_smoke.py still pass --genrl-engine
    # (ROADMAP.md D13 drops it).
    genrl_engine: str = "continuous"
    genrl_lanes: int = 0  # continuous decode lanes; 0 -> genrl_batch
    genrl_page_size: int = 8  # KV pool page size (tokens per page)
    genrl_num_pages: int = 0  # KV pool pages; 0 -> all-lane worst case
    genrl_macro_steps: int = 4  # decode substeps fused per macro-step
    # Admission flush deadline (ms): the oldest queued prompt waits at most
    # this long before a flush fires even with lanes to spare (the serving
    # batcher's max_wait_s on the admission queue); 0 = admit immediately.
    genrl_admit_wait_ms: float = 0.0
    genrl_max_pending: int = 0  # admission queue bound (0 = unbounded)
    genrl_paged_attn: str = "auto"  # pallas | xla | auto (backend)
    # Group sampling (ISSUE 14): generate this many completions per
    # prompt — the GRPO data layout.  Rounds sample genrl_batch /
    # samples_per_prompt distinct prompts; each group admits via
    # submit_group (shared-prefix CoW fork, ~1/n of the prefill).
    samples_per_prompt: int = 1
    # Macro-step pipelining: K macro dispatches in flight, host read
    # lagging by K-1 so harvest/admission/prefill overlap device decode
    # (1 = the old synchronous semantics, parity-pinned).
    genrl_steps_in_flight: int = 2
    # Shared-prefix KV reuse: cache full prompt pages and share them
    # copy-on-write into later admissions of the same prefix (flushed on
    # every param push; off = always prefill from scratch).
    genrl_prefix_cache: bool = True
    # Speculative decoding (ISSUE 16, continuous engine only): each pass,
    # lanes self-draft up to spec_k tokens from their own n-gram table
    # (no draft model — nothing extra on the snapshot plane) and ONE
    # batched verify pass accepts/rejects them under the exact
    # speculative-sampling rule, so the output distribution is unchanged.
    # Off by default: the win depends on the task's draft acceptance rate
    # (see docs/SEQUENCE_RL.md "Speculative decoding").
    spec_enable: bool = False
    spec_k: int = 4  # draft tokens per pass when spec_enable (>= 1)
    spec_ngram: int = 3  # n-gram width the self-drafter matches

    # Pad-free packed learner (ISSUE 15): bin-pack completed sequences
    # (compact prompt+response, no intra-sequence pad) into fixed
    # [rows, learner_pack_len] rows with per-token segment ids and
    # per-segment position reset; the learn step runs segment-blocked
    # causal attention so tokens never see their row-mates.  Off (the
    # default) keeps the padded bucket-pair layout — the packed path's
    # parity twin (loss/grads agree to 1e-5 on the same sequences).
    learner_packing: bool = False
    # Packed row length; 0 derives the engine bucket pair (prompt bucket
    # + response bucket), so one row fits the longest possible sequence.
    learner_pack_len: int = 0
    # Segment attention impl for the packed forward: pallas = the flash
    # training kernel (fwd + custom_vjp bwd, cross-segment/pad blocks
    # skipped), xla = dense packed mask, auto = pallas on TPU else xla.
    learner_packed_attn: str = "auto"

    # Disaggregated dataflow (genrl/disagg.py, ISSUE 12): N generation
    # hosts behind jax-free shells stream completed sequences over the
    # fleet wire into this learner's sequence replay, with quantized
    # generation-tagged param snapshots flowing back.
    disagg_hosts: int = 2
    # Engine-shell admission capacity per host; 0 derives
    # max(1, genrl_batch // disagg_hosts) so one round's worth of lanes
    # spreads across the fleet.
    disagg_lanes_per_host: int = 0
    disagg_quantize: str = "int8"  # snapshot wire format: int8 | none
    disagg_upload_batch: int = 4  # completed sequences per uplink frame
    # How long one train round may wait for the generation fleet to
    # deliver its sequence batch before raising (a dead fleet must surface
    # as an error, not a silent hang).
    disagg_round_timeout_s: float = 120.0
    # Durable learner ledger directory (ISSUE 19): non-empty enables the
    # preemption-tolerant plane — SIGTERM at the between-rounds safe-point
    # saves lease table + dedup keys + replay + snapshot generation into
    # <dir>/learner_ledger, and the next run against the same dir resumes
    # at the same learn step under a bumped learner epoch.
    disagg_ledger_dir: str = ""

    def validate(self) -> None:
        super().validate()
        if self.vocab_size < 4:
            raise ValueError(f"vocab_size must be >= 4, got {self.vocab_size}")
        if self.prompt_len < 1 or self.max_new_tokens < 1:
            raise ValueError(
                "prompt_len and max_new_tokens must be >= 1, got "
                f"{self.prompt_len}/{self.max_new_tokens}"
            )
        if self.temperature < 0:
            raise ValueError(
                f"temperature must be >= 0 (0 = greedy), got "
                f"{self.temperature}"
            )
        if self.block_family not in (
            "gpt2", "olmoe", "longcat", "joyai", "nemotron_h", "qwen3_next", "zaya",
            "xing4",
        ):
            raise ValueError(
                "block_family must be one of the eight families gpt2 | olmoe | "
                "longcat | joyai | nemotron_h | qwen3_next | zaya | xing4, got "
                f"{self.block_family!r}"
            )
        hybrid = self.block_family == "nemotron_h"
        delta = self.block_family == "qwen3_next"
        cca = self.block_family == "zaya"
        rows = self.block_family == "xing4"
        if hybrid and (
            not self.layer_pattern
            or set(self.layer_pattern) - set("ME*-")
            or len(self.layer_pattern) != self.n_layers
        ):
            raise ValueError(
                "the nemotron_h family needs layer_pattern, a string of "
                "M | E | * | - with one character for each of n_layers "
                f"({self.n_layers}), got {self.layer_pattern!r}"
            )
        if not hybrid and (
            self.layer_pattern or self.moe_expert_act != "swiglu"
            or (not delta and (self.ssm_heads or self.moe_shared_width))
            or (not (delta or cca) and self.kv_heads)
        ):
            raise ValueError(
                "layer_pattern and moe_expert_act are the nemotron_h family's, "
                "the ssm sizes and moe_shared_width that family's and "
                "qwen3_next's, kv_heads theirs and zaya's, got them with "
                f"{self.block_family!r}"
            )
        if delta and not 1 <= self.full_attention_interval <= self.n_layers:
            raise ValueError(
                "the qwen3_next family needs full_attention_interval in "
                f"1..n_layers ({self.n_layers}), got {self.full_attention_interval}"
            )
        if (not delta and self.full_attention_interval) or (
            not (delta or cca) and self.rotary_dim
        ):
            raise ValueError(
                "full_attention_interval is the qwen3_next family's and "
                "rotary_dim that family's and zaya's, got them with "
                f"{self.block_family!r}"
            )
        if not cca and (self.cca_time0 or self.cca_time1 or self.router_hidden):
            raise ValueError(
                "cca_time0, cca_time1 and router_hidden are the zaya family's, "
                f"got them with {self.block_family!r}"
            )
        if self.moe_expert_act not in ("swiglu", "relu2"):
            raise ValueError(
                f"moe_expert_act must be swiglu | relu2, got {self.moe_expert_act!r}"
            )
        if self.kv_heads < 0 or (self.kv_heads and self.n_heads % self.kv_heads):
            raise ValueError(
                "kv_heads must divide n_heads (0: one each), got "
                f"{self.kv_heads}/{self.n_heads}"
            )
        lane_state = (
            (hybrid and "M" in self.layer_pattern)
            or (delta and self.full_attention_interval > 1)
            or cca
        )
        if lane_state and self.spec_enable:
            raise ValueError(
                "spec_enable cannot serve a model with a layer that carries "
                "lane state (a Mamba-2 or a Gated DeltaNet mixer's state, a "
                "convolutional attention's window): a rejected draft is undone "
                "by moving a page cursor back, and what a lane carries has no "
                "cursor to rewind"
            )
        if self.moe_scoring not in ("softmax", "sigmoid"):
            raise ValueError(
                f"moe_scoring must be softmax | sigmoid, got {self.moe_scoring!r}"
            )
        if self.mtp_layers not in (0, 1) or self.mtp_loss_coef < 0:
            raise ValueError(
                "mtp_layers must be 0 | 1 and mtp_loss_coef >= 0, got "
                f"{self.mtp_layers}/{self.mtp_loss_coef}"
            )
        if self.mtp_layers and not self.learner_packing:
            raise ValueError(
                "mtp_layers needs learner_packing: the multi-token-prediction "
                "term lives in the packed loss"
            )
        if self.block_family != "joyai" and (
            (self.dense_layers and not rows) or self.mtp_layers
            or (self.moe_shared_experts and not (hybrid or delta or rows))
        ):
            raise ValueError(
                "mtp_layers is the joyai family's, dense_layers joyai's and "
                "xing4's (a stream of rows takes no multi-token-prediction "
                "module) and moe_shared_experts theirs, nemotron_h's and "
                f"qwen3_next's, got them with {self.block_family!r}"
            )
        if not 0 <= self.dense_layers <= self.n_layers:
            raise ValueError(
                "dense_layers must lie in 0..n_layers "
                f"({self.n_layers}), got {self.dense_layers}"
            )
        scaled = self.rope_factor != 1.0
        if rows and (
            self.hc_mult < 2 or self.hc_sinkhorn_iters < 1 or self.hc_eps <= 0
            or not self.hc_clamp_min < self.hc_clamp_max
        ):
            raise ValueError(
                "the xing4 family needs hc_mult >= 2 rows, hc_sinkhorn_iters "
                ">= 1, hc_eps > 0 and hc_clamp_min < hc_clamp_max, got "
                f"{self.hc_mult}/{self.hc_sinkhorn_iters}/{self.hc_eps}/"
                f"{self.hc_clamp_min}/{self.hc_clamp_max}"
            )
        if not rows and (self.hc_mult != 1 or self.hc_sinkhorn_iters or scaled):
            raise ValueError(
                "hc_mult, hc_sinkhorn_iters and rope_factor (YaRN) are the "
                f"xing4 family's, got them with {self.block_family!r}"
            )
        if scaled and not (
            self.rope_factor > 1.0 and self.rope_original_max >= 1
            and self.rope_beta_fast > self.rope_beta_slow > 0
        ):
            raise ValueError(
                "YaRN needs rope_factor > 1, rope_original_max >= 1 and "
                "rope_beta_fast > rope_beta_slow > 0, got "
                f"{self.rope_factor}/{self.rope_original_max}/"
                f"{self.rope_beta_fast}/{self.rope_beta_slow}"
            )
        if self.head_dim < 0 or self.router_aux_loss_coef < 0:
            raise ValueError(
                "head_dim and router_aux_loss_coef must be >= 0, got "
                f"{self.head_dim}/{self.router_aux_loss_coef}"
            )
        if not 0.0 < self.clip_range < 1.0:
            raise ValueError(
                f"clip_range must be in (0, 1), got {self.clip_range}"
            )
        if self.kl_cost < 0 or self.value_cost < 0:
            raise ValueError(
                "kl_cost and value_cost must be >= 0, got "
                f"{self.kl_cost}/{self.value_cost}"
            )
        if self.genrl_batch < 1 or self.genrl_sample_batch < 1:
            raise ValueError(
                "genrl_batch and genrl_sample_batch must be >= 1, got "
                f"{self.genrl_batch}/{self.genrl_sample_batch}"
            )
        if self.genrl_buffer_sequences < self.genrl_batch:
            raise ValueError(
                f"genrl_buffer_sequences ({self.genrl_buffer_sequences}) "
                f"must be >= genrl_batch ({self.genrl_batch})"
            )
        if self.genrl_push_every < 1:
            raise ValueError(
                f"genrl_push_every must be >= 1, got {self.genrl_push_every}"
            )
        if self.genrl_iter_mode not in ("auto", "scan", "unroll"):
            raise ValueError(
                "genrl_iter_mode must be auto | scan | unroll, got "
                f"{self.genrl_iter_mode!r}"
            )
        if self.genrl_engine != "continuous":
            raise ValueError(
                "genrl_engine must be 'continuous': the cohort engine is "
                f"gone (PR 28), got {self.genrl_engine!r}"
            )
        if self.genrl_lanes < 0 or self.genrl_page_size < 1:
            raise ValueError(
                "genrl_lanes must be >= 0 and genrl_page_size >= 1, got "
                f"{self.genrl_lanes}/{self.genrl_page_size}"
            )
        if self.genrl_macro_steps < 1:
            raise ValueError(
                f"genrl_macro_steps must be >= 1, got {self.genrl_macro_steps}"
            )
        if self.genrl_paged_attn not in ("auto", "pallas", "xla"):
            raise ValueError(
                "genrl_paged_attn must be auto | pallas | xla, got "
                f"{self.genrl_paged_attn!r}"
            )
        if self.samples_per_prompt < 1:
            raise ValueError(
                f"samples_per_prompt must be >= 1, got "
                f"{self.samples_per_prompt}"
            )
        if self.genrl_batch % self.samples_per_prompt != 0:
            raise ValueError(
                f"genrl_batch ({self.genrl_batch}) must be a multiple of "
                f"samples_per_prompt ({self.samples_per_prompt}) so rounds "
                "hold whole groups"
            )
        if self.genrl_steps_in_flight < 1:
            raise ValueError(
                f"genrl_steps_in_flight must be >= 1, got "
                f"{self.genrl_steps_in_flight}"
            )
        if self.spec_enable and self.spec_k < 1:
            raise ValueError(
                f"spec_k must be >= 1 when spec_enable, got {self.spec_k}"
            )
        if self.spec_ngram < 1:
            raise ValueError(
                f"spec_ngram must be >= 1, got {self.spec_ngram}"
            )
        if self.learner_packed_attn not in ("auto", "pallas", "xla"):
            raise ValueError(
                "learner_packed_attn must be auto | pallas | xla, got "
                f"{self.learner_packed_attn!r}"
            )
        if self.learner_pack_len < 0:
            raise ValueError(
                f"learner_pack_len must be >= 0, got "
                f"{self.learner_pack_len}"
            )
        if self.learner_pack_len and (
            self.learner_pack_len < self.prompt_len + self.max_new_tokens
        ):
            raise ValueError(
                f"learner_pack_len ({self.learner_pack_len}) must fit one "
                "maximum-length sequence (prompt_len + max_new_tokens = "
                f"{self.prompt_len + self.max_new_tokens}) or every "
                "full-length completion would be shed"
            )
        if self.disagg_hosts < 1:
            raise ValueError(
                f"disagg_hosts must be >= 1, got {self.disagg_hosts}"
            )
        if self.disagg_lanes_per_host < 0 or self.disagg_upload_batch < 1:
            raise ValueError(
                "disagg_lanes_per_host must be >= 0 and "
                "disagg_upload_batch >= 1, got "
                f"{self.disagg_lanes_per_host}/{self.disagg_upload_batch}"
            )
        if self.disagg_quantize not in ("int8", "none"):
            raise ValueError(
                "disagg_quantize must be int8 | none, got "
                f"{self.disagg_quantize!r}"
            )
        if self.disagg_round_timeout_s <= 0:
            raise ValueError(
                "disagg_round_timeout_s must be positive, got "
                f"{self.disagg_round_timeout_s}"
            )


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------

_BOOL_TRUE = {"1", "true", "yes", "on"}
_BOOL_FALSE = {"0", "false", "no", "off"}


def _str2bool(v: str) -> bool:
    lv = v.lower()
    if lv in _BOOL_TRUE:
        return True
    if lv in _BOOL_FALSE:
        return False
    raise argparse.ArgumentTypeError(f"expected a boolean, got {v!r}")


def build_parser(cls: Type[T], parser: Optional[argparse.ArgumentParser] = None) -> argparse.ArgumentParser:
    """Generate an argparse parser from a dataclass schema (tyro-free)."""
    parser = parser or argparse.ArgumentParser(description=cls.__doc__)
    for f in fields(cls):  # type: ignore[arg-type]
        if not f.init:
            continue
        name = "--" + f.name.replace("_", "-")
        default = (
            f.default
            if f.default is not dataclasses.MISSING
            else f.default_factory()  # type: ignore[misc]
        )
        help_text = f.metadata.get("help", "") if f.metadata else ""
        ftype = f.type if isinstance(f.type, type) else None
        # Resolve string annotations like "int" / "Optional[str]"
        if ftype is None:
            tname = str(f.type)
            ftype = {
                "int": int,
                "float": float,
                "str": str,
                "bool": bool,
            }.get(tname, str if "str" in tname else type(default) if default is not None else str)
        if ftype is bool:
            # accept both bare `--flag` (== true) and `--flag false`
            parser.add_argument(
                name,
                type=_str2bool,
                nargs="?",
                const=True,
                default=default,
                help=help_text,
            )
        else:
            parser.add_argument(name, type=ftype, default=default, help=help_text)
    return parser


def parse_args(
    cls: Type[T] = RLArguments,  # type: ignore[assignment]
    argv: Optional[Sequence[str]] = None,
) -> T:
    """Parse CLI args into an instance of ``cls`` and validate it."""
    parser = build_parser(cls)
    ns = parser.parse_args(argv)
    kwargs = {f.name: getattr(ns, f.name) for f in fields(cls) if f.init}  # type: ignore[arg-type]
    args = cls(**kwargs)  # type: ignore[call-arg]
    if hasattr(args, "validate"):
        args.validate()
    return args
