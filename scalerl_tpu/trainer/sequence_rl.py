"""Sequence-RL trainer: the generate -> score -> learn round loop.

The orchestration glue of the ``genrl/`` plane (MindSpeed RL's dataflow at
single-host scale, Podracer's fused-program discipline inside each stage):

1. **generate** — the continuous-batching engine (``genrl/continuous.py``)
   keeps its lane pool fed and advances it one jitted macro-step at a
   time, ONE upload and ONE batched read a macro-step, under the
   steady-state transfer guard once warm;
2. **score** — the task's rule-based reward runs on host numpy (the
   verifier stays off-device by design);
3. **pack + replay** — sequences become prioritized sequence-replay
   chunks (``genrl/rollout.py`` -> ``data/sequence_replay.py``), inserted
   and sampled on device with the ``seq_*`` jitted entry points;
4. **learn** — one token-PPO step (``agents/token_ppo.py``), metrics read
   back with ONE batched transfer; the learner then publishes a fresh
   generation to the engine (device-side copy, no host sync) and reports
   generation staleness off the metrics that already crossed the host
   boundary — no extra transfers anywhere in the round.

dp×mp sharding rides ``maybe_enable_mesh_from_args`` exactly like the
other trainer families.
"""

from __future__ import annotations

import json
import os
import threading
import time
from contextlib import nullcontext
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from scalerl_tpu.agents.token_ppo import TokenPPOAgent
from scalerl_tpu.config import GenRLArguments
from scalerl_tpu.data.sequence_replay import (
    seq_add,
    seq_export,
    seq_import,
    seq_init,
    seq_sample,
)
from scalerl_tpu.genrl.continuous import ContinuousConfig, ContinuousEngine
from scalerl_tpu.genrl.rollout import (
    pack_completions,
    packed_field_shapes,
    packed_rows_from_completions,
    sequence_field_shapes,
)
from scalerl_tpu.genrl.task import TokenRecallTask
from scalerl_tpu.models.transformer import (
    RopeScaling,
    TransformerPolicy,
    block_spec,
    interval_specs,
    layer_specs,
    pattern_specs,
)
from scalerl_tpu.ops.pallas_per import resolve_sample_method
from scalerl_tpu.parallel.train_step import maybe_enable_mesh_from_args
from scalerl_tpu.runtime import telemetry, tracing
from scalerl_tpu.utils.buckets import bucket_for, default_buckets
from scalerl_tpu.utils.logging import get_logger

logger = get_logger(__name__)


def build_genrl_model(args: GenRLArguments) -> TransformerPolicy:
    """Token-mode transformer sized off the shared policy fields, with
    ``max_len`` covering the largest (prompt, response) bucket pair (and
    the packed row length when the pad-free learner is on)."""
    max_p = bucket_for(args.prompt_len, default_buckets(args.prompt_len))
    max_r = bucket_for(
        args.max_new_tokens, default_buckets(args.max_new_tokens)
    )
    max_len = max_p + max_r
    seg_fn = None
    if getattr(args, "learner_packing", False):
        from scalerl_tpu.ops.pallas_attention import make_segment_attn_fn

        seg_fn = make_segment_attn_fn(args.learner_packed_attn)
        max_len = max(max_len, args.learner_pack_len or 0)
    bf16 = bool(getattr(args, "bf16_params", False))
    import jax.numpy as jnp

    spec = block_spec(
        args.block_family,
        head_dim=args.head_dim or None,
        norm_eps=args.rms_norm_eps,
        rope_theta=args.rope_theta,
        num_experts=args.moe_experts,
        experts_per_token=args.moe_experts_per_token,
        expert_width=args.moe_hidden,
        norm_topk_prob=args.moe_norm_topk_prob,
        q_lora_rank=args.mla_q_lora_rank,
        kv_lora_rank=args.mla_kv_lora_rank,
        qk_nope_head_dim=args.mla_qk_nope_head_dim,
        qk_rope_head_dim=args.mla_qk_rope_head_dim,
        v_head_dim=args.mla_v_head_dim,
        ffn_hidden=args.ffn_hidden,
        zero_experts=args.moe_zero_experts,
        experts_held=args.moe_experts_held,
        first_expert=args.moe_first_expert,
        routed_scaling=args.moe_routed_scaling,
        scoring=args.moe_scoring,
        shared_experts=args.moe_shared_experts,
        kv_heads=args.kv_heads,
        expert_act=args.moe_expert_act,
        shared_width=args.moe_shared_width,
        ssm_heads=args.ssm_heads,
        ssm_head_dim=args.ssm_head_dim,
        ssm_state=args.ssm_state,
        ssm_groups=args.ssm_groups,
        ssm_conv=args.ssm_conv,
        ssm_chunk=args.ssm_chunk,
        rotary_dim=args.rotary_dim,
        cca_time0=args.cca_time0,
        cca_time1=args.cca_time1,
        router_width=args.router_hidden,
        streams=args.hc_mult,
        hc_iters=args.hc_sinkhorn_iters,
        hc_eps=args.hc_eps,
        hc_clamp=(args.hc_clamp_min, args.hc_clamp_max),
        rope_scaling=(
            RopeScaling(
                args.rope_factor, args.rope_original_max, args.rope_beta_fast,
                args.rope_beta_slow, args.rope_mscale, args.rope_mscale_all_dim,
            )
            if args.rope_factor != 1.0 else None
        ),
    )
    if args.layer_pattern:
        layers = pattern_specs(spec, args.layer_pattern)
    elif args.full_attention_interval:
        layers = interval_specs(spec, args.n_layers, args.full_attention_interval)
    elif args.dense_layers:
        layers = layer_specs(spec, args.n_layers, args.dense_layers)
    else:  # a stack of one kind of layer is its block, ``n_layers`` times
        layers = ()
    return TransformerPolicy(
        num_actions=args.vocab_size,
        vocab_size=args.vocab_size,
        d_model=args.d_model,
        num_heads=args.n_heads,
        num_layers=args.n_layers,
        max_len=max_len,
        dtype=jnp.bfloat16 if bf16 else jnp.float32,
        param_dtype=jnp.bfloat16 if bf16 else jnp.float32,
        segment_attn_fn=seg_fn,
        block=spec,
        layers=layers,
        mtp_layers=args.mtp_layers,
    )


def _engine_config(
    args: GenRLArguments, lanes: int, max_prompt_len: int
) -> ContinuousConfig:
    """The generation engine's config off the run args (one definition
    for the in-process trainer and the generation hosts' factory)."""
    return ContinuousConfig(
        vocab_size=args.vocab_size,
        max_prompt_len=max_prompt_len,
        max_new_tokens=args.max_new_tokens,
        temperature=args.temperature,
        top_k=args.top_k,
        eos_token=args.eos_token,
        seed=args.seed,
        lanes=lanes,
        page_size=args.genrl_page_size,
        num_pages=args.genrl_num_pages,
        steps_per_macro=args.genrl_macro_steps,
        admit_max_wait_s=args.genrl_admit_wait_ms / 1e3,
        max_pending=args.genrl_max_pending,
        paged_attn=args.genrl_paged_attn,
        steps_in_flight=args.genrl_steps_in_flight,
        prefix_cache=args.genrl_prefix_cache,
        spec_k=args.spec_k if args.spec_enable else 0,
        spec_ngram=args.spec_ngram,
    )


def _bucketed_rows(pk, row_buckets, pad_gauge):
    """Bucket a :class:`PackedLearnerBatch`'s row count up the pow2
    ladder (shape-stable ``seq_add``), publish the batch pad ratio, and
    return ``(fields, priorities, decode_tokens)`` — the insert triple
    both trainers feed the replay."""
    pk = pk.bucketed(bucket_for(max(pk.rows, 1), row_buckets))
    pad_gauge.set(pk.pad_ratio)
    fields, priorities = pk.fields()
    return fields, priorities, pk.decode_tokens


class SequenceRLTrainer:
    """Single-learner sequence-RL loop over a synthetic (or injected) task.

    ``task``: anything with ``sample_prompts(batch, rng) -> (prompts,
    lengths)`` and ``score(prompts, lengths, response, response_len) ->
    rewards`` — defaults to the hermetic :class:`TokenRecallTask`.
    """

    def __init__(
        self,
        args: GenRLArguments,
        task: Optional[Any] = None,
        agent: Optional[TokenPPOAgent] = None,
    ) -> None:
        args.validate()
        self.args = args
        self.task = task or TokenRecallTask(
            vocab_size=args.vocab_size,
            prompt_len=args.prompt_len,
            response_len=args.max_new_tokens,
        )
        self.agent = agent or TokenPPOAgent(args, build_genrl_model(args))
        maybe_enable_mesh_from_args(self.agent, args)
        self._mesh_lock = threading.Lock()
        self.engine = ContinuousEngine(
            self.agent.model,
            self.agent.get_weights(),
            _engine_config(
                args,
                args.genrl_lanes or args.genrl_batch,
                max(
                    getattr(self.task, "max_prompt_len", args.prompt_len),
                    args.prompt_len,
                ),
            ),
            iter_mode=args.genrl_iter_mode,
        )
        # a macro-step can finish more lanes than one learn batch
        # consumes; extras carry into the next round so insert batches
        # stay shape-stable (seq_add compiles once per batch size)
        self._completion_backlog = []
        # replay geometry is pinned to the engine's LARGEST bucket pair so
        # one buffer covers every round
        self._prompt_pad = bucket_for(
            self.engine.config.max_prompt_len,
            self.engine.config.resolved_prompt_buckets(),
        )
        self._response_pad = bucket_for(
            args.max_new_tokens,
            self.engine.config.resolved_response_buckets(),
        )
        # pad-free packed learner (ISSUE 15): the replay unit becomes a
        # packed ROW of several compact sequences; insert row counts pad
        # up a pow2 ladder so seq_add compiles once per bucket
        self.packing = bool(args.learner_packing)
        self._pack_len = args.learner_pack_len or (
            self._prompt_pad + self._response_pad
        )
        self._row_buckets = default_buckets(args.genrl_batch)
        self.replay = seq_init(
            packed_field_shapes(self._pack_len)
            if self.packing
            else sequence_field_shapes(
                self._prompt_pad, self._response_pad
            ),
            (),  # no recurrent core: attention over the cache is the memory
            args.genrl_buffer_sequences,
        )
        self._seq_method = resolve_sample_method("auto")
        self._rng = np.random.default_rng(args.seed)
        self._sample_key = jax.random.PRNGKey(args.seed + 1)
        self.learn_steps = 0
        reg = telemetry.get_registry()
        self._learn_meter = reg.meter("genrl.learn_steps_per_s")
        self._reward_gauge = reg.gauge("genrl.mean_reward")
        self._stale_gauge = reg.gauge("genrl.staleness")
        self._kl_gauge = reg.gauge("genrl.kl_ref")
        self._pad_gauge = reg.gauge("genrl.pad_ratio")
        self.reward_history: List[float] = []

    def _dispatch_guard(self):
        """Serialize multi-device dispatch when the agent is meshed (the
        HostPlaneMixin idiom, graftlint JG002): single-device runs keep
        the lock-free fast path."""
        if (
            getattr(self.agent, "mesh", None) is not None
            or getattr(self.agent, "_learn_mesh", None) is not None
        ):
            return self._mesh_lock
        return nullcontext()

    def _generate(self) -> List[Any]:
        """One round's generation: keep the lane pool fed until exactly
        ``genrl_batch`` sequences have finished (macro-steps that overshoot
        bank their extras in the backlog: insert batches stay
        shape-stable).  Engine cycles only; the packing is ``round.pack``."""
        B = self.args.genrl_batch
        spp = self.args.samples_per_prompt
        with tracing.span("round.generate", kind="genrl") as gen_span:
            macro_steps, groups = self.engine.macro_steps, 0
            while len(self._completion_backlog) < B:
                deficit = (
                    B
                    - len(self._completion_backlog)
                    - self.engine.live_lanes
                    - self.engine.pending
                )
                if deficit > 0:
                    # group sampling: one submit_group per distinct prompt
                    # fans out into spp lanes sharing the prompt KV
                    # copy-on-write (overshoot banks in the backlog)
                    n_groups = -(-deficit // spp)
                    prompts, lengths = self.task.sample_prompts(
                        n_groups, self._rng
                    )
                    for i in range(n_groups):
                        self.engine.submit_group(prompts[i], spp, lengths[i])
                    groups += n_groups
                self._completion_backlog.extend(self.engine.step())
            batch = self._completion_backlog[:B]
            self._completion_backlog = self._completion_backlog[B:]
            gen_span.set(
                macro_steps=self.engine.macro_steps - macro_steps,
                groups_submitted=groups,
                decode_tokens=float(
                    sum(len(c.response_tokens) for c in batch)
                ),
            )
        return batch

    def _round(self):
        """Generate, then pack and score on the host: the insert triple,
        the rewards and the round's decode tokens."""
        batch = self._generate()
        with tracing.span("round.pack", kind="genrl"):
            packed = pack_completions(
                batch, self._prompt_pad, self._response_pad
            )
        with tracing.span("round.score", kind="genrl"):
            rewards = self.task.score(
                packed.prompts,
                packed.prompt_len,
                packed.response_tokens,
                packed.response_len,
            )
        with tracing.span("round.pack", kind="genrl"):
            if self.packing:
                pk = packed_rows_from_completions(
                    packed, rewards, self._pack_len
                )
                fields, priorities, decode = _bucketed_rows(
                    pk, self._row_buckets, self._pad_gauge
                )
                return fields, priorities, rewards, decode
            self._pad_gauge.set(
                1.0
                - (packed.prompt_len.sum() + packed.mask.sum())
                / max(packed.sequences.size, 1)
            )
            fields, priorities = packed.fields(rewards)
            return fields, priorities, rewards, packed.decode_tokens

    def train_round(self) -> Dict[str, float]:
        """One generate -> score -> insert -> sample -> learn round."""
        # one live span per phase (runtime/tracing.span): each is a profiler
        # annotation, a recorded span when the round was head-sampled
        # (SCALERL_TRACE_SAMPLE) and always a count and a total in the
        # registry (tracing.span_totals); none forces a device value
        # (JG001).  The phases tile the round: what the root holds beside
        # them is the bookkeeping at its end
        with tracing.span("genrl.round", kind="genrl") as root:
            fields, priorities, rewards, decode_tokens = self._round()
            with self._dispatch_guard():
                with tracing.span("round.seq_add", kind="genrl"):
                    self.replay = seq_add(self.replay, fields, (), priorities)
                with tracing.span("round.sample", kind="genrl"):
                    self._sample_key, sub = jax.random.split(self._sample_key)
                    batch, _core, _idx, weights = seq_sample(
                        self.replay,
                        sub,
                        self.args.genrl_sample_batch,
                        method=self._seq_method,
                    )
                    batch = dict(batch)
                    batch["is_weight"] = weights
                with tracing.span("round.learn", kind="genrl"):
                    metrics = self.agent.learn(batch)  # ONE batched transfer
            self.learn_steps += 1
            self._learn_meter.mark()
            if self.learn_steps % self.args.genrl_push_every == 0:
                # learner_step feeds the plane's gen -> step map, so
                # staleness below reports the UNIFIED definition (learner
                # steps behind the newest generation, docs/OBSERVABILITY.md)
                with tracing.span("round.push", kind="genrl") as push:
                    self.engine.push_params(
                        self.agent.get_weights(), learner_step=self.learn_steps
                    )
                    push.set(**self.engine.last_push)
            # staleness off the metric that already crossed the host
            # boundary inside the batched read: no extra transfer
            staleness = self.engine.staleness_steps(
                int(round(metrics["mean_generation"]))
            )
            self._stale_gauge.set(staleness)
            telemetry.observe_staleness(staleness, plane="genrl")
            mean_reward = float(np.mean(rewards))
            self._reward_gauge.set(mean_reward)
            if "kl_ref" in metrics:
                self._kl_gauge.set(metrics["kl_ref"])
            metrics["round_reward"] = mean_reward
            metrics["staleness"] = staleness
            metrics["decode_tokens"] = float(decode_tokens)
            self.reward_history.append(mean_reward)
            root.set(step=self.learn_steps, staleness=staleness)
        return metrics

    def lowered_programs(self) -> Dict[str, Any]:
        """The round's three kernel-bearing programs, lowered against the
        trainer's live state: the engine's ``decode`` macro-step, the
        replay ``sample`` and the ``learn`` step.  Nothing is trained or
        donated; the sample runs once to give the learn step its batch."""
        key = jax.random.PRNGKey(0)
        n = self.args.genrl_sample_batch
        programs = {
            "sample": seq_sample.lower(
                self.replay, key, n, method=self._seq_method
            )
        }
        with self._dispatch_guard():
            batch, _core, _idx, weights = seq_sample(
                self.replay, key, n, method=self._seq_method
            )
            programs["learn"] = self.agent.lower_learn(
                dict(batch, is_weight=weights)
            )
        programs["decode"] = self.engine.lower_decode()
        return programs

    def train(self, rounds: Optional[int] = None) -> Dict[str, float]:
        rounds = rounds if rounds is not None else self.args.genrl_rounds
        metrics: Dict[str, float] = {}
        log_every = max(getattr(self.args, "logger_frequency", 50) or 50, 1)
        for i in range(rounds):
            metrics = self.train_round()
            if (i + 1) % log_every == 0 or i + 1 == rounds:
                logger.info(
                    "genrl round %d/%d reward=%.3f loss=%.4f staleness=%.1f",
                    i + 1,
                    rounds,
                    metrics.get("round_reward", 0.0),
                    metrics.get("total_loss", 0.0),
                    metrics.get("staleness", 0.0),
                )
        summary = dict(metrics)
        tail = self.reward_history[-10:]
        summary["final_reward_mean"] = float(np.mean(tail)) if tail else 0.0
        summary["rounds"] = float(len(self.reward_history))
        return summary


# ---------------------------------------------------------------------------
# the disaggregated topology (ISSUE 12): generation fleet -> this learner


class _WireCompletion:
    """Adapter: one wire sequence payload viewed through the
    ``CompletedSequence`` attribute surface ``pack_completions`` reads."""

    __slots__ = (
        "prompt", "prompt_len", "response_tokens", "behavior_logp",
        "values", "generation",
    )

    def __init__(self, payload: Dict[str, Any]) -> None:
        self.prompt = np.asarray(payload["prompt"], np.int32)
        self.prompt_len = int(payload["prompt_len"])
        self.response_tokens = np.asarray(
            payload["response_tokens"], np.int32
        )
        self.behavior_logp = np.asarray(payload["behavior_logp"], np.float32)
        self.values = np.asarray(payload["values"], np.float32)
        self.generation = int(payload["generation"])


class _EngineShellFactory:
    """Picklable engine factory for the generation hosts: builds the
    token-mode model + generation engine from the run args INSIDE the
    host process — the only seam of the disagg shell that touches jax."""

    def __init__(self, args: GenRLArguments, lanes: int) -> None:
        self.args = args
        self.lanes = lanes

    def __call__(self, params: Any, generation: int):
        from scalerl_tpu.genrl.disagg import (
            ContinuousEngineShell,
            _device_ready,
        )

        args = self.args
        engine = ContinuousEngine(
            build_genrl_model(args),
            _device_ready(params),
            _engine_config(args, self.lanes, args.prompt_len),
            iter_mode=args.genrl_iter_mode,
        )
        return ContinuousEngineShell(engine, initial_generation=generation)


class DisaggSequenceRLTrainer:
    """Sequence RL over the disaggregated dataflow (``genrl/disagg.py``):
    ``disagg_hosts`` generation hosts behind jax-free shells stream
    completed, generation-tagged sequences over the codec-v2 fleet wire
    into this learner's sequence replay; quantized param snapshots flow
    back every ``genrl_push_every`` learn steps.  The learn half (replay,
    token-PPO step, dp×mp mesh) is identical to
    :class:`SequenceRLTrainer` — disaggregation changes WHERE sequences
    are born, not how they are learned from.

    ``use_threads=True`` (default) runs the hosts as in-process threads —
    the wire, lease/ack/dedup, and snapshot protocol all still flow, with
    no per-host jax process spin-up; ``False`` spawns real host processes
    (the chaos/soak shape).

    Preemption tolerance (docs/DISTRIBUTED.md "Preemption & elastic
    membership"): with ``ledger_dir`` set, the trainer rides the durable
    learner ledger — a :class:`~scalerl_tpu.runtime.supervisor.
    PreemptionGuard` safe-point between rounds turns SIGTERM into
    :meth:`save_resume` (full learner accounting plane + replay contents +
    agent weights + lease cursor/RNG in ONE crash-safe frame), and the
    next construction against the same ``ledger_dir`` resumes at the same
    learn step under a bumped learner epoch.
    """

    def __init__(
        self,
        args: GenRLArguments,
        task: Optional[Any] = None,
        agent: Optional[TokenPPOAgent] = None,
        engine_factory: Optional[Any] = None,
        use_threads: bool = True,
        ledger_dir: Optional[str] = None,
        guard: Optional[Any] = None,
    ) -> None:
        from scalerl_tpu.genrl.disagg import (
            DisaggConfig,
            LocalGenerationFleet,
            SequenceLearner,
            record_consumption_trace,
        )
        from scalerl_tpu.runtime.param_server import _to_host

        self._record_consumption_trace = record_consumption_trace

        args.validate()
        self.args = args
        self._to_host = _to_host
        self.task = task or TokenRecallTask(
            vocab_size=args.vocab_size,
            prompt_len=args.prompt_len,
            response_len=args.max_new_tokens,
        )
        self.agent = agent or TokenPPOAgent(args, build_genrl_model(args))
        maybe_enable_mesh_from_args(self.agent, args)
        self._mesh_lock = threading.Lock()
        self._prompt_pad = bucket_for(
            args.prompt_len, default_buckets(args.prompt_len)
        )
        self._response_pad = bucket_for(
            args.max_new_tokens, default_buckets(args.max_new_tokens)
        )
        # disaggregation changes WHERE sequences are born, not how they
        # are learned from: the packed learner rides identically here
        self.packing = bool(args.learner_packing)
        self._pack_len = args.learner_pack_len or (
            self._prompt_pad + self._response_pad
        )
        self._row_buckets = default_buckets(args.genrl_batch)
        self.replay = seq_init(
            packed_field_shapes(self._pack_len)
            if self.packing
            else sequence_field_shapes(
                self._prompt_pad, self._response_pad
            ),
            (),
            args.genrl_buffer_sequences,
        )
        self._seq_method = resolve_sample_method("auto")
        self._sample_key = jax.random.PRNGKey(args.seed + 1)
        lanes = args.disagg_lanes_per_host or max(
            1, args.genrl_batch // args.disagg_hosts
        )
        self.config = DisaggConfig(
            num_hosts=args.disagg_hosts,
            lanes_per_host=lanes,
            upload_batch=args.disagg_upload_batch,
            snapshot_quantize=args.disagg_quantize,
            # a shallow accepted-sequence queue + stale-eviction keeps the
            # consumed data fresh: queue depth IS worst-case staleness
            seq_maxsize=max(4 * args.genrl_batch, 2 * lanes * args.disagg_hosts),
        )
        # the learner owns the prompts: leases carry the task-sampled
        # tokens so generation hosts stay task-agnostic decode capacity
        self._lease_rng = np.random.default_rng(args.seed + 2)
        self._lease_lock = threading.Lock()
        self._lease_seq = 0
        self.guard = guard
        ledger_dir = ledger_dir or getattr(args, "disagg_ledger_dir", "")
        self.ledger_path = (
            os.path.join(ledger_dir, "learner_ledger") if ledger_dir else None
        )
        self.learner = SequenceLearner(
            self.config, self._next_lease, ledger_path=self.ledger_path
        )
        self.learn_steps = 0
        self.reward_history: List[float] = []
        if self.learner.restored_extra is not None:
            self._adopt_restored(self.learner.restored_extra)
        self.learner.start()
        if self.learner.generation == 0:
            # fresh start only: a restored learner already holds the wire
            # snapshot (and generation counter) its hosts must adopt
            self.learner.publish(
                self._to_host(self.agent.get_weights()), learner_step=0
            )
        self.fleet = LocalGenerationFleet(
            self.learner,
            self.config,
            engine_factory or _EngineShellFactory(args, lanes),
            use_threads=use_threads,
        )
        self.fleet.start()
        reg = telemetry.get_registry()
        self._learn_meter = reg.meter("genrl.learn_steps_per_s")
        self._reward_gauge = reg.gauge("genrl.mean_reward")
        self._pad_gauge = reg.gauge("genrl.pad_ratio")

    def _adopt_restored(self, extra: Dict[str, Any]) -> None:
        """Rebuild the trainer half of a preempted run from the ledger's
        ``extra`` tree: learn step, replay contents, agent weights, the
        lease cursor + RNG (so resumed prompt leases continue the exact
        pre-restart sequence), and the reward history."""
        self.learn_steps = int(extra.get("learn_steps", 0))
        self._lease_seq = int(extra.get("lease_seq", 0))
        rng_state = extra.get("lease_rng")
        if rng_state:
            # PCG64 state words are 128-bit — they ride the ledger as a
            # JSON string, not codec ints
            self._lease_rng.bit_generator.state = json.loads(rng_state)
        if "replay" in extra:
            self.replay = seq_import(extra["replay"])
        if "agent" in extra:
            self.agent.set_weights(jax.device_put(extra["agent"]))
        self.reward_history = [
            float(r) for r in extra.get("reward_history", [])
        ]
        logger.info(
            "disagg trainer resumed at learn step %d (epoch %d, "
            "%d leases reissued)",
            self.learn_steps, self.learner.learner_epoch,
            self.learner.resumed_sequences_reissued,
        )

    def save_resume(self) -> Optional[str]:
        """The PreemptionGuard safe-point action: stop the plane and
        persist learner ledger + trainer state as one crash-safe frame
        (write-new-then-rotate + sha256 manifest).  Returns the ledger
        path, or None when no ``ledger_dir`` is configured."""
        self.learner.stop()
        if self.ledger_path is None:
            return None
        extra = {
            "learn_steps": self.learn_steps,
            "lease_seq": self._lease_seq,
            "lease_rng": json.dumps(self._lease_rng.bit_generator.state),
            "reward_history": [float(r) for r in self.reward_history],
            "replay": seq_export(self.replay),
            "agent": self._to_host(self.agent.get_weights()),
        }
        return self.learner.save_ledger(self.ledger_path, extra=extra)

    def _dispatch_guard(self):
        """Serialize multi-device dispatch when the agent is meshed (the
        HostPlaneMixin idiom, graftlint JG002).  Meshed runs should pair
        this with PROCESS hosts (``use_threads=False``) so generation
        dispatch lives in its own jax runtime entirely."""
        if (
            getattr(self.agent, "mesh", None) is not None
            or getattr(self.agent, "_learn_mesh", None) is not None
        ):
            return self._mesh_lock
        return nullcontext()

    def _next_lease(self) -> Dict[str, Any]:
        with self._lease_lock:
            self._lease_seq += 1
            seq = self._lease_seq
            prompts, lengths = self.task.sample_prompts(1, self._lease_rng)
        n = int(lengths[0])
        lease = {
            "seed": seq,
            "prompt": prompts[0, :n].astype(np.int32),
            "length": n,
        }
        spp = self.args.samples_per_prompt
        if spp > 1:
            # group sampling: this lease fans out into spp completions on
            # the generation host (submit_group: one shared prompt prefix)
            # — the learner counts the lease complete when all spp
            # samples arrived
            lease["samples"] = spp
        return lease

    def train_round(self) -> Dict[str, float]:
        """One disaggregated round: drain ``genrl_batch`` wire sequences
        from the fleet -> pack -> score -> insert -> sample -> learn ->
        publish a quantized snapshot."""
        B = self.args.genrl_batch
        batch: List[_WireCompletion] = []
        raw: List[Dict[str, Any]] = []  # keeps the trace/_t_q wire keys
        deadline = time.monotonic() + self.args.disagg_round_timeout_s
        while len(batch) < B:
            payload = self.learner.get_sequence(timeout=0.2)
            if payload is not None:
                raw.append(payload)
                batch.append(_WireCompletion(payload))
            elif time.monotonic() > deadline:
                raise RuntimeError(
                    f"disagg round starved: {len(batch)}/{B} sequences "
                    f"after {self.args.disagg_round_timeout_s:.0f}s "
                    f"(live hosts: {self.learner.live_host_count()})"
                )
        t_drain = time.monotonic()
        packed = pack_completions(
            batch, self._prompt_pad, self._response_pad
        )
        rewards = self.task.score(
            packed.prompts,
            packed.prompt_len,
            packed.response_tokens,
            packed.response_len,
        )
        if self.packing:
            pk = packed_rows_from_completions(
                packed, rewards, self._pack_len
            )
            fields, priorities, _decode = _bucketed_rows(
                pk, self._row_buckets, self._pad_gauge
            )
        else:
            self._pad_gauge.set(
                1.0
                - (packed.prompt_len.sum() + packed.mask.sum())
                / max(packed.sequences.size, 1)
            )
            fields, priorities = packed.fields(rewards)
        t_add0 = time.monotonic()
        with self._dispatch_guard():
            self.replay = seq_add(self.replay, fields, (), priorities)
            self._sample_key, sub = jax.random.split(self._sample_key)
            learn_batch, _core, _idx, weights = seq_sample(
                self.replay,
                sub,
                self.args.genrl_sample_batch,
                method=self._seq_method,
            )
            learn_batch = dict(learn_batch)
            learn_batch["is_weight"] = weights
            t_learn0 = time.monotonic()
            metrics = self.agent.learn(learn_batch)  # ONE batched transfer
        self.learn_steps += 1
        # extend each consumed sequence's trace with the learner-side edges
        # (replay wait -> seq_add -> the learn step that consumed it) — the
        # monotonic stamps above were taken around work the round already
        # does, so tracing off costs nothing
        self._record_consumption_trace(
            raw, t_drain, t_add0, t_learn0, t_learn0, time.monotonic(),
            self.learn_steps,
        )
        self._learn_meter.mark()
        if self.learn_steps % self.args.genrl_push_every == 0:
            self.learner.publish(
                self._to_host(self.agent.get_weights()),
                learner_step=self.learn_steps,
            )
        staleness = self.learner.observe_consumed(
            int(round(metrics["mean_generation"]))
        )
        mean_reward = float(np.mean(rewards))
        self._reward_gauge.set(mean_reward)
        metrics["round_reward"] = mean_reward
        metrics["staleness"] = staleness
        metrics["decode_tokens"] = float(packed.decode_tokens)
        self.reward_history.append(mean_reward)
        return metrics

    def train(self, rounds: Optional[int] = None) -> Dict[str, float]:
        rounds = rounds if rounds is not None else self.args.genrl_rounds
        metrics: Dict[str, float] = {}
        try:
            for _ in range(rounds):
                if self.guard is not None and self.guard.poll_chaos(
                    "learner"
                ):
                    # the safe-point: SIGTERM (real, or the chaos plan's
                    # seeded preempt draw) landed — save the full plane
                    # between rounds and exit; the next construction
                    # against the same ledger_dir resumes this step
                    telemetry.record_event(
                        "preemption_exit",
                        plane="disagg",
                        step=self.learn_steps,
                    )
                    self.save_resume()
                    break
                metrics = self.train_round()
        finally:
            self.close()
        summary = dict(metrics)
        tail = self.reward_history[-10:]
        summary["final_reward_mean"] = float(np.mean(tail)) if tail else 0.0
        summary["rounds"] = float(len(self.reward_history))
        summary["wire_sequences"] = float(self.learner.total_sequences)
        summary["learn_steps"] = float(self.learn_steps)
        return summary

    def close(self) -> None:
        self.learner.stop()
        self.fleet.join(timeout=5.0)
