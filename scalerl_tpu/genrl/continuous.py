"""Continuous-batching decode: a persistent lane pool over a paged KV cache.

The vLLM shape of the generation plane (ISSUE 11; MindSpeed RL argues the
generation tier is where sequence-RL throughput is won, arxiv 2507.19017):
instead of fixed cohorts where every lane waits for the slowest sequence,
:class:`ContinuousEngine` runs a FIXED number of decode lanes forever and
the host swaps *sequences* through them —

- **macro-steps** — ONE jitted program (compiled once; lane count, page
  geometry and ``steps_per_macro`` are all static) advances every lane
  ``steps_per_macro`` tokens: sample from the carried last-logits, latch
  EOS / response-budget, scatter the new K/V into pool pages, attend
  through the page table (``ops/pallas_paged_attention.py`` behind the
  ``paged_attn_fn`` seam), carry the fresh logits.  The host dispatches
  once and reads back once — PR 10's one-batched-read round discipline at
  macro-step granularity, under ``steady_state_guard()`` once warm;
- **pipelined admission/decode** (ISSUE 14) — ``steps_in_flight`` macro
  dispatches stay in flight with the host read lagging dispatch by K-1
  (the PR 1 ``MetricsPipeline`` idiom applied to the decode loop), so
  harvest, cache lookups, admission bookkeeping and prefill overlap
  device decode instead of serializing with it.  ``K=1`` is the old
  fully-synchronous semantics, parity-pinned.  A lane that latches done
  mid-flight keeps null-writing until its macro is read — exactly the
  within-macro dead-lane behavior, stretched K-1 macros;
- **continuous admission** — between macro-steps the host harvests lanes
  that finished (frees their pages immediately — KV memory tracks LIVE
  tokens), then admits queued prompts into the freed lanes through the
  serving batcher's flush-on-size-or-deadline predicate
  (:meth:`DynamicBatcher.poll_batch`) and the shared pow2 bucket ladder.
  Admission looks up the :class:`~scalerl_tpu.genrl.prefix_cache
  .PrefixCache` first: the longest cached full-page prefix is *shared*
  into the lane's table (a refcount bump, zero FLOPs) and only the
  uncached tail is prefilled — through the local-attention prefill
  program when nothing matched, or the shared-table tail-prefill program
  (gather-through-table attention) on a hit;
- **group sampling (CoW fork)** — :meth:`submit_group` admits one prompt
  into ``n`` lanes: the leader prefills (tail only, as above), the other
  ``n-1`` lanes map the SAME full prompt pages copy-on-write and only the
  last partial page is physically copied per lane by a small jitted
  page-copy program — so a GRPO-shaped round pays ~1/n of its prefill;
- **paged KV** — the cache the model describes
  (``TransformerPolicy.init_paged_cache``, one
  :class:`~scalerl_tpu.models.transformer.ModelCache` whatever the stack:
  a K and a V pool an attention layer, or the one latent pool an ``mla``
  attention caches into) plus the jax-free refcounting
  :class:`~scalerl_tpu.genrl.paging.PageAllocator`: admission reserves a
  sequence's worst-case pages
  (exhaustion backpressures, never corrupts; shared pages count against
  EVERY holder's reservation, so sharing never loosens the guarantee)
  while physical pages are drawn lazily as contexts grow.
- **lane state beside the pages** -- a stack with a layer that carries
  lane state (``model.lane_state``: a Mamba-2 or a Gated DeltaNet
  mixer's state and window, a compressed convolutional attention's
  window beside its OWN pools) has, in the same cache's ``ssm`` and
  ``conv`` fields (empty for every other model), float32 arrays indexed
  by LANE, whose size does not depend on a lane's length, beside the page
  pools of its attention layers.  It rides in the same pytree as the pools
  (donated through every program, never copied whole): the local prefill
  writes a lane's rows at the prompt's true length, every decode substep
  updates them in place, the group fork copies the leader's rows to the
  members, and a dead lane's rows are whatever they are until the next
  prefill writes them.  No page table describes that state, so such a
  model is admitted **by local prefill and group fork only**: prefix-cache
  lookups and inserts are skipped (``genrl.prefix_skipped_recurrent``)
  and speculation is refused;

- **speculative decoding** (ISSUE 16, ``spec_k > 0``) — the sequential-
  depth lever: each pass, every live lane proposes up to ``spec_k``
  continuation tokens from its own jax-free n-gram table
  (:class:`~scalerl_tpu.genrl.drafter.NgramDrafter` — no second model,
  nothing extra rides the snapshot plane), and ONE batched verify program
  scores all proposed tokens through the shared-table tail-prefill path:
  it samples the bonus token from the carried logits in-program, feeds
  ``[t0, d1..dk]`` at positions ``cl..cl+k``, accepts the longest draft
  prefix under the exact speculative-sampling rule (greedy match at
  temperature 0; accept-with-prob ``pi(d)`` plus a carried banned-token
  residual resample at temperature > 0 — the output distribution is
  UNCHANGED either way), and advances each lane ``1..k+1`` tokens.
  Rejected tails roll back host-side via page-cursor rewind
  (:func:`~scalerl_tpu.genrl.paging.rewind_pages` — a refcount
  decrement, never a mutation, so CoW-shared pages are untouched); the
  device needs no rollback at all because attention never reads past a
  lane's cursor and the next pass's writes overwrite the rejected slots.
  Spec mode is inherently synchronous (drafting pass ``m+1`` needs pass
  ``m``'s emitted tokens), so it runs at ``steps_in_flight = 1``
  semantics regardless of the configured depth.

At temperature 0 the engine's tokens are those of a greedy full forward
(``model.apply`` on the whole sequence) on the same params — the parity
the acceptance tests pin, with the prefix cache on or off, speculation on
or off.  A sequence is tagged with the param
generation that admitted it; a ``push_params`` mid-flight rotates the
policy under lanes already decoding (inherent to continuous batching; the
token-PPO ratios absorb it exactly like actor lag) and FLUSHES the prefix
cache — cached K/V belongs to the generation that wrote it.
"""

from __future__ import annotations

import time
from collections import deque
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import (
    Any,
    Callable,
    Deque,
    Dict,
    List,
    NamedTuple,
    Optional,
    Tuple,
)

import jax
import jax.numpy as jnp
import numpy as np

from scalerl_tpu.genrl.drafter import NgramDrafter
from scalerl_tpu.genrl.paging import PageAllocator, rewind_pages
from scalerl_tpu.genrl.prefix_cache import PrefixCache
from scalerl_tpu.models.routed_ffn import router_balance
from scalerl_tpu.models.transformer import (
    TransformerPolicy,
    fork_cache,
    prompt_attention_mask,
)
from scalerl_tpu.ops.pallas_paged_attention import (
    largest_copy,
    make_paged_attn_fn,
    pages_per_block,
    table_copies,
)
from scalerl_tpu.runtime import telemetry, tracing
from scalerl_tpu.runtime.device_loop import resolve_iter_mode
from scalerl_tpu.runtime.dispatch import steady_state_guard
from scalerl_tpu.runtime.param_server import ParamSnapshotPlane
from scalerl_tpu.serving.batcher import (
    DynamicBatcher,
    ServingConfig,
    ServingRequest,
)
from scalerl_tpu.utils import profiling  # noqa: F401  (installs the spans' profiler half)
from scalerl_tpu.utils.buckets import bucket_for, default_buckets

# the decode substep's phase that no module names, as a device trace's
# ``op_name`` shows it (``benchmark/op_scopes.py``, PERF.md section 3)
_SCOPE_SAMPLE = "sample"

# module seams: tests monkeypatch these to count host transfers and assert
# the one-upload-one-read-per-macro-step invariant
_device_put = jax.device_put
_device_get = jax.device_get


def adjust_logits(
    logits: jnp.ndarray, temperature: float, top_k: int, vocab_size: int
) -> jnp.ndarray:
    """Sampling adjustments (top-k mask then temperature) — the behavior
    logprob is computed from THESE logits, so the stored logp is the true
    log-density of the sampling distribution.  ``temperature == 0`` (greedy)
    skips the scale: sampling argmaxes and the logp reads the unscaled
    log-softmax."""
    if top_k > 0 and top_k < vocab_size:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits >= kth, logits, jnp.float32(-1e30))
    if temperature > 0:
        logits = logits / jnp.float32(temperature)
    return logits


def sample_tokens(key, adj_logits: jnp.ndarray, temperature: float):
    """Categorical sample from adjusted logits; argmax at temperature 0."""
    if temperature == 0:
        return jnp.argmax(adj_logits, axis=-1)
    return jax.random.categorical(key, adj_logits, axis=-1)


@dataclass
class ContinuousConfig:
    """Sampling knobs plus the continuous-batching geometry.

    ``eos_token < 0`` disables early stopping (fixed-length responses, the
    synthetic-task default); with an EOS id, a lane latches done on
    sampling it.  ``temperature == 0`` selects greedy (argmax) decoding —
    the setting the parity tests pin token-identical outputs at.

    ``num_pages = 0`` sizes the pool for every lane's worst case (null
    page included) — no admission backpressure by default; smaller pools
    trade admission latency for KV memory and are exercised by the
    exhaustion tests.  ``admit_max_wait_s`` is the deadline half of the
    admission flush predicate (0 = admit the moment lanes are free).
    """

    vocab_size: int
    max_prompt_len: int = 64
    max_new_tokens: int = 64
    temperature: float = 1.0
    top_k: int = 0  # 0 = full distribution
    eos_token: int = -1
    pad_token: int = 0
    prompt_buckets: Tuple[int, ...] = ()  # () -> pow2 ladder
    response_buckets: Tuple[int, ...] = ()
    seed: int = 0
    lanes: int = 64
    page_size: int = 16
    num_pages: int = 0
    steps_per_macro: int = 8
    admit_max_wait_s: float = 0.0
    max_pending: int = 0  # bounded admission queue; 0 = unbounded
    paged_attn: str = "auto"  # pallas | xla | auto (backend-resolved)
    # Admission batching: hold admission until at least this many lanes are
    # free (unless the pool is fully idle), so prefill dispatches amortize
    # over bigger batches instead of firing per macro-step for a lane or
    # two.  1 = admit the moment anything frees (lowest latency); ~lanes/8
    # trades a little occupancy for much cheaper admission (the measured
    # CPU sweet spot; see docs/SEQUENCE_RL.md "Continuous batching").
    min_free_lanes: int = 1
    # Macro-step pipelining (ISSUE 14): K macro dispatches stay in flight
    # with the host read lagging by K-1, so harvest/admission/prefill
    # overlap device decode.  1 = the old read-after-every-dispatch
    # semantics (parity-pinned); 2 is the measured sweet spot — deeper
    # only lengthens harvest lag without adding overlap.
    steps_in_flight: int = 2
    # Shared-prefix KV reuse (ISSUE 14): cache full prompt pages keyed by
    # rolling block hash, share them copy-on-write into later admissions
    # of the same prefix.  Off = every admission prefills from scratch
    # (the cache-off twin the token-identity tests compare against).
    prefix_cache: bool = True
    # Speculative decoding (ISSUE 16): 0 compiles speculation out entirely
    # (the plain macro-step engine, parity-pinned); k > 0 drafts up to k
    # tokens per lane per pass from the lane's own n-gram table and
    # verifies them in ONE batched pass.  Wins when the workload's
    # acceptance rate clears ~1/(k+1); pure-noise text degrades toward
    # one token per pass (see docs/SEQUENCE_RL.md "Speculative decoding").
    spec_k: int = 0
    # n-gram width the self-drafter matches against the context tail.
    spec_ngram: int = 3

    def resolved_prompt_buckets(self) -> Tuple[int, ...]:
        return tuple(self.prompt_buckets) or default_buckets(self.max_prompt_len)

    def resolved_response_buckets(self) -> Tuple[int, ...]:
        return tuple(self.response_buckets) or default_buckets(self.max_new_tokens)

    def validate(self) -> None:
        if self.vocab_size < 2:
            raise ValueError(f"vocab_size must be >= 2, got {self.vocab_size}")
        if self.max_prompt_len < 1 or self.max_new_tokens < 1:
            raise ValueError(
                "max_prompt_len and max_new_tokens must be >= 1, got "
                f"{self.max_prompt_len}/{self.max_new_tokens}"
            )
        if self.temperature < 0:
            raise ValueError(
                f"temperature must be >= 0 (0 = greedy), got "
                f"{self.temperature}"
            )
        if self.top_k < 0 or self.top_k > self.vocab_size:
            raise ValueError(
                f"top_k must be in [0, vocab_size], got {self.top_k}"
            )
        if self.eos_token >= self.vocab_size:
            raise ValueError(
                f"eos_token {self.eos_token} outside vocab {self.vocab_size}"
            )
        if self.lanes < 1:
            raise ValueError(f"lanes must be >= 1, got {self.lanes}")
        if self.min_free_lanes < 1 or self.min_free_lanes > self.lanes:
            raise ValueError(
                f"min_free_lanes must be in [1, lanes], got "
                f"{self.min_free_lanes}"
            )
        if self.page_size < 1:
            raise ValueError(
                f"page_size must be >= 1, got {self.page_size}"
            )
        if self.steps_per_macro < 1:
            raise ValueError(
                f"steps_per_macro must be >= 1, got {self.steps_per_macro}"
            )
        if self.num_pages < 0:
            raise ValueError(
                f"num_pages must be >= 0 (0 = auto), got {self.num_pages}"
            )
        if self.steps_in_flight < 1:
            raise ValueError(
                f"steps_in_flight must be >= 1, got {self.steps_in_flight}"
            )
        if self.spec_k < 0:
            raise ValueError(
                f"spec_k must be >= 0 (0 = speculation off), got "
                f"{self.spec_k}"
            )
        if self.spec_ngram < 1:
            raise ValueError(
                f"spec_ngram must be >= 1, got {self.spec_ngram}"
            )


class CompletedSequence(NamedTuple):
    """One finished lane occupancy, assembled host-side across the
    macro-steps it spanned."""

    prompt: np.ndarray  # [n] int32 true prompt tokens
    prompt_len: int
    response_tokens: np.ndarray  # [r] int32 real tokens only
    behavior_logp: np.ndarray  # [r] f32
    values: np.ndarray  # [r] f32
    generation: int  # param generation at admission
    submit_time: float
    admit_time: float
    finish_time: float
    # opaque caller tag carried from submit() to harvest — the disagg
    # shell routes prompt-lease ids through it so out-of-order completions
    # still close the lease that admitted them
    tag: Any = None


def _snapshot_overwrite(new, old):
    """A copy of ``new``; jitted with ``old`` donated, its outputs take
    ``old``'s buffers (the name is the device program's in a trace)."""
    del old
    return jax.tree_util.tree_map(jnp.copy, new)


@dataclass
class _Lane:
    """Host-side record of one lane's current occupancy."""

    busy: bool = False
    prompt: Optional[np.ndarray] = None
    prompt_len: int = 0
    context_len: int = 0
    pages: List[int] = field(default_factory=list)
    reserved: int = 0
    tokens: List[np.ndarray] = field(default_factory=list)
    logps: List[np.ndarray] = field(default_factory=list)
    values: List[np.ndarray] = field(default_factory=list)
    generation: int = 0
    submit_time: float = 0.0
    admit_time: float = 0.0
    tag: Any = None
    # index of the first macro dispatch that includes this occupancy: a
    # pipelined read of an OLDER macro must not be applied to it (the
    # lane id may have been recycled from a finished occupancy)
    admit_macro: int = 0


class ContinuousEngine(ParamSnapshotPlane):
    """Persistent continuous-batching decode loop over a paged KV cache.

    ``model``: a token-mode :class:`TransformerPolicy` whose ``max_len``
    covers ``prompt_bucket_max + response_bucket``.  The engine compiles
    exactly ONE decode macro-step program (lane count static), one prefill
    program per (bucket, admit-bucket) pair — local-attention for cold
    prompts, shared-table for cached-prefix tails — and one page-copy fork
    program per admit bucket; the ``_decode_traces`` / ``_prefill_traces``
    / ``_fork_traces`` counters let tests pin zero retraces after warmup.
    """

    def __init__(
        self,
        model: TransformerPolicy,
        params: Any,
        config: ContinuousConfig,
        iter_mode: str = "auto",
        dispatch_guard: Optional[Callable[[], Any]] = None,
    ) -> None:
        config.validate()
        if model.vocab_size is None:
            raise ValueError(
                "ContinuousEngine needs a token-mode TransformerPolicy "
                "(vocab_size set); got a feature-embedding model"
            )
        # what a lane carries of a layer (a recurrent state, a window of
        # convolution inputs) is no page-table fact: nothing can enter it
        # at a page boundary (a prefix hit) or rewind it by a page cursor
        # (a rejected draft)
        self._lane_state = model.lane_state
        if self._lane_state and config.spec_k:
            raise ValueError(
                "speculation (spec_k > 0) cannot serve a model with a "
                "layer that carries lane state: a rejected draft is undone "
                "by moving a page cursor back, and what a lane carries has "
                "no cursor to rewind"
            )
        self.config = config
        self.model = model
        self.iter_mode = resolve_iter_mode(iter_mode)
        self._dispatch_guard = dispatch_guard or nullcontext
        # generation is single-chip: pools, lane state and the param
        # snapshots all live on this one device, whatever mesh the learner
        # shards its own copy over (_place re-places every push)
        self._device = jax.devices()[0]
        # a push's copy written over the retired snapshot (_copy_snapshot):
        # ``old`` is donated, and kept though unread; a donated argument
        # the function does not use is otherwise pruned before donation
        self._copy_over = jax.jit(
            _snapshot_overwrite,
            donate_argnums=1,
            keep_unused=True,
            out_shardings=jax.sharding.SingleDeviceSharding(self._device),
        )
        self._paged_attn = make_paged_attn_fn(
            config.paged_attn, model.block.attention
        )
        if model.paged_attn_fn is None:
            # route the model's paged decode reads through the resolved impl
            # (clone shares the param structure: same names, same shapes)
            self.model = model.clone(paged_attn_fn=self._paged_attn)
        if self.model.constrain is not None:
            # a meshed learner's model pins activations to ITS mesh
            self.model = self.model.clone(constrain=None)
        self._init_param_plane(params)
        L = config.lanes
        ps = config.page_size
        self._max_prompt_bucket = bucket_for(
            config.max_prompt_len, config.resolved_prompt_buckets()
        )
        # the response budget is the response BUCKET
        self._response_budget = bucket_for(
            config.max_new_tokens, config.resolved_response_buckets()
        )
        max_context = self._max_prompt_bucket + self._response_budget
        if model.max_len < max_context:
            raise ValueError(
                f"model.max_len ({model.max_len}) must cover prompt bucket "
                f"+ response budget ({max_context})"
            )
        self._pages_per_lane = -(-max_context // ps)  # table width (static)
        num_pages = config.num_pages or (L * self._pages_per_lane + 1)
        # device state: pools + per-lane decode carry (donated through
        # every program; the host rebinds after each dispatch).  The
        # model describes its cache; here it is one pytree of pools
        # (with lane state: pools and the lane-indexed arrays)
        self._pools = model.init_paged_cache(num_pages, ps, lanes=L)
        # what the decode kernels' walk of a pool goes by: a fresh run of
        # pages starts where it can grow to the largest copy of that walk
        # (a block of a narrow pool holds several)
        pool = (self._pools.k + self._pools.rows)[0]
        self._copy_rule = (ps, pool.shape[2], pool.dtype.itemsize, num_pages)
        self.allocator = PageAllocator(num_pages, ps, stretch=largest_copy(*self._copy_rule))
        self._worst_pages = self.allocator.pages_for_tokens(max_context)
        self._prefix_cache: Optional[PrefixCache] = None
        if config.prefix_cache:
            self._prefix_cache = PrefixCache(self.allocator, ps)
            # cached-but-unreferenced chains are reclaimed on demand, so
            # the cache occupies the pool's slack without ever
            # backpressuring admission
            self.allocator.set_reclaim_hook(self._prefix_cache.evict)
        # admission queue: the serving batcher reused verbatim — flush on
        # size (free lanes) OR deadline, bounded by max_pending with sheds
        self._batcher = DynamicBatcher(
            ServingConfig(
                max_batch=L,
                max_wait_s=config.admit_max_wait_s,
                max_pending=config.max_pending,
            )
        )
        self._admit_buckets = default_buckets(L)
        # a routed-experts model: the decode program also returns each
        # substep's token counts of every router output (live lanes, every
        # layer).  The outputs are the computed experts, of which this
        # program holds ``held`` from ``first`` on, then the zero-compute
        # ones; OLMoE holds all it routes over and has none of the latter
        spec = model.block
        self._routed = spec.ffn == "experts"
        self._experts = spec.num_experts
        self._held = slice(
            spec.first_expert,
            spec.first_expert + (spec.experts_held or spec.num_experts),
        )
        self._expert_tokens = np.zeros(
            (model.routed_layers, spec.num_experts + spec.zero_experts), np.int64
        )
        self._expert_hits = 0  # held experts that received a token, summed
        self._expert_substeps = 0  # over this many (substep, layer) pairs
        # bytes a lane carries beside pages, all layers together
        self._state_bytes_per_lane = (
            sum(a.nbytes for a in self._pools.ssm + self._pools.conv) // L
        )
        # what the decode program carries in place beside the pools
        self._dispatch_attrs = (
            {"state_bytes": self._state_bytes_per_lane * L}
            if self._lane_state
            else {}
        )
        self.state_forks = 0  # members whose state rows a fork wrote
        # how far the decode kernels' copies merge: live pages of the tables
        # as uploaded, and the copies a pool's walk issues for them
        self._table_pages = 0
        self._table_copies = 0
        self.prefix_skipped_recurrent = 0  # admissions that skipped the cache
        self._logits_st = jnp.zeros((L, config.vocab_size), jnp.float32)
        self._value_st = jnp.zeros((L,), jnp.float32)
        self._cl = jnp.zeros((L,), jnp.int32)
        self._done = jnp.ones((L,), jnp.bool_)  # inert until admitted
        self._resp = jnp.zeros((L,), jnp.int32)
        # host mirrors / bookkeeping
        self._lanes = [_Lane() for _ in range(L)]
        self._table = np.zeros((L, self._pages_per_lane), np.int32)
        self._key = jax.random.PRNGKey(config.seed)
        self._decode_fn = self._build_decode()
        self._prefill_fns: Dict[Tuple, Callable] = {}
        self._fork_fns: Dict[int, Callable] = {}
        # in-flight macro reads: (dispatch index, device outputs); reads
        # pop the left end once depth reaches steps_in_flight
        self._inflight: Deque[Tuple[int, Any]] = deque()
        self._decode_traces = 0
        self._prefill_traces = 0
        self._fork_traces = 0
        self._verify_traces = 0
        self._warm = False
        self.macro_steps = 0
        self.completed_total = 0
        self._occupancy_sum = 0.0
        # speculative decode (ISSUE 16): compiled out entirely at k = 0 —
        # the plain macro-step path never pays a branch, a wider program,
        # or drafter bookkeeping
        self._spec_k = config.spec_k
        self._drafter: Optional[NgramDrafter] = None
        # verify programs keyed by effective draft width: a pow2 ladder
        # over the pass's max draft length (0, 1, 2, 4, ..., k), mirroring
        # the admit path's prompt buckets.  A ramping fleet whose drafts
        # are still short verifies through a narrow program instead of
        # paying k wasted positions per lane per pass — and each bucket
        # compiles exactly once (the ladder is finite and shape-static),
        # so the retrace pin holds at <= len(buckets) forever
        self._verify_fns: Dict[int, Callable] = {}
        self._spec_buckets: Tuple[int, ...] = ()
        self._spec_warm: set = set()
        # banned-token carry for the temperature>0 residual resample: the
        # token rejected by last pass's accept-test, masked out of the
        # NEXT pass's bonus-token sampling (exact residual for a
        # point-mass drafter).  Host-side because spec mode reads every
        # pass synchronously anyway — it rides the one batched upload.
        self._banned = np.full((L,), -1, np.int32)
        self.spec_proposed_total = 0
        self.spec_accepted_total = 0
        self.spec_rollback_pages_total = 0
        self._spec_draft_s = 0.0
        self._spec_verify_s = 0.0
        if self._spec_k:
            self._drafter = NgramDrafter(
                n=config.spec_ngram, k=config.spec_k
            )
            ladder = [0]
            b = 1
            while b < config.spec_k:
                ladder.append(b)
                b *= 2
            ladder.append(config.spec_k)
            self._spec_buckets = tuple(ladder)
        # prefill-savings accounting (the bench's saved-ratio numerator /
        # denominator): full-page prefix tokens admitted vs those skipped
        # via cache hits and CoW group shares
        self.prefix_tokens_total = 0
        self.prefix_tokens_saved = 0
        self.prefill_tokens = 0
        reg = telemetry.get_registry()
        self._decode_meter = reg.meter("genrl.decode_tokens_per_s")
        self._prompt_meter = reg.meter("genrl.prompt_tokens_per_s")
        self._occupancy_gauge = reg.gauge("genrl.lane_occupancy")
        self._admitted_counter = reg.counter("genrl.admitted")
        self._completed_counter = reg.counter("genrl.completed")
        self._shared_counter = reg.counter("genrl.pages_shared")
        self._prefix_skipped_counter = reg.counter(
            "genrl.prefix_skipped_recurrent"
        )
        self._admit_hist = reg.histogram("genrl.admission_latency_s")
        self._spec_proposed_counter = reg.counter("genrl.spec_proposed")
        self._spec_accepted_counter = reg.counter("genrl.spec_accepted")
        self._spec_rollback_counter = reg.counter(
            "genrl.spec_rollback_pages"
        )
        self._spec_accept_gauge = reg.gauge("genrl.spec_acceptance_rate")
        reg.bind("genrl.pages", self.allocator.stats)
        if self._prefix_cache is not None:
            reg.bind("genrl.prefix", self._prefix_cache.stats)
        reg.bind(
            "genrl.continuous",
            lambda: {
                "generation": self.generation,
                "macro_steps": self.macro_steps,
                "completed": self.completed_total,
                "live_lanes": sum(l.busy for l in self._lanes),
                "pending": self._batcher.stats()["pending_lanes"],
                "in_flight": len(self._inflight),
                "shed_total": self._batcher.shed_total,
                "iter_mode": self.iter_mode,
                "spec_k": self._spec_k,
            },
        )

    # -- admission ------------------------------------------------------
    def submit(
        self,
        prompt: np.ndarray,
        prompt_length: Optional[int] = None,
        tag: Any = None,
    ) -> bool:
        """Queue one prompt for admission; False = shed (queue at
        ``max_pending``).  ``prompt``: 1-D int32 (or the right-padded
        ``[L0]`` row with an explicit true length).  ``tag`` rides the lane
        unchanged and comes back on the :class:`CompletedSequence`.
        Single prompts take the same cache-lookup admission path as
        groups (a hit still skips the cached prefix's prefill)."""
        return self.submit_group(prompt, 1, prompt_length, tag)

    def submit_group(
        self,
        prompt: np.ndarray,
        n: int,
        prompt_length: Optional[int] = None,
        tag: Any = None,
    ) -> bool:
        """Queue one prompt for ``n`` sampled completions (the GRPO group
        shape); False = shed.  The group admits atomically into ``n``
        lanes that share the prompt's KV copy-on-write: one tail prefill
        for the leader, full prompt pages shared into the other ``n-1``
        tables, and only the last partial page physically copied per
        lane.  Every member completes as its own
        :class:`CompletedSequence` carrying the same ``tag``."""
        if n < 1 or n > self.config.lanes:
            raise ValueError(
                f"group size must be in [1, lanes], got {n}"
            )
        if n * self._worst_pages > self.allocator.capacity:
            # groups admit atomically: one the pool can never cover would
            # sit queued forever (every member reserves its full worst
            # case — sharing never loosens the exhaustion guarantee)
            raise ValueError(
                f"group of {n} needs {n * self._worst_pages} worst-case "
                f"pages but the pool caps at {self.allocator.capacity}"
            )
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        m = int(prompt_length) if prompt_length is not None else len(prompt)
        if m < 1 or m > self.config.max_prompt_len:
            raise ValueError(
                f"prompt length {m} outside [1, {self.config.max_prompt_len}]"
            )
        return self._batcher.submit(
            ServingRequest(
                conn=None,
                req_id=None,
                lanes=n,
                payload={
                    "prompt": prompt[:m].copy(),
                    "len": m,
                    "n": n,
                    "tag": tag,
                },
            )
        )

    @property
    def pending(self) -> int:
        """Queued-but-unadmitted LANES (a group of n counts n)."""
        return self._batcher.stats()["pending_lanes"]

    @property
    def live_lanes(self) -> int:
        return sum(l.busy for l in self._lanes)

    @property
    def prefix_saved_ratio(self) -> float:
        """Fraction of admitted full-page prefix tokens whose prefill was
        skipped (cache hits + CoW group shares)."""
        return self.prefix_tokens_saved / max(self.prefix_tokens_total, 1)

    def _admit(self) -> None:
        """Admit queued prompts into free lanes via the batcher's
        flush-on-size-or-deadline predicate.  All table math is host-side
        numpy; the device sees one batched upload per prefill group plus
        one for the CoW fork — never a per-lane transfer."""
        free_ids = [i for i, l in enumerate(self._lanes) if not l.busy]
        if not free_ids:
            return
        if len(free_ids) < self.config.min_free_lanes and len(
            free_ids
        ) < self.config.lanes:
            # admission batching: wait for more lanes to free so the
            # prefill dispatch amortizes (a fully idle pool always admits)
            return
        # admission never over-commits the page pool: cap the flush at the
        # number of worst-case sequences the allocator can still reserve
        # (shared pages count against every holder's reservation, so the
        # cap is exact with or without the prefix cache)
        affordable = (
            self.allocator.capacity - self.allocator.reserved
        ) // self._worst_pages
        limit = min(len(free_ids), affordable)
        batch = self._batcher.poll_batch(max_lanes=limit)
        if not batch:
            return
        now = time.monotonic()
        ps = self.config.page_size
        params, gen = self._snapshot_params()
        local: Dict[int, List[Tuple]] = {}
        prefix: Dict[int, List[Tuple]] = {}
        forks: List[Tuple[int, int, int, int]] = []
        inserts: List[Tuple[np.ndarray, int, List[int]]] = []
        admitted = 0
        for req in batch:
            prompt = req.payload["prompt"]
            m = req.payload["len"]
            n = req.payload.get("n", 1)
            lane_ids = [free_ids.pop(0) for _ in range(n)]
            leader = lane_ids[0]
            # longest cached full-page prefix — capped at m-1 tokens so
            # the uncached tail always holds the token whose forward
            # produces the lane's first decode logits
            cached: List[int] = []
            use_cache = self._prefix_cache is not None and not self._lane_state
            if use_cache:
                cached = self._prefix_cache.lookup(prompt, m - 1)
            elif self._prefix_cache is not None:
                self.prefix_skipped_recurrent += 1
                self._prefix_skipped_counter.inc()
            ck = len(cached) * ps
            worst = self.allocator.pages_for_tokens(
                m + self._response_budget
            )
            full_tokens = (m // ps) * ps  # full-page prefix tokens
            ok = self.allocator.try_reserve(worst)
            assert ok, "admission cap should have prevented over-reserve"
            holder = f"lane[{leader}]"
            if cached:
                self.allocator.share(cached, holder=holder)
                self._shared_counter.inc(len(cached))
            tail_pages = self.allocator.alloc(
                self.allocator.pages_for_tokens(m) - len(cached),
                holder=holder, after=cached[-1] if cached else None,
            )
            pages = cached + tail_pages
            self._occupy(leader, req, prompt, m, pages, worst, gen, now)
            t_len = m - ck
            row = (leader, prompt, m, ck, pages)
            if ck == 0:
                P = bucket_for(m, self.config.resolved_prompt_buckets())
                local.setdefault(P, []).append(row)
            else:
                T = bucket_for(
                    t_len, self.config.resolved_prompt_buckets()
                )
                prefix.setdefault(T, []).append(row)
            self.prefix_tokens_total += full_tokens
            self.prefix_tokens_saved += min(ck, full_tokens)
            self.prefill_tokens += t_len
            self._prompt_meter.mark(t_len)
            # group members fork off the leader copy-on-write: shared full
            # prompt pages, one physical copy of the partial page
            n_full = m // ps
            partial = pages[n_full] if m % ps else None
            for member in lane_ids[1:]:
                ok = self.allocator.try_reserve(worst)
                assert ok, "admission cap should have prevented over-reserve"
                mh = f"lane[{member}]"
                mpages = list(pages[:n_full])
                if n_full:
                    self.allocator.share(mpages, holder=mh)
                    self._shared_counter.inc(n_full)
                if partial is not None:
                    # a run of the member's own: the page after the shared
                    # ones is the leader's
                    copy = self.allocator.alloc(
                        1, holder=mh, after=mpages[-1] if mpages else None
                    )[0]
                    mpages.append(copy)
                    forks.append((leader, member, partial, copy))
                else:
                    forks.append((leader, member, 0, 0))
                self._occupy(member, req, prompt, m, mpages, worst, gen, now)
                self.prefix_tokens_total += full_tokens
                self.prefix_tokens_saved += full_tokens
            admitted += n
            self._admit_hist.observe(now - req.t_enqueue)
            if use_cache and n_full:
                inserts.append((prompt, m, pages[:n_full]))
        self._admitted_counter.inc(admitted)
        for P, rows in local.items():
            self._dispatch_local_prefill(P, rows, params)
        for T, rows in prefix.items():
            self._dispatch_prefix_prefill(T, rows, params)
        if forks:
            self._dispatch_fork(forks)
        # register the freshly-written chains AFTER the prefill dispatches
        # (device programs are ordered, so any later reader through a
        # shared table sees the completed writes)
        for prompt, m, full_pages in inserts:
            self._prefix_cache.insert(prompt, m, full_pages)

    def _occupy(
        self,
        lane_id: int,
        req: ServingRequest,
        prompt: np.ndarray,
        m: int,
        pages: List[int],
        reserved: int,
        gen: int,
        now: float,
    ) -> None:
        lane = self._lanes[lane_id]
        lane.busy = True
        lane.prompt = prompt
        lane.prompt_len = m
        lane.context_len = m
        lane.pages = pages
        lane.reserved = reserved
        lane.tokens, lane.logps, lane.values = [], [], []
        lane.generation = gen
        lane.submit_time = req.t_enqueue
        lane.admit_time = now
        lane.tag = req.payload.get("tag")
        lane.admit_macro = self.macro_steps
        self._table[lane_id] = 0
        self._table[lane_id, : len(pages)] = pages
        if self._drafter is not None:
            # a recycled lane id starts a fresh draft table over the new
            # prompt, and any banned-token carry from the previous
            # occupant dies with it
            self._drafter.start(lane_id, prompt[:m])
            self._banned[lane_id] = -1

    # -- prefill dispatch ------------------------------------------------
    def _dispatch_local_prefill(
        self, P: int, rows: List[Tuple], params: Any
    ) -> None:
        """Cold prompts (no cached prefix): causal local-attention prefill
        over the compact batch, K/V written straight into the lanes'
        fresh pages — ONE batched upload, no read."""
        ps = self.config.page_size
        A = bucket_for(len(rows), self._admit_buckets)
        L = self.config.lanes
        tokens = np.full((A, P), self.config.pad_token, np.int32)
        lengths = np.ones((A,), np.int32)
        lane_ids = np.full((A,), L, np.int32)  # pad rows scatter-drop
        page_ids = np.zeros((A, P), np.int32)  # pad writes -> null page
        offsets = np.zeros((A, P), np.int32)
        for r, (lane_id, prompt, m, _ck, pages) in enumerate(rows):
            tokens[r, :m] = prompt
            lengths[r] = m
            lane_ids[r] = lane_id
            pos = np.arange(m)
            page_ids[r, :m] = np.asarray(pages, np.int32)[pos // ps]
            offsets[r, :m] = pos % ps
        fn = self._prefill_fn(("local", P, A))
        with self._dispatch_guard():
            # ONE explicit batched host->device upload per prefill dispatch
            up = _device_put((tokens, lengths, lane_ids, page_ids, offsets))
            (
                self._pools,
                self._logits_st,
                self._value_st,
                self._cl,
                self._done,
                self._resp,
            ) = fn(
                params,
                self._pools,
                self._logits_st,
                self._value_st,
                self._cl,
                self._done,
                self._resp,
                *up,
            )

    def _dispatch_prefix_prefill(
        self, T: int, rows: List[Tuple], params: Any
    ) -> None:
        """Cache-hit prompts: prefill ONLY the uncached tail.  The tail's
        K/V scatters into lane-owned pages; attention gathers the whole
        context (shared prefix + tail) through the page table — sharing
        is purely a page-table fact."""
        ps = self.config.page_size
        A = bucket_for(len(rows), self._admit_buckets)
        L = self.config.lanes
        Mp = self._pages_per_lane
        tokens = np.full((A, T), self.config.pad_token, np.int32)
        tail_lengths = np.ones((A,), np.int32)
        lane_ids = np.full((A,), L, np.int32)
        page_ids = np.zeros((A, T), np.int32)
        offsets = np.zeros((A, T), np.int32)
        table = np.zeros((A, Mp), np.int32)
        starts = np.zeros((A,), np.int32)
        for r, (lane_id, prompt, m, ck, pages) in enumerate(rows):
            t_len = m - ck
            tokens[r, :t_len] = prompt[ck:m]
            tail_lengths[r] = t_len
            lane_ids[r] = lane_id
            gpos = ck + np.arange(t_len)
            page_ids[r, :t_len] = np.asarray(pages, np.int32)[gpos // ps]
            offsets[r, :t_len] = gpos % ps
            table[r, : len(pages)] = pages
            starts[r] = ck
        fn = self._prefill_fn(("prefix", T, A))
        with self._dispatch_guard():
            up = _device_put(
                (tokens, tail_lengths, lane_ids, page_ids, offsets,
                 table, starts)
            )
            (
                self._pools,
                self._logits_st,
                self._value_st,
                self._cl,
                self._done,
                self._resp,
            ) = fn(
                params,
                self._pools,
                self._logits_st,
                self._value_st,
                self._cl,
                self._done,
                self._resp,
                *up,
            )

    def _dispatch_fork(self, forks: List[Tuple[int, int, int, int]]) -> None:
        """One jitted page-copy + lane-state fork for EVERY group member
        admitted this cycle: copies the leader's partial prompt page into
        the member's private page and replicates the leader's post-prefill
        decode carry — one small upload, no read."""
        F = bucket_for(len(forks), self._admit_buckets)
        L = self.config.lanes
        src_lane = np.zeros((F,), np.int32)
        dst_lane = np.full((F,), L, np.int32)  # pad rows scatter-drop
        src_page = np.zeros((F,), np.int32)  # pad copies null -> null
        dst_page = np.zeros((F,), np.int32)
        for i, (sl, dl, sp, dp) in enumerate(forks):
            src_lane[i] = sl
            dst_lane[i] = dl
            src_page[i] = sp
            dst_page[i] = dp
        fn = self._fork_fn(F)
        if self._lane_state:
            self.state_forks += len(forks)
        with self._dispatch_guard():
            up = _device_put((src_lane, dst_lane, src_page, dst_page))
            (
                self._pools,
                self._logits_st,
                self._value_st,
                self._cl,
                self._done,
                self._resp,
            ) = fn(
                self._pools,
                self._logits_st,
                self._value_st,
                self._cl,
                self._done,
                self._resp,
                *up,
            )

    # -- program construction -------------------------------------------
    def _prefill_fn(self, key: Tuple) -> Callable:
        fn = self._prefill_fns.get(key)
        if fn is None:
            kind, a, b = key
            fn = (
                self._build_prefill(a, b)
                if kind == "local"
                else self._build_prefix_prefill(a, b)
            )
            self._prefill_fns[key] = fn
        return fn

    def _fork_fn(self, F: int) -> Callable:
        fn = self._fork_fns.get(F)
        if fn is None:
            fn = self._build_fork(F)
            self._fork_fns[F] = fn
        return fn

    def _build_prefill(self, P: int, A: int) -> Callable:
        """Prefill ``A`` admitted prompts at bucket ``P``: causal forward
        over the compact (right-padded) prompts, K/V written straight into
        the newly-allocated pages, last-position logits/value + cursor +
        flags scattered into the lane state — all device-side, no read."""
        model = self.model
        lane_state = self._lane_state

        def prefill(
            params, pools, logits_st, value_st, cl, done, resp,
            tokens, lengths, lane_ids, page_ids, page_offsets,
        ):
            self._prefill_traces += 1
            positions = jnp.broadcast_to(jnp.arange(P), (A, P))
            mask = prompt_attention_mask(lengths, P)
            # what a lane carries leaves the prompt at its TRUE length
            # (the mask's diagonal says which tokens are real) and is
            # written to the admitted lanes' rows; pad rows drop
            state = dict(state_lanes=lane_ids) if lane_state else {}
            out, pools = model.apply(
                params,
                tokens,
                positions=positions,
                attn_mask=mask,
                paged_cache=pools,
                page_ids=page_ids,
                page_offsets=page_offsets,
                **state,
            )
            rows = jnp.arange(A)
            last = lengths - 1
            logits_last = out.policy_logits[rows, last]
            value_last = out.baseline[rows, last]
            # pad rows carry lane_id == lanes: out-of-bounds scatters drop
            logits_st = logits_st.at[lane_ids].set(logits_last, mode="drop")
            value_st = value_st.at[lane_ids].set(value_last, mode="drop")
            cl = cl.at[lane_ids].set(lengths, mode="drop")
            done = done.at[lane_ids].set(False, mode="drop")
            resp = resp.at[lane_ids].set(0, mode="drop")
            return pools, logits_st, value_st, cl, done, resp

        return jax.jit(prefill, donate_argnums=(1, 2, 3, 4, 5, 6))

    def _build_prefix_prefill(self, T: int, A: int) -> Callable:
        """Chunked tail prefill over a shared cached prefix: the ``T``
        tail tokens of ``A`` lanes scatter K/V into lane-owned pages and
        attend through the page table (cached prefix + tail) with a
        causal-from-start mask; last-position logits/value + cursor
        scattered exactly like the local prefill."""
        model = self.model

        def prefix_prefill(
            params, pools, logits_st, value_st, cl, done, resp,
            tokens, tail_lengths, lane_ids, page_ids, page_offsets,
            table, starts,
        ):
            self._prefill_traces += 1
            positions = jnp.clip(
                starts[:, None] + jnp.arange(T)[None, :],
                0,
                model.max_len - 1,
            )
            out, pools = model.apply(
                params,
                tokens,
                positions=positions,
                paged_cache=pools,
                page_ids=page_ids,
                page_offsets=page_offsets,
                page_table=table,
                prefix_starts=starts,
            )
            rows = jnp.arange(A)
            last = tail_lengths - 1
            logits_last = out.policy_logits[rows, last]
            value_last = out.baseline[rows, last]
            logits_st = logits_st.at[lane_ids].set(logits_last, mode="drop")
            value_st = value_st.at[lane_ids].set(value_last, mode="drop")
            cl = cl.at[lane_ids].set(starts + tail_lengths, mode="drop")
            done = done.at[lane_ids].set(False, mode="drop")
            resp = resp.at[lane_ids].set(0, mode="drop")
            return pools, logits_st, value_st, cl, done, resp

        return jax.jit(prefix_prefill, donate_argnums=(1, 2, 3, 4, 5, 6))

    def _build_fork(self, F: int) -> Callable:
        """The CoW fork program at admit bucket ``F``: batched pool-page
        copy (``pools[dst] = pools[src]`` per layer — only partial prompt
        pages ever ride here) plus leader -> member lane-state
        replication, the rows a lane carries of a layer among it
        (:func:`fork_cache`).  Pad rows copy null -> null and
        scatter-drop."""

        def fork(
            pools, logits_st, value_st, cl, done, resp,
            src_lane, dst_lane, src_page, dst_page,
        ):
            self._fork_traces += 1
            pools = fork_cache(pools, src_page, dst_page, src_lane, dst_lane)
            logits_st = logits_st.at[dst_lane].set(
                logits_st[src_lane], mode="drop"
            )
            value_st = value_st.at[dst_lane].set(
                value_st[src_lane], mode="drop"
            )
            cl = cl.at[dst_lane].set(cl[src_lane], mode="drop")
            done = done.at[dst_lane].set(done[src_lane], mode="drop")
            resp = resp.at[dst_lane].set(resp[src_lane], mode="drop")
            return pools, logits_st, value_st, cl, done, resp

        return jax.jit(fork, donate_argnums=(0, 1, 2, 3, 4, 5))

    def _build_decode(self) -> Callable:
        """The ONE macro-step program: ``steps_per_macro`` fused substeps
        of sample -> latch -> paged write -> paged attention -> carry."""
        model = self.model
        cfg = self.config
        ps = cfg.page_size
        steps = cfg.steps_per_macro
        budget = self._response_budget
        use_scan = self.iter_mode == "scan"
        routed = self._routed

        def substep(params, table, carry, _t):
            pools, logits, value, cl, done, resp, key = carry
            with jax.named_scope(_SCOPE_SAMPLE):
                key, sub = jax.random.split(key)
                adj = adjust_logits(
                    logits, cfg.temperature, cfg.top_k, cfg.vocab_size
                )
                token = sample_tokens(sub, adj, cfg.temperature)
                logp = jnp.take_along_axis(
                    jax.nn.log_softmax(adj, axis=-1), token[:, None], axis=-1
                )[:, 0]
            alive = jnp.logical_not(done)
            resp2 = resp + alive.astype(jnp.int32)
            finished = resp2 >= budget
            if cfg.eos_token >= 0:
                finished = jnp.logical_or(finished, token == cfg.eos_token)
            done2 = jnp.logical_or(done, finished)
            emit = jnp.where(
                alive, token, jnp.int32(max(cfg.eos_token, cfg.pad_token))
            ).astype(jnp.int32)
            out_t = (emit, logp, value, alive.astype(jnp.float32))
            # feed the sampled token back through the paged model: write
            # K/V at flat position cl (dead lanes route to the null page)
            page_idx = jnp.take_along_axis(
                table, (cl // ps)[:, None], axis=1
            )[:, 0]
            page_idx = jnp.where(alive, page_idx, 0)
            offs = jnp.where(alive, cl % ps, 0)
            att_len = jnp.where(alive, cl + 1, 1)
            call = dict(
                positions=cl[:, None],
                paged_cache=pools,
                page_ids=page_idx[:, None],
                page_offsets=offs[:, None],
                page_table=table,
                attn_lengths=att_len,
            )
            feed = token[:, None].astype(jnp.int32)
            if routed:
                # the live lanes' per-expert token counts of every layer
                # ride out with the substep's tokens: one more row of the
                # macro-step's one batched read
                (out, pools), sown = model.apply(
                    params, feed, mutable=["intermediates"], **call
                )
                out_t += (
                    router_balance(
                        sown["intermediates"], alive[:, None]
                    ).counts,
                )
            else:
                out, pools = model.apply(params, feed, **call)
            cl2 = cl + alive.astype(jnp.int32)
            new_carry = (
                pools,
                out.policy_logits[:, 0],
                out.baseline[:, 0],
                cl2,
                done2,
                resp2,
                key,
            )
            return new_carry, out_t

        def decode(params, pools, logits_st, value_st, cl, done, resp,
                   table, key):
            self._decode_traces += 1
            carry = (pools, logits_st, value_st, cl, done, resp, key)
            if use_scan:
                carry, outs = jax.lax.scan(
                    lambda c, t: substep(params, table, c, t),
                    carry,
                    jnp.arange(steps),
                )
                toks, logps, values, alive = (
                    jnp.swapaxes(o, 0, 1) for o in outs[:4]
                )
            else:
                cols = []
                for t in range(steps):
                    carry, out_t = substep(params, table, carry, t)
                    cols.append(out_t)
                outs = tuple(
                    jnp.stack([c[i] for c in cols]) for i in range(len(cols[0]))
                )
                toks, logps, values, alive = (
                    jnp.swapaxes(o, 0, 1) for o in outs[:4]
                )
            pools, logits_st, value_st, cl, done, resp, _key = carry
            outputs = {
                "tokens": toks.astype(jnp.int32),
                "logp": logps.astype(jnp.float32),
                "value": values.astype(jnp.float32),
                "mask": alive,
                "cl": cl,
                "done": done,
                "resp": resp,
            }
            if routed:
                outputs["expert_counts"] = outs[4]  # [steps, layers, E]
            return pools, logits_st, value_st, cl, done, resp, outputs

        return jax.jit(decode, donate_argnums=(1, 2, 3, 4, 5, 6))

    def _build_verify(self, k_eff: int) -> Callable:
        """One speculative verify program at draft width ``k_eff``
        (ISSUE 16): sample the bonus token from the carried logits, feed
        ``[t0, d1..dk]`` through the shared-table tail-prefill path in a
        single forward, accept the longest draft prefix, and carry the
        state at the last accepted position.

        Lane count and ``k_eff`` are both static, so each ladder bucket
        compiles exactly once (``_verify_traces`` pins the total at
        <= len(buckets)); ``_spec_step`` routes every pass to the
        smallest bucket that fits its longest draft, so short-draft
        passes — the ramp, and lanes the AIMD cap has clamped — never
        pay ``spec_k`` computed positions.  ``k_eff`` may be 0: the
        bonus-only program, one position per lane, the spec-mode twin of
        a single decode substep.  The carried-logits
        invariant survives untouched: ``logits_st`` is always the
        distribution for the token at cursor ``cl``, computed from an
        all-accepted context — output slot ``a`` qualifies because slots
        ``0..a`` fed exactly the emitted tokens.  K/V written for
        rejected slots is garbage past the cursor: never attended (the
        tail path masks ``pos <= qpos``) and overwritten by the next
        pass's writes, so the device needs no rollback — rollback is
        purely the host-side page rewind.

        Distribution correctness at temperature > 0 is the standard
        speculative-sampling argument for a point-mass (deterministic)
        drafter: draft ``d_j`` is accepted with probability
        ``pi_j(d_j)``; on an accept-test rejection the replacement token
        must come from the residual ``pi(x) / (1 - pi(d))`` over
        ``x != d``, which is exactly next pass's bonus sampling with
        ``d`` masked out (the ``banned`` carry).  The STORED behavior
        logp is always from the unmasked distribution — marginally the
        output token is ``pi``-distributed, which is what the learner's
        ratios need.  At temperature 0 both rules collapse to greedy
        argmax equality and ``banned`` stays -1.
        """
        model = self.model
        cfg = self.config
        k = k_eff
        T = k + 1
        V = cfg.vocab_size
        budget = self._response_budget
        greedy = cfg.temperature == 0.0
        pad = jnp.int32(max(cfg.eos_token, cfg.pad_token))

        def verify(
            params, pools, logits_st, value_st, cl, done, resp,
            drafts, draft_len, page_ids, page_offsets, table, banned, key,
        ):
            self._verify_traces += 1
            L = cl.shape[0]
            rows = jnp.arange(L)
            alive = jnp.logical_not(done)
            k0, kacc = jax.random.split(key)
            # bonus token: sampled from the carried logits exactly like a
            # decode substep — except at temperature > 0 a banned token
            # (last pass's accept-test rejection) is masked from the
            # SAMPLING distribution only (the residual rule)
            adj0 = adjust_logits(
                logits_st, cfg.temperature, cfg.top_k, V
            )
            if greedy:
                samp0 = adj0
            else:
                ban_pen = jnp.zeros((L, V), jnp.float32)
                ban_pen = ban_pen.at[rows, jnp.clip(banned, 0, V - 1)].set(
                    jnp.where(banned >= 0, -1e9, 0.0)
                )
                samp0 = adj0 + ban_pen
            t0 = sample_tokens(k0, samp0, cfg.temperature)
            logp0 = jnp.take_along_axis(
                jax.nn.log_softmax(adj0, axis=-1), t0[:, None], axis=-1
            )[:, 0]
            # one forward over [t0, d1..dk] at positions cl..cl+k through
            # the shared-table tail path; slot j's output is the policy
            # distribution for position cl+j+1
            X = jnp.concatenate([t0[:, None], drafts], axis=1)
            positions = jnp.clip(
                cl[:, None] + jnp.arange(T)[None, :], 0, model.max_len - 1
            )
            out, pools = model.apply(
                params,
                X,
                positions=positions,
                paged_cache=pools,
                page_ids=page_ids,
                page_offsets=page_offsets,
                page_table=table,
                prefix_starts=cl,
            )
            o_logits = out.policy_logits  # [L, T, V]
            o_value = out.baseline  # [L, T]
            adj = adjust_logits(
                o_logits.reshape(L * T, V), cfg.temperature, cfg.top_k, V
            ).reshape(L, T, V)
            # accept test per draft j (against the distribution AFTER slot
            # j-1): greedy equality at temperature 0, accept-with-prob
            # pi(d) otherwise; gated on the host's draft_len clamp and on
            # no EOS having been emitted earlier in this pass
            prev = adj[:, :k]
            logp_d = jnp.take_along_axis(
                jax.nn.log_softmax(prev, axis=-1),
                drafts[:, :, None], axis=-1,
            )[:, :, 0]
            if greedy:
                accept = drafts == jnp.argmax(prev, axis=-1)
            else:
                u = jax.random.uniform(
                    kacc, (L, k), minval=1e-20, maxval=1.0
                )
                accept = jnp.log(u) < logp_d
            valid = jnp.arange(1, k + 1)[None, :] <= draft_len[:, None]
            ok = accept & valid
            if cfg.eos_token >= 0:
                ok = ok & (X[:, :k] != cfg.eos_token)
            chain = jnp.cumprod(ok.astype(jnp.int32), axis=1)
            a = chain.sum(axis=1)  # accepted drafts per lane, in [0, k]
            # emitted stream: t0 plus the accepted prefix — the outputs
            # mirror the decode macro's (prefix-contiguous mask), so the
            # host harvest path is shared verbatim
            slot = jnp.arange(T)[None, :]
            mask = (slot <= a[:, None]) & alive[:, None]
            emit = jnp.where(mask, X, pad).astype(jnp.int32)
            logps = jnp.concatenate([logp0[:, None], logp_d], axis=1)
            values = jnp.concatenate(
                [value_st[:, None], o_value[:, :k]], axis=1
            )
            n_emit = (1 + a) * alive.astype(jnp.int32)
            resp2 = resp + n_emit
            cl2 = cl + n_emit
            last_tok = jnp.take_along_axis(X, a[:, None], axis=1)[:, 0]
            finished = resp2 >= budget
            if cfg.eos_token >= 0:
                finished = jnp.logical_or(
                    finished, last_tok == cfg.eos_token
                )
            done2 = jnp.logical_or(done, alive & finished)
            # carry the state at the LAST ACCEPTED slot: its output is the
            # distribution for the token at the new cursor
            new_logits = jnp.take_along_axis(
                o_logits, a[:, None, None], axis=1
            )[:, 0]
            new_value = jnp.take_along_axis(o_value, a[:, None], axis=1)[
                :, 0
            ]
            logits_st2 = jnp.where(alive[:, None], new_logits, logits_st)
            value_st2 = jnp.where(alive, new_value, value_st)
            if greedy or k == 0:
                # no draft positions -> nothing the accept test could
                # have rejected; the residual carry stays clear
                banned2 = jnp.full((L,), -1, jnp.int32)
            else:
                # ban only on a genuine accept-test rejection (not mere
                # draft/budget exhaustion) of a still-live lane
                j1 = jnp.clip(a, 0, k - 1)
                hit = jnp.take_along_axis(accept, j1[:, None], axis=1)[
                    :, 0
                ]
                d1 = jnp.take_along_axis(drafts, j1[:, None], axis=1)[
                    :, 0
                ]
                rej = (
                    (a < k)
                    & jnp.take_along_axis(valid, j1[:, None], axis=1)[:, 0]
                    & jnp.logical_not(hit)
                    & alive
                    & jnp.logical_not(done2)
                )
                if cfg.eos_token >= 0:
                    no_eos = jnp.take_along_axis(
                        X[:, :k] != cfg.eos_token, j1[:, None], axis=1
                    )[:, 0]
                    rej = rej & no_eos
                banned2 = jnp.where(rej, d1, -1).astype(jnp.int32)
            outputs = {
                "tokens": emit,
                "logp": logps.astype(jnp.float32),
                "value": values.astype(jnp.float32),
                "mask": mask.astype(jnp.float32),
                "cl": cl2,
                "done": done2,
                "resp": resp2,
                "banned": banned2,
            }
            return (
                pools, logits_st2, value_st2, cl2, done2, resp2, outputs
            )

        return jax.jit(verify, donate_argnums=(1, 2, 3, 4, 5, 6))

    def _place(self, snapshot: Any) -> Any:
        """The train-layout -> infer-layout step of a param push: gather a
        (possibly dp×mp-sharded) learner tree onto the engine's device.  A
        sharded tree would make the decode program SPMD, and its Mosaic
        paged-attention kernel cannot be partitioned."""
        return jax.device_put(snapshot, self._device)

    def _copy_snapshot(self, params: Any) -> Tuple[Any, int, bool]:
        """A push's snapshot copy, written OVER the snapshot it retires
        when the incoming tree already lives whole on the engine's device
        and matches the current snapshot leaf for leaf (structure, shapes,
        dtypes): one program takes the old snapshot as a donated operand,
        so its outputs land in those buffers.  No second copy of the
        weights exists at any moment and ``_place`` has nothing to move.
        Anything else (the first snapshot, a mesh learner's tree that
        ``device_put`` must gather, numpy leaves, a changed tree, the
        engine's own snapshot pushed back) takes the plane's route.

        Safe because ONE thread both steps and pushes an engine (the
        trainer's round, a disagg host's loop): macro-steps already
        enqueued keep their operands until they have run (the runtime
        orders a donated buffer's reuse behind its readers), and
        ``push_params`` rebinds ``_params`` before it returns.  The
        servers, read by other threads while a push arrives, never take
        this path (``ParamSnapshotPlane._copy_snapshot``)."""
        old = self._params
        if old is not None and self._lives_here_like(params, old):
            return self._copy_over(params, old), 1, True
        return super()._copy_snapshot(params)

    def _lives_here_like(self, params: Any, old: Any) -> bool:
        """Device arrays whole on the engine's device, ``old``'s structure,
        shapes and dtypes, and none of them ``old``'s own (a buffer cannot
        be both read and donated by one call)."""
        new_leaves, new_def = jax.tree_util.tree_flatten(params)
        old_leaves, old_def = jax.tree_util.tree_flatten(old)
        here = {self._device}
        return new_def == old_def and all(
            isinstance(n, jax.Array)
            and n is not o
            and n.shape == o.shape
            and n.dtype == o.dtype
            and n.sharding.device_set == here
            for n, o in zip(new_leaves, old_leaves)
        )

    def lower_decode(self):
        """Lower the decode macro-step against the engine's live state —
        nothing runs and nothing is donated.  The returned
        ``jax.stages.Lowered`` is how a caller reads what the program
        actually contains (``chip_smoke.py`` looks for the Mosaic custom
        call of the paged-attention kernel in its text)."""
        params, _gen = self._snapshot_params()
        traces = self._decode_traces
        lowered = self._decode_fn.lower(
            params, self._pools, self._logits_st, self._value_st,
            self._cl, self._done, self._resp, self._table, self._key,
        )
        # lowering re-traces the body; the counter pins DISPATCH retraces
        self._decode_traces = traces
        return lowered

    # -- param plane -----------------------------------------------------
    def push_params(
        self,
        params: Any,
        learner_step: Optional[int] = None,
        quantize: Optional[str] = None,
    ) -> int:
        """Publish fresh params AND flush the prefix cache: cached K/V was
        computed under the previous generation, and reusing it would break
        the temperature-0 token-identity contract.  Live lanes keep their
        shared pages (their own refs) until harvest — only the cache's
        index drops.

        A full-precision push from the engine's own device overwrites the
        retired snapshot's buffers (:meth:`_copy_snapshot`;
        ``last_push["in_place"]``), so a tree obtained from
        ``_snapshot_params()`` does NOT outlive the next push: its arrays
        are deleted.  Call it from the thread that steps the engine."""
        with tracing.span("genrl.push_params", kind="genrl") as span:
            gen = super().push_params(params, learner_step, quantize)
            if self._prefix_cache is not None:
                self._prefix_cache.flush()
            span.set(**self.last_push)
        return gen

    # -- the macro-step --------------------------------------------------
    def _ensure_pages(self) -> None:
        """Pre-extend each live lane's page list to cover the in-flight
        decode horizon's worst case (all allocation stays within the
        lane's admission-time reservation, so it can never fail
        mid-flight).  With K macros in flight the host's ``context_len``
        is stale by up to K-1 macros, so the horizon covers the pending
        dispatches plus the one about to go out."""
        ps = self.config.page_size
        if self._spec_k:
            # spec mode is synchronous: the horizon is one verify pass's
            # worst case — the bonus token plus k accepted drafts
            steps = self._spec_k + 1
        else:
            steps = self.config.steps_per_macro * (len(self._inflight) + 1)
        for lane_id, lane in enumerate(self._lanes):
            if not lane.busy:
                continue
            horizon = min(
                lane.context_len + steps,
                lane.prompt_len + self._response_budget,
            )
            need = min(
                self.allocator.pages_for_tokens(horizon), lane.reserved
            )
            delta = need - len(lane.pages)
            if delta > 0:
                new_pages = self.allocator.alloc(
                    delta, holder=f"lane[{lane_id}]",
                    after=lane.pages[-1] if lane.pages else None,
                )
                start = len(lane.pages)
                lane.pages.extend(new_pages)
                self._table[
                    lane_id, start : start + len(new_pages)
                ] = new_pages

    def step(self) -> List[CompletedSequence]:
        """One engine cycle: admit -> dispatch the next decode macro-step
        (ONE upload) -> read the OLDEST in-flight macro once
        ``steps_in_flight`` are pending (ONE batched read, lagging
        dispatch by K-1) -> harvest.  Returns the sequences that
        completed in the macro(s) read this cycle.

        With ``spec_k > 0`` the cycle is the draft -> verify -> rewind
        loop instead (:meth:`_spec_step`) — same admission, same harvest,
        same one-upload-one-read transfer discipline, but synchronous by
        construction (next pass's drafts need this pass's tokens)."""
        # ONE live span per cycle and per phase of it -- never per token,
        # never per lane; host stamps only (graftlint JG001)
        cycle, kind = (
            (self._spec_step, "genrl-spec")
            if self._spec_k
            else (self._plain_step, "genrl")
        )
        with tracing.span("genrl.macro_step", kind=kind) as span:
            return cycle(span)

    def _plain_step(self, step_span) -> List[CompletedSequence]:
        with tracing.span("genrl.admit", kind="genrl"):
            self._admit()
        dispatched = False
        occ = 0.0
        if self.live_lanes > 0:
            self._ensure_pages()
            params, gen = self._snapshot_params()
            occ = self.live_lanes / self.config.lanes
            self._occupancy_gauge.set(occ)
            self._occupancy_sum += occ
            guard = steady_state_guard() if self._warm else nullcontext()
            with guard:
                # ``generation``: the snapshot this macro-step decodes with,
                # so a trace shows the first one on freshly pushed weights
                with self._dispatch_guard(), tracing.span(
                    "genrl.dispatch", kind="genrl", generation=gen,
                    pages_per_copy=self._note_table(), **self._dispatch_attrs,
                ):
                    self._key, sub = jax.random.split(self._key)
                    # ONE explicit batched host->device upload per macro
                    table_dev = _device_put(self._table)
                    (
                        self._pools,
                        self._logits_st,
                        self._value_st,
                        self._cl,
                        self._done,
                        self._resp,
                        outputs,
                    ) = self._decode_fn(
                        params,
                        self._pools,
                        self._logits_st,
                        self._value_st,
                        self._cl,
                        self._done,
                        self._resp,
                        table_dev,
                        sub,
                    )
            self._inflight.append((self.macro_steps, outputs))
            self.macro_steps += 1
            self._warm = True
            dispatched = True
        completions: List[CompletedSequence] = []
        # read the oldest in-flight macro once K are pending (reads lag
        # dispatch by K-1); with nothing dispatched this cycle, drain —
        # outputs are loop OUTPUTS (never donated), so holding device
        # references to K of them while later macros run is safe by
        # construction (the MetricsPipeline argument)
        while self._inflight and (
            len(self._inflight) >= self.config.steps_in_flight
            or not dispatched
        ):
            macro_idx, outputs = self._inflight.popleft()
            guard = steady_state_guard() if self._warm else nullcontext()
            with guard, tracing.span("genrl.read", kind="genrl"):
                # ... and ONE explicit batched device->host read
                host = _device_get(outputs)
            completions.extend(self._harvest(host, macro_idx))
            if self._routed:
                self._note_expert_counts(host["expert_counts"])
            if dispatched:
                break  # steady state: exactly one read per step
        step_span.set(
            completed=len(completions), live_lanes=self.live_lanes,
            occupancy=round(occ, 4), in_flight=len(self._inflight),
        )
        return completions

    def _spec_step(self, step_span) -> List[CompletedSequence]:
        """One speculative cycle (ISSUE 16): admit -> draft (host-side
        n-gram lookups, jax-free) -> ONE batched upload + verify dispatch
        -> ONE batched read -> feed the drafter, harvest, and rewind the
        page cursor of every rejected tail.

        The transfer shape matches the plain macro-step exactly — one
        upload, one read — so graftlint's decode discipline holds; the
        read is synchronous (``steps_in_flight`` is ignored here) because
        pass ``m+1``'s drafts are functions of pass ``m``'s emitted
        tokens."""
        with tracing.span("genrl.admit", kind="genrl-spec"):
            self._admit()
        completions: List[CompletedSequence] = []
        occ = 0.0
        draft_s = verify_s = 0.0
        if self.live_lanes > 0:
            self._ensure_pages()
            params, _gen = self._snapshot_params()
            occ = self.live_lanes / self.config.lanes
            self._occupancy_gauge.set(occ)
            self._occupancy_sum += occ
            cfg = self.config
            ps = cfg.page_size
            k = self._spec_k
            L = cfg.lanes
            # -- draft: per-lane n-gram proposals + page routing, all
            # host numpy (the gap between read and dispatch the device
            # decodes through in plain mode is spent drafting here)
            t_draft0 = time.monotonic()
            with tracing.span("seq.draft", kind="genrl-spec"):
                drafts = np.zeros((L, k), np.int32)
                draft_len = np.zeros((L,), np.int32)
                busy = np.zeros((L,), bool)
                cl_host = np.zeros((L,), np.int64)
                proposed = 0
                for lane_id, lane in enumerate(self._lanes):
                    if not lane.busy:
                        continue
                    busy[lane_id] = True
                    cl_host[lane_id] = lane.context_len
                    # the bonus token always fits (a live lane has budget
                    # room by the done latch); drafts are clamped so the
                    # whole accepted run stays within the response budget
                    room = (
                        lane.prompt_len
                        + self._response_budget
                        - lane.context_len
                        - 1
                    )
                    if room > 0:
                        d = self._drafter.propose(lane_id)
                        if d is not None:
                            dl = min(len(d), room, k)
                            if dl:
                                drafts[lane_id, :dl] = d[:dl]
                                draft_len[lane_id] = dl
                                proposed += dl
                # bucket the pass to the smallest ladder width that fits its
                # longest draft: a ramp pass whose best proposal is 1 token
                # verifies through the 2-wide program, not the k-wide one —
                # on a compute-bound substrate the unused slots of a too-wide
                # program are pure wall-clock waste.  Each bucket is its own
                # compiled program (shape-static), so this never retraces
                dmax = int(draft_len.max())
                kb = next(b for b in self._spec_buckets if b >= dmax)
                fn = self._verify_fns.get(kb)
                if fn is None:
                    fn = self._verify_fns[kb] = self._build_verify(kb)
                T = kb + 1
                drafts = drafts[:, :kb]
                # slot j writes K/V at flat position cl + j; slots past the
                # draft length (and the whole row of a dead lane) route to
                # the null page.  ``self._table[lane, pos // ps]`` already
                # IS the padded page matrix (0 where unheld), so routing is
                # one vectorized [L, T] gather — no per-lane numpy traffic
                # in the host gap the device sits idle through
                slot = np.arange(T)
                gpos = cl_host[:, None] + slot[None, :]
                page_idx = np.minimum(gpos // ps, self._table.shape[1] - 1)
                writable = (slot[None, :] <= draft_len[:, None]) & busy[:, None]
                rows = np.arange(L)[:, None]
                page_ids = np.where(
                    writable, self._table[rows, page_idx], 0
                ).astype(np.int32)
                offsets = np.where(writable, gpos % ps, 0).astype(np.int32)
            draft_s = time.monotonic() - t_draft0
            # -- verify: ONE batched upload, ONE dispatch, ONE read
            t_verify0 = time.monotonic()
            # per-BUCKET warmth: a first dispatch at a new ladder width
            # compiles (materializing host constants), which the
            # steady-state transfer guard would flag — every later pass
            # through that bucket runs guarded
            guard = (
                steady_state_guard()
                if kb in self._spec_warm
                else nullcontext()
            )
            with tracing.span("seq.verify", kind="genrl-spec"):
                with guard:
                    with self._dispatch_guard():
                        self._key, sub = jax.random.split(self._key)
                        up = _device_put(
                            (drafts, draft_len, page_ids, offsets,
                             self._table, self._banned)
                        )
                        (
                            self._pools,
                            self._logits_st,
                            self._value_st,
                            self._cl,
                            self._done,
                            self._resp,
                            outputs,
                        ) = fn(
                            params,
                            self._pools,
                            self._logits_st,
                            self._value_st,
                            self._cl,
                            self._done,
                            self._resp,
                            *up,
                            sub,
                        )
                    with tracing.span("genrl.read", kind="genrl-spec"):
                        host = _device_get(outputs)
            verify_s = time.monotonic() - t_verify0
            macro_idx = self.macro_steps
            self.macro_steps += 1
            self._warm = True
            self._spec_warm.add(kb)
            self._banned = np.array(host["banned"], np.int32)  # writable copy
            # -- drafter maintenance from the already-read outputs (no
            # extra transfer): live lanes learn their emitted tokens,
            # finished lanes drop their tables before the id recycles
            mask = np.asarray(host["mask"], np.float32)
            tokens = np.asarray(host["tokens"], np.int32)
            done = np.asarray(host["done"], bool)
            accepted = 0
            for lane_id, lane in enumerate(self._lanes):
                if not lane.busy:
                    continue
                count = int(mask[lane_id].sum())
                accepted += max(count - 1, 0)
                self._drafter.observe(
                    lane_id, int(draft_len[lane_id]), max(count - 1, 0)
                )
                if count:
                    self._drafter.extend(
                        lane_id, tokens[lane_id, :count]
                    )
                if done[lane_id]:
                    self._drafter.release(lane_id)
            completions = self._harvest(host, macro_idx)
            # -- page-cursor rewind: every live lane frees the whole
            # pages past its post-verify cursor (the rejected tail's
            # pre-extension) — refcount decrements only, so CoW-shared
            # pages another holder still needs are never touched
            freed = 0
            for lane_id, lane in enumerate(self._lanes):
                if not lane.busy:
                    continue
                keep = self.allocator.pages_for_tokens(lane.context_len)
                n = rewind_pages(
                    self.allocator, lane.pages, keep,
                    holder=f"lane[{lane_id}]",
                )
                if n:
                    self._table[lane_id, keep : keep + n] = 0
                    freed += n
            self.spec_proposed_total += proposed
            self.spec_accepted_total += accepted
            self.spec_rollback_pages_total += freed
            self._spec_draft_s += draft_s
            self._spec_verify_s += verify_s
            if proposed:
                self._spec_proposed_counter.inc(proposed)
            if accepted:
                self._spec_accepted_counter.inc(accepted)
            if freed:
                self._spec_rollback_counter.inc(freed)
            self._spec_accept_gauge.set(self.spec_acceptance_rate)
        step_span.set(
            completed=len(completions), live_lanes=self.live_lanes,
            occupancy=round(occ, 4),
            acceptance_rate=round(self.spec_acceptance_rate, 4),
        )
        return completions

    @property
    def spec_acceptance_rate(self) -> float:
        """Fraction of proposed draft tokens the verify pass accepted."""
        return self.spec_accepted_total / max(self.spec_proposed_total, 1)

    def spec_timers(self) -> Optional[Tuple[float, float]]:
        """Cumulative host (draft_s, verify_s) across all spec passes, or
        None with speculation compiled out — the disagg host's seq.draft /
        seq.verify trace edges are deltas of this."""
        if not self._spec_k:
            return None
        return (self._spec_draft_s, self._spec_verify_s)

    def _note_expert_counts(self, counts: np.ndarray) -> None:
        """Fold one macro-step's ``[substeps, layers, E]`` expert token
        counts into the lifetime counters ``stats()`` exposes.  A substep
        in which no lane was alive routed nothing and is not counted."""
        counts = np.asarray(counts, np.int64)
        live = counts.sum(axis=(1, 2)) > 0
        self._expert_tokens += counts.sum(axis=0)
        self._expert_hits += int((counts[live][:, :, self._held] > 0).sum())
        self._expert_substeps += int(live.sum()) * counts.shape[1]

    def _note_table(self) -> float:
        """Live pages over copies for the table about to be uploaded (the
        kernels' rule, reckoned on the host from the host's context
        lengths), added to the lifetime sums; 1.0 is a page a copy."""
        live = np.array(
            [max(1, -(-l.context_len // self.config.page_size)) * l.busy for l in self._lanes]
        )
        pages = int(live.sum())
        copies = table_copies(self._table, live, *self._copy_rule)
        self._table_pages += pages
        self._table_copies += copies
        return round(pages / max(copies, 1), 3)

    def stats(self) -> Dict[str, Any]:
        """Engine-lifetime counters, batched from host state that already
        crossed the device boundary — reading this never adds a
        transfer."""
        held_picks = int(self._expert_tokens[:, self._held].sum())
        routed_picks = int(self._expert_tokens[:, : self._experts].sum())
        return {
            "macro_steps": self.macro_steps,
            "completed": self.completed_total,
            "live_lanes": self.live_lanes,
            "mean_occupancy": self.mean_occupancy,
            "prefill_tokens": self.prefill_tokens,
            "prefix_saved_ratio": self.prefix_saved_ratio,
            "spec_k": self._spec_k,
            "spec_proposed": self.spec_proposed_total,
            "spec_accepted": self.spec_accepted_total,
            "spec_rollback_pages": self.spec_rollback_pages_total,
            "spec_acceptance_rate": self.spec_acceptance_rate,
            "spec_draft_s": self._spec_draft_s,
            "spec_verify_s": self._spec_verify_s,
            # routed-experts models only (zeros otherwise): tokens each
            # router output of each layer received from live decode lanes,
            # the number of (substep, layer, held expert) cells that
            # received one, and the number of (substep, layer) pairs that
            # could have; then the same picks split three ways, by whether
            # the output is an expert held here, one another chip holds,
            # or a zero-compute one
            "expert_tokens": self._expert_tokens.copy(),
            "expert_hits": self._expert_hits,
            "expert_substeps": self._expert_substeps,
            "held_expert_tokens": held_picks,
            "absent_expert_tokens": routed_picks - held_picks,
            "zero_expert_tokens": int(self._expert_tokens.sum()) - routed_picks,
            # a model with lane state only (zeros otherwise): bytes a
            # lane carries beside pages (recurrent states, windows), member
            # lanes whose rows a fork wrote, admissions that skipped the
            # prefix cache
            "state_bytes_per_lane": self._state_bytes_per_lane,
            "state_forks": self.state_forks,
            "prefix_skipped_recurrent": self.prefix_skipped_recurrent,
            # pages the allocator handed out next to their holder's last
            # one, over all it handed out; live pages of the decode tables
            # as uploaded over the copies the kernels' walk issues for
            # them (1.0: a page a copy), and that ratio's two sums
            "page_adjacent_share": self.allocator.adjacent
            / max(self.allocator.allocated_total, 1),
            "pages_per_copy": self._table_pages / max(self._table_copies, 1),
            "table_pages": self._table_pages,
            "table_copies": self._table_copies,
            # tokens the kernels' walk fetches and attends to at a step
            "block_tokens": pages_per_block(*self._copy_rule[:3]) * self.config.page_size,
        }

    def _harvest(
        self, host: Dict[str, np.ndarray], macro_idx: int
    ) -> List[CompletedSequence]:
        with tracing.span("genrl.harvest", kind="genrl"):
            mask = np.asarray(host["mask"], np.float32)
            tokens = np.asarray(host["tokens"], np.int32)
            logp = np.asarray(host["logp"], np.float32)
            value = np.asarray(host["value"], np.float32)
            done = np.asarray(host["done"], bool)
            cl = np.asarray(host["cl"], np.int32)
            finish = time.monotonic()
            completions: List[CompletedSequence] = []
            decode_tokens = 0
            for lane_id, lane in enumerate(self._lanes):
                if not lane.busy:
                    continue
                if lane.admit_macro > macro_idx:
                    # this read predates the lane's current occupancy (the id
                    # was recycled while this macro was in flight): the row
                    # belongs to the finished previous occupant, already
                    # harvested — never apply it to the new one
                    continue
                count = int(mask[lane_id].sum())
                decode_tokens += count
                if count > 0:
                    lane.tokens.append(tokens[lane_id, :count])
                    lane.logps.append(logp[lane_id, :count])
                    lane.values.append(value[lane_id, :count])
                lane.context_len = int(cl[lane_id])
                if done[lane_id]:
                    completions.append(
                        CompletedSequence(
                            prompt=lane.prompt,
                            prompt_len=lane.prompt_len,
                            response_tokens=np.concatenate(lane.tokens)
                            if lane.tokens
                            else np.zeros((0,), np.int32),
                            behavior_logp=np.concatenate(lane.logps)
                            if lane.logps
                            else np.zeros((0,), np.float32),
                            values=np.concatenate(lane.values)
                            if lane.values
                            else np.zeros((0,), np.float32),
                            generation=lane.generation,
                            submit_time=lane.submit_time,
                            admit_time=lane.admit_time,
                            finish_time=finish,
                            tag=lane.tag,
                        )
                    )
                    # release the lane: every page hold returns to the pool
                    # (shared prefix pages just drop one ref; exclusively
                    # owned pages go back to the free list immediately — the
                    # memory-scales-with-live-tokens half)
                    self.allocator.free(lane.pages, holder=f"lane[{lane_id}]")
                    self.allocator.release(lane.reserved)
                    self._table[lane_id] = 0
                    self._lanes[lane_id] = _Lane()
            self._decode_meter.mark(decode_tokens)
            self.completed_total += len(completions)
            if completions:
                self._completed_counter.inc(len(completions))
        return completions

    @property
    def mean_occupancy(self) -> float:
        """Mean live-lane fraction over all dispatched macro-steps
        (sampled post-admission, the occupancy the decode program saw)."""
        return self._occupancy_sum / max(self.macro_steps, 1)

    def run_until(
        self, n_completions: int, max_macro_steps: int = 10_000
    ) -> List[CompletedSequence]:
        """Drive macro-steps until ``n_completions`` sequences finished
        (requires enough prompts submitted/submittable to get there)."""
        out: List[CompletedSequence] = []
        for _ in range(max_macro_steps):
            if len(out) >= n_completions:
                return out
            if (
                self.live_lanes == 0
                and self.pending == 0
                and not self._inflight
            ):
                raise RuntimeError(
                    f"engine drained at {len(out)}/{n_completions} "
                    "completions (no live lanes, empty queue)"
                )
            out.extend(self.step())
        raise RuntimeError(
            f"run_until({n_completions}) exceeded {max_macro_steps} "
            "macro-steps"
        )
