"""Batched KV-cached generation engine: the acting half of sequence RL.

One jitted program per (prompt bucket, response bucket) pair covers the
WHOLE generation round — prefill over the left-padded prompt batch plus a
``lax.scan`` (TPU/GPU) or Python-unrolled (CPU, the PR 6 ``iter_mode``
verdict) loop of single-token decode steps with temperature/top-k
sampling.  The host dispatches once and reads back once:

- **bucketed static shapes** — prompt lengths pad up a power-of-two ladder
  (``serving/batcher.py``'s ``bucket_for``) and prompts are LEFT-padded
  (right-aligned) inside the bucket, so every lane's decode cursor is the
  same scalar and XLA compiles once per bucket, never retracing on ragged
  prompts (graftlint JG003 designed out);
- **one batched host read per round** — the program returns one pytree
  (tokens, behavior logprobs, values, alive mask, lengths) fetched with a
  single ``_device_get``; after a bucket's first (compiling) round the
  call runs under ``steady_state_guard()``, so a stray implicit transfer
  anywhere in the loop raises at the line that did it (JG001's runtime
  twin, same discipline as the fused drivers and the serving flush loop);
- **generation-tagged parameters** — the learner publishes snapshots via
  :meth:`push_params` (device-side copy + monotonic bump, the
  ``InferenceServer`` idiom); every completed sequence carries the
  generation that produced it, so the learner's importance ratios know
  their off-policy lag.
"""

from __future__ import annotations

import time
from contextlib import nullcontext
from dataclasses import dataclass
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from scalerl_tpu.models.transformer import (
    TransformerPolicy,
    decode_attention_mask,
    init_kv_cache,
    prefill_attention_mask,
    sequence_positions,
)
from scalerl_tpu.runtime import telemetry, tracing
from scalerl_tpu.runtime.device_loop import resolve_iter_mode
from scalerl_tpu.runtime.dispatch import steady_state_guard
from scalerl_tpu.runtime.param_server import ParamSnapshotPlane
from scalerl_tpu.utils.buckets import bucket_for, default_buckets

# module seams: tests monkeypatch these to count host transfers and assert
# the one-upload-one-read-per-round invariant
_device_put = jax.device_put
_device_get = jax.device_get


def adjust_logits(
    logits: jnp.ndarray, temperature: float, top_k: int, vocab_size: int
) -> jnp.ndarray:
    """Sampling adjustments (top-k mask then temperature) — the behavior
    logprob is computed from THESE logits, so the stored logp is the true
    log-density of the sampling distribution.  ``temperature == 0`` (greedy)
    skips the scale: sampling argmaxes and the logp reads the unscaled
    log-softmax (both engines share this helper, so temperature-0 parity
    across them is exact by construction)."""
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
class GenerationConfig:
    """Knobs for the generation engine.

    ``eos_token < 0`` disables early stopping (fixed-length responses, the
    synthetic-task default); with an EOS id, lanes latch done on sampling
    it and their remaining steps emit EOS with a zeroed alive mask.
    ``temperature == 0`` selects greedy (argmax) decoding — the setting the
    fixed-vs-continuous engine parity tests pin token-identical outputs at.
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


class GenerationResult(NamedTuple):
    """One generation round, materialized on the host (post batched read)."""

    sequences: np.ndarray  # [B, P+R] int32 left-padded prompt + response
    response_tokens: np.ndarray  # [B, R] int32
    behavior_logp: np.ndarray  # [B, R] f32 logprob under the SAMPLING dist
    values: np.ndarray  # [B, R] f32 baseline before each sampled token
    mask: np.ndarray  # [B, R] f32 1.0 where the token is real
    response_len: np.ndarray  # [B] int32
    prompt_len: np.ndarray  # [B] int32 true (unpadded) prompt lengths
    prompt_pad: int  # the prompt bucket P this round compiled at
    response_pad: int  # the response bucket R
    generation: int  # param generation that produced the round

    @property
    def decode_tokens(self) -> int:
        return int(self.mask.sum())

    @property
    def prompt_tokens(self) -> int:
        return int(self.prompt_len.sum())


class GenerationEngine(ParamSnapshotPlane):
    """Owns generation-tagged param snapshots + one jitted decode program
    per (prompt, response) bucket pair.

    ``model``: a token-mode :class:`TransformerPolicy` (``vocab_size`` set,
    ``max_len >= prompt_bucket + response_bucket``).  ``params``: the
    initial snapshot (the learner's live params at construction).
    ``dispatch_guard``: zero-arg context-manager factory entered around
    every device dispatch — trainers with a live mesh pass their mesh
    dispatch guard (graftlint JG002).
    """

    def __init__(
        self,
        model: TransformerPolicy,
        params: Any,
        config: GenerationConfig,
        iter_mode: str = "auto",
        dispatch_guard: Optional[Callable[[], Any]] = None,
    ) -> None:
        config.validate()
        if model.vocab_size is None:
            raise ValueError(
                "GenerationEngine needs a token-mode TransformerPolicy "
                "(vocab_size set); got a feature-embedding model"
            )
        max_p = bucket_for(
            config.max_prompt_len, config.resolved_prompt_buckets()
        )
        max_r = bucket_for(
            config.max_new_tokens, config.resolved_response_buckets()
        )
        if model.max_len < max_p + max_r:
            raise ValueError(
                f"model.max_len ({model.max_len}) must cover the largest "
                f"bucket pair (prompt {max_p} + response {max_r})"
            )
        self.model = model
        self.config = config
        self.iter_mode = resolve_iter_mode(iter_mode)
        self._dispatch_guard = dispatch_guard or nullcontext
        self._init_param_plane(params)
        self._key = jax.random.PRNGKey(config.seed)
        self._programs: Dict[Tuple[int, int], Callable] = {}
        self._warm: set = set()
        reg = telemetry.get_registry()
        self._round_counter = reg.counter("genrl.rounds")
        self._prompt_meter = reg.meter("genrl.prompt_tokens_per_s")
        self._decode_meter = reg.meter("genrl.decode_tokens_per_s")
        reg.bind(
            "genrl.engine",
            lambda: {
                "generation": self.generation,
                "warm_buckets": len(self._warm),
                "iter_mode": self.iter_mode,
            },
        )

    # -- program construction ------------------------------------------
    def _adjust_logits(self, logits: jnp.ndarray) -> jnp.ndarray:
        return adjust_logits(
            logits, self.config.temperature, self.config.top_k,
            self.config.vocab_size,
        )

    def _build_program(self, P: int, R: int) -> Callable:
        """Build + jit the whole-round program at one bucket pair.

        The Python ints ``P``/``R`` are closed over (never traced), so the
        returned callable is shape-stable by construction; ``iter_mode``
        picks lax.scan vs a Python-unrolled decode loop inside the SAME
        jitted program (identical math, asserted in tests).
        """
        model = self.model
        cfg = self.config
        S = P + R
        head_dim = model.head_dim
        use_scan = self.iter_mode == "scan"

        def step(params, lengths, carry, t):
            cache, logits, value, done, key = carry
            key, sub = jax.random.split(key)
            adj = self._adjust_logits(logits)
            token = sample_tokens(sub, adj, cfg.temperature)
            logp = jnp.take_along_axis(
                jax.nn.log_softmax(adj, axis=-1), token[:, None], axis=-1
            )[:, 0]
            # a token is real if its lane had not finished BEFORE this step
            # (the step that samples EOS still emits a real token)
            alive = jnp.logical_not(done)
            if cfg.eos_token >= 0:
                token = jnp.where(done, cfg.eos_token, token)
                done = jnp.logical_or(done, token == cfg.eos_token)
            out_t = (token, logp, value, alive.astype(jnp.float32))
            # feed the sampled token back through the cached model
            pos = (lengths + t)[:, None]
            mask = decode_attention_mask(lengths, P, t, S)
            out, cache = model.apply(
                params,
                token[:, None],
                positions=pos,
                kv_cache=cache,
                cache_index=P + t,
                attn_mask=mask,
            )
            new_carry = (
                cache,
                out.policy_logits[:, 0],
                out.baseline[:, 0],
                done,
                key,
            )
            return new_carry, out_t

        def generate(params, tokens, lengths, key):
            B = tokens.shape[0]
            cache = init_kv_cache(
                B, S, model.num_layers, model.num_heads, head_dim,
            )
            ppos = sequence_positions(lengths, P, S)[:, :P]
            pmask = prefill_attention_mask(lengths, P, S)
            out, cache = model.apply(
                params,
                tokens,
                positions=ppos,
                kv_cache=cache,
                cache_index=0,
                attn_mask=pmask,
            )
            carry = (
                cache,
                out.policy_logits[:, -1],
                out.baseline[:, -1],
                jnp.zeros((B,), jnp.bool_),
                key,
            )
            if use_scan:
                carry, outs = jax.lax.scan(
                    lambda c, t: step(params, lengths, c, t),
                    carry,
                    jnp.arange(R),
                )
                toks, logps, values, alive = outs
                # scan stacks on axis 0: [R, B] -> [B, R]
                toks = jnp.swapaxes(toks, 0, 1)
                logps = jnp.swapaxes(logps, 0, 1)
                values = jnp.swapaxes(values, 0, 1)
                alive = jnp.swapaxes(alive, 0, 1)
            else:
                cols = []
                for t in range(R):
                    carry, out_t = step(params, lengths, carry, t)
                    cols.append(out_t)
                toks = jnp.stack([c[0] for c in cols], axis=1)
                logps = jnp.stack([c[1] for c in cols], axis=1)
                values = jnp.stack([c[2] for c in cols], axis=1)
                alive = jnp.stack([c[3] for c in cols], axis=1)
            resp_len = jnp.sum(alive, axis=1).astype(jnp.int32)
            return {
                "tokens": toks.astype(jnp.int32),
                "logp": logps.astype(jnp.float32),
                "value": values.astype(jnp.float32),
                "mask": alive,
                "resp_len": resp_len,
            }

        return jax.jit(generate)

    def _program(self, P: int, R: int) -> Callable:
        fn = self._programs.get((P, R))
        if fn is None:
            fn = self._build_program(P, R)
            self._programs[(P, R)] = fn
        return fn

    def prefill_program(self, P: int, R: int) -> Callable:
        """Jitted prefill-only step at a bucket pair — the bench's
        prefill-tokens/s numerator (``generate`` fuses prefill + decode
        into one program, so the split timing needs this twin)."""
        model = self.model
        S = P + R
        head_dim = model.head_dim

        def prefill(params, tokens, lengths):
            B = tokens.shape[0]
            cache = init_kv_cache(
                B, S, model.num_layers, model.num_heads, head_dim,
            )
            ppos = sequence_positions(lengths, P, S)[:, :P]
            pmask = prefill_attention_mask(lengths, P, S)
            out, cache = model.apply(
                params, tokens, positions=ppos, kv_cache=cache,
                cache_index=0, attn_mask=pmask,
            )
            return out.policy_logits[:, -1], out.baseline[:, -1], cache

        return jax.jit(prefill)

    # -- the generation round ------------------------------------------
    def _align_prompts(
        self, prompts: np.ndarray, lengths: np.ndarray, P: int
    ) -> np.ndarray:
        """Right-align (left-pad) host prompts into the ``[B, P]`` bucket."""
        B = prompts.shape[0]
        out = np.full((B, P), self.config.pad_token, np.int32)
        for b in range(B):
            n = int(lengths[b])
            out[b, P - n:] = prompts[b, :n]
        return out

    def generate(
        self,
        prompts: np.ndarray,
        prompt_lengths: Optional[np.ndarray] = None,
        max_new_tokens: Optional[int] = None,
    ) -> GenerationResult:
        """Run one generation round; returns host numpy results.

        ``prompts``: ``[B, L]`` int32, right-padded (token ``b`` real for
        the first ``prompt_lengths[b]`` columns).  The round pads to the
        (prompt, response) bucket pair, dispatches the ONE jitted program,
        and reads the outputs back with a single batched ``_device_get`` —
        armed with ``steady_state_guard()`` once the bucket pair is warm.
        """
        t_round0 = time.monotonic()
        prompts = np.asarray(prompts, np.int32)
        B, L = prompts.shape
        if prompt_lengths is None:
            prompt_lengths = np.full(B, L, np.int32)
        prompt_lengths = np.asarray(prompt_lengths, np.int32)
        if prompt_lengths.max(initial=1) > self.config.max_prompt_len:
            raise ValueError(
                f"prompt length {int(prompt_lengths.max())} exceeds "
                f"max_prompt_len={self.config.max_prompt_len}"
            )
        P = bucket_for(
            int(prompt_lengths.max(initial=1)),
            self.config.resolved_prompt_buckets(),
        )
        R = bucket_for(
            int(max_new_tokens or self.config.max_new_tokens),
            self.config.resolved_response_buckets(),
        )
        aligned = self._align_prompts(prompts, prompt_lengths, P)
        fn = self._program(P, R)
        params, gen = self._snapshot_params()
        warm = (P, R) in self._warm
        guard = steady_state_guard() if warm else nullcontext()
        with guard:
            with self._dispatch_guard():
                self._key, sub = jax.random.split(self._key)
                # ONE explicit batched host->device upload per round ...
                dev_tokens, dev_lengths = _device_put(
                    (aligned, prompt_lengths)
                )
                out = fn(params, dev_tokens, dev_lengths, sub)
                # ... and ONE explicit batched device->host read
                host = _device_get(out)
        self._warm.add((P, R))
        sequences = np.concatenate(
            [aligned, np.asarray(host["tokens"], np.int32)], axis=1
        )
        result = GenerationResult(
            sequences=sequences,
            response_tokens=np.asarray(host["tokens"], np.int32),
            behavior_logp=np.asarray(host["logp"], np.float32),
            values=np.asarray(host["value"], np.float32),
            mask=np.asarray(host["mask"], np.float32),
            response_len=np.asarray(host["resp_len"], np.int32),
            prompt_len=prompt_lengths,
            prompt_pad=P,
            response_pad=R,
            generation=gen,
        )
        self._round_counter.inc()
        self._prompt_meter.mark(result.prompt_tokens)
        self._decode_meter.mark(result.decode_tokens)
        if tracing.sampling_enabled():
            # ONE head-sampled span per generation round (the whole fused
            # prefill+decode dispatch + its single batched read) — never
            # per token; host monotonic stamps only (JG001 good twin)
            tracing.record_span(
                "genrl.generate_round", None, t_round0, time.monotonic(),
                kind="genrl", batch=B, prompt_pad=P, response_pad=R,
                decode_tokens=int(result.decode_tokens), generation=gen,
            )
        return result
