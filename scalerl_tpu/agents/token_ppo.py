"""Token-level PPO learner for the sequence-RL plane.

The learning half of the MindSpeed-RL-shaped dataflow (``genrl/``): a
PPO-clip update over *generated token sequences* where every response
token is one action —

- **per-token importance ratios** against the STORED behavior logprobs
  (the sampling distribution the generation engine actually drew from),
  so replayed / stale sequences are corrected exactly like IMPALA corrects
  actor lag;
- **KL-to-reference penalty**: a frozen reference copy of the initial
  params rides the train state, and ``kl_cost > 0`` adds the forward KL
  from the current policy to it per token (the RLHF anchor keeping the
  policy from collapsing onto the reward);
- **length-masked losses over padded buckets**: sequences live in static
  (prompt bucket + response bucket) shapes; every loss/metric term is
  masked by the real-token mask and normalized by real token count, so
  bucket padding is numerically invisible;
- **pad-free packed rows** (ISSUE 15): with ``learner_packing`` the batch
  instead carries ``genrl/rollout.py``'s bin-packed ``[rows, S]`` layout
  (``segment_ids`` present) and :func:`token_ppo_packed_loss` runs
  segment-blocked causal attention — same loss and gradients to 1e-5,
  none of the pad FLOPs; the learn fn dispatches on the batch layout at
  trace time, so the padded path stays the packed path's parity twin;
- the whole update is ONE pure jitted ``(state, batch) -> (state,
  metrics)`` function riding the existing machinery: the nonfinite guard
  (decided from the loss and the gradient norm before the update,
  :func:`make_token_ppo_learn_fn`), the dp×mp sharded learn step
  (``enable_mesh`` -> ``make_parallel_learn_fn`` with the logical mp rule
  table), and the one-batched-transfer metric discipline
  (``learn_device`` + ``get_metrics``).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import struct

from scalerl_tpu.models.routed_ffn import router_balance
from scalerl_tpu.models.transformer import (
    TransformerPolicy,
    sequence_attention_mask,
    sequence_positions,
)
from scalerl_tpu.runtime import tracing
from scalerl_tpu.utils import profiling  # noqa: F401  (installs the spans' profiler half)
from scalerl_tpu.utils.checkpoint import load_checkpoint, save_checkpoint


@struct.dataclass
class TokenPPOTrainState:
    params: Any
    ref_params: Any  # frozen KL anchor (identity through every update)
    opt_state: Any
    step: jnp.ndarray  # learner updates
    tokens_seen: jnp.ndarray  # real (unmasked) response tokens consumed


def masked_mean(x: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Mean of ``x`` over positions where ``mask`` is 1 (safe on empty)."""
    return jnp.sum(x * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def _forward_with_balance(model, params, tokens, real_tokens, **call):
    """The learner's forward; for a routed-experts model also what its
    routers did with the real tokens (``models/routed_ffn.py``
    :func:`router_balance`), else None."""
    if model.block.ffn != "experts":
        return model.apply(params, tokens, **call), None
    out, sown = model.apply(
        params, tokens, mutable=["intermediates"], **call
    )
    return out, router_balance(sown["intermediates"], real_tokens)


def _add_router_aux(total, metrics, balance, coef: float, spec):
    """``coef x E x sum_e f_e P_e`` joins the total; the metric dict
    gains the term, the largest expert's load, and the real tokens' picks
    of the routed experts split into those whose banks are held here and
    those another rank holds (sums over the routed layers)."""
    if balance is None:
        return total
    metrics["moe_aux_loss"] = balance.aux_loss
    metrics["moe_max_load"] = balance.max_load
    held = spec.experts_held or spec.num_experts
    routed = balance.counts[:, : spec.num_experts]
    here = routed[:, spec.first_expert : spec.first_expert + held]
    metrics["moe_held_picks"] = jnp.sum(here).astype(jnp.float32)
    metrics["moe_absent_picks"] = (jnp.sum(routed) - jnp.sum(here)).astype(
        jnp.float32
    )
    return total + coef * balance.aux_loss


def _add_mtp(total, metrics, mtp_logits, batch, w_full, coef: float):
    """The multi-token-prediction term of a packed batch: position ``i``'s
    module output predicts token ``i + 2``.  A position counts when tokens
    ``i + 1`` and ``i + 2`` lie in ``i``'s own segment and token ``i + 2``
    is a response token the loss mask counts; ``L_mtp`` is the mean of
    ``-log p_i(t_{i+2})`` over those (weighted like every loss term), and
    ``coef x L_mtp`` joins the total.  ``mtp_top1_match`` is the share of
    counted positions whose largest logit is ``t_{i+2}``."""
    seg = batch["segment_ids"]
    S = seg.shape[1]

    def ahead(x, n):  # x at offset i + n, in place (the row keeps its length)
        return jnp.roll(x, -n, axis=1)

    tgt = ahead(batch["tokens"], 2)
    own = (
        (seg > 0) & (ahead(seg, 1) == seg) & (ahead(seg, 2) == seg)
        & (jnp.arange(S)[None, :] < S - 2)
    )
    mask = ahead(batch["mask"], 2) * own
    w_mask = ahead(w_full, 2) * own
    logp = jax.nn.log_softmax(mtp_logits, axis=-1)  # [N, S, V]
    nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    mtp_loss = masked_mean(nll, w_mask)
    metrics["mtp_loss"] = mtp_loss
    metrics["mtp_top1_match"] = masked_mean(
        (jnp.argmax(mtp_logits, axis=-1) == tgt).astype(jnp.float32), mask
    )
    return total + coef * mtp_loss


def token_ppo_loss(
    params,
    ref_params,
    model: TransformerPolicy,
    batch: Dict[str, jnp.ndarray],
    clip_range: float,
    value_cost: float,
    entropy_cost: float,
    kl_cost: float,
    adv_norm: bool,
    router_aux_coef: float = 0.0,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """PPO-clip over one ``[B, S]`` packed-sequence batch.

    ``batch`` carries the ``genrl/rollout.py`` fields: ``tokens [B, S]``
    (left-padded prompt + response), ``behavior_logp``/``value``/``mask``
    ``[B, R]``, ``reward``/``prompt_len``/``generation`` ``[B]``, plus an
    optional ``is_weight [B]`` (PER importance weights).  The prompt pad
    ``P = S - R`` is static by shape, so one compile covers every batch at
    the same bucket pair.
    """
    tokens = batch["tokens"]
    behavior_logp = batch["behavior_logp"]
    behavior_value = batch["value"]
    mask = batch["mask"]
    reward = batch["reward"]
    prompt_len = batch["prompt_len"]
    B, S = tokens.shape
    R = behavior_logp.shape[1]
    P = S - R
    seq_w = batch.get("is_weight")
    w_mask = mask if seq_w is None else mask * seq_w[:, None]

    positions = sequence_positions(prompt_len, P, S)
    attn_mask = sequence_attention_mask(prompt_len, P, S)
    # real tokens: the prompt behind its left pad, the response under its mask
    cols = jnp.arange(S)[None, :]
    real = (cols >= P - prompt_len[:, None]) & (cols < P)
    real = real.at[:, P:].set(mask > 0)
    out, balance = _forward_with_balance(
        model, params, tokens, real, positions=positions, attn_mask=attn_mask
    )
    # token at absolute position p is predicted by the output at p-1:
    # response tokens occupy [P, S) -> predicting slice [P-1, S-1)
    pred_logits = out.policy_logits[:, P - 1:S - 1]  # [B, R, V]
    values = out.baseline[:, P - 1:S - 1]  # [B, R]
    resp_tokens = tokens[:, P:S]
    logp_all = jax.nn.log_softmax(pred_logits, axis=-1)
    new_logp = jnp.take_along_axis(
        logp_all, resp_tokens[..., None], axis=-1
    )[..., 0]

    # terminal sequence-level reward, undiscounted credit to every real
    # token; baseline = the sampling-time value estimate
    adv = reward[:, None] - behavior_value
    if adv_norm:
        mu = masked_mean(adv, mask)
        var = masked_mean(jnp.square(adv - mu), mask)
        adv = (adv - mu) * jax.lax.rsqrt(var + 1e-8)
    adv = jax.lax.stop_gradient(adv * mask)

    log_ratio = new_logp - jax.lax.stop_gradient(behavior_logp)
    ratio = jnp.exp(log_ratio)
    unclipped = ratio * adv
    clipped = jnp.clip(ratio, 1.0 - clip_range, 1.0 + clip_range) * adv
    pg_loss = -masked_mean(jnp.minimum(unclipped, clipped), w_mask)

    value_loss = value_cost * 0.5 * masked_mean(
        jnp.square(values - reward[:, None]), w_mask
    )
    # entropy bonus (negative entropy minimised, the ops/losses convention)
    ent = jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1)
    entropy_term = entropy_cost * masked_mean(ent, w_mask)

    total = pg_loss + value_loss + entropy_term
    metrics = {
        "pg_loss": pg_loss,
        "value_loss": value_loss,
        "entropy": -masked_mean(ent, mask),
        "mean_ratio": masked_mean(ratio, mask),
        "mean_approx_kl": masked_mean((ratio - 1.0) - log_ratio, mask),
        "mean_clip_frac": masked_mean(
            (jnp.abs(ratio - 1.0) > clip_range).astype(jnp.float32), mask
        ),
        "mean_reward": jnp.mean(reward),
        "mean_value": masked_mean(values, mask),
        "mean_generation": jnp.mean(batch["generation"].astype(jnp.float32)),
        "mean_response_len": jnp.mean(jnp.sum(mask, axis=1)),
    }
    if kl_cost > 0.0:
        ref_out = model.apply(
            ref_params, tokens, positions=positions, attn_mask=attn_mask
        )
        ref_logp = jax.lax.stop_gradient(
            jax.nn.log_softmax(ref_out.policy_logits[:, P - 1:S - 1], axis=-1)
        )
        # forward KL(pi || pi_ref), per token, over the full vocab
        kl = jnp.sum(jnp.exp(logp_all) * (logp_all - ref_logp), axis=-1)
        kl_term = kl_cost * masked_mean(kl, w_mask)
        total = total + kl_term
        metrics["kl_ref"] = masked_mean(kl, mask)
    total = _add_router_aux(total, metrics, balance, router_aux_coef, model.block)
    metrics["total_loss"] = total
    metrics = {
        k: v if k == "total_loss" else jax.lax.stop_gradient(v)
        for k, v in metrics.items()
    }
    return total, metrics


def token_ppo_packed_loss(
    params,
    ref_params,
    model: TransformerPolicy,
    batch: Dict[str, jnp.ndarray],
    clip_range: float,
    value_cost: float,
    entropy_cost: float,
    kl_cost: float,
    adv_norm: bool,
    router_aux_coef: float = 0.0,
    mtp_coef: float = 0.0,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """PPO-clip over PACKED learner rows — the pad-free twin of
    :func:`token_ppo_loss`.

    ``batch`` carries the ``genrl/rollout.py`` packed-row fields, all
    ``[N, S]`` per-token: ``tokens`` (compact prompt+response segments),
    ``segment_ids`` (0 = pad), ``positions`` (reset per segment),
    ``behavior_logp``/``value``/``reward``/``generation`` aligned at each
    response token's own offset, and ``mask`` = the loss mask (1 exactly
    on response tokens).  Token ``t`` is predicted by the model output at
    ``t - 1`` — always in-segment, because every segment starts with at
    least one prompt token — so all per-token terms shift by one and the
    math is the padded loss over the identical token multiset: the two
    paths agree to float tolerance on loss AND gradients (the parity
    contract the tests pin at 1e-5).  An optional ``is_weight [N]`` (PER
    weights, per ROW — the replay unit) scales the loss mask exactly like
    the padded path's per-sequence weight.

    A model that carries a multi-token-prediction module (``mtp_layers``)
    runs it in this forward, and ``mtp_coef x L_mtp`` joins the total
    (:func:`_add_mtp`); its gradient reaches the trunk, the embedding and
    the head.  The padded loss has no such term.
    """
    tokens = batch["tokens"]
    seg = batch["segment_ids"]
    positions = batch["positions"]
    seq_w = batch.get("is_weight")
    w_full = (
        batch["mask"] if seq_w is None else batch["mask"] * seq_w[:, None]
    )

    mtp = dict(mtp=True) if model.mtp_layers else {}
    out, balance = _forward_with_balance(
        model, params, tokens, seg > 0, positions=positions, segment_ids=seg,
        **mtp,
    )
    # output at row offset t-1 predicts the token at offset t
    pred_logits = out.policy_logits[:, :-1]  # [N, S-1, V]
    values = out.baseline[:, :-1]
    tgt = tokens[:, 1:]
    mask = batch["mask"][:, 1:]
    w_mask = w_full[:, 1:]
    behavior_logp = batch["behavior_logp"][:, 1:]
    behavior_value = batch["value"][:, 1:]
    reward = batch["reward"][:, 1:]
    logp_all = jax.nn.log_softmax(pred_logits, axis=-1)
    new_logp = jnp.take_along_axis(logp_all, tgt[..., None], axis=-1)[
        ..., 0
    ]

    adv = reward - behavior_value
    if adv_norm:
        mu = masked_mean(adv, mask)
        var = masked_mean(jnp.square(adv - mu), mask)
        adv = (adv - mu) * jax.lax.rsqrt(var + 1e-8)
    adv = jax.lax.stop_gradient(adv * mask)

    log_ratio = new_logp - jax.lax.stop_gradient(behavior_logp)
    ratio = jnp.exp(log_ratio)
    unclipped = ratio * adv
    clipped = jnp.clip(ratio, 1.0 - clip_range, 1.0 + clip_range) * adv
    pg_loss = -masked_mean(jnp.minimum(unclipped, clipped), w_mask)

    value_loss = value_cost * 0.5 * masked_mean(
        jnp.square(values - reward), w_mask
    )
    ent = jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1)
    entropy_term = entropy_cost * masked_mean(ent, w_mask)

    total = pg_loss + value_loss + entropy_term
    # rows hold several sequences: sequence counts come from the max
    # segment id per row, reward/generation means are token-weighted
    # (the padded metrics are sequence-weighted — loss terms, not these
    # diagnostics, carry the parity contract)
    num_seqs = jnp.sum(jnp.max(seg, axis=1).astype(jnp.float32))
    metrics = {
        "pg_loss": pg_loss,
        "value_loss": value_loss,
        "entropy": -masked_mean(ent, mask),
        "mean_ratio": masked_mean(ratio, mask),
        "mean_approx_kl": masked_mean((ratio - 1.0) - log_ratio, mask),
        "mean_clip_frac": masked_mean(
            (jnp.abs(ratio - 1.0) > clip_range).astype(jnp.float32), mask
        ),
        "mean_reward": masked_mean(reward, mask),
        "mean_value": masked_mean(values, mask),
        "mean_generation": masked_mean(
            batch["generation"][:, 1:].astype(jnp.float32), mask
        ),
        "mean_response_len": jnp.sum(batch["mask"])
        / jnp.maximum(num_seqs, 1.0),
        "real_token_frac": jnp.mean((seg > 0).astype(jnp.float32)),
    }
    if kl_cost > 0.0:
        ref_out = model.apply(
            ref_params, tokens, positions=positions, segment_ids=seg
        )
        ref_logp = jax.lax.stop_gradient(
            jax.nn.log_softmax(ref_out.policy_logits[:, :-1], axis=-1)
        )
        kl = jnp.sum(jnp.exp(logp_all) * (logp_all - ref_logp), axis=-1)
        kl_term = kl_cost * masked_mean(kl, w_mask)
        total = total + kl_term
        metrics["kl_ref"] = masked_mean(kl, mask)
    total = _add_router_aux(total, metrics, balance, router_aux_coef, model.block)
    if model.mtp_layers:
        total = _add_mtp(total, metrics, out.mtp_logits, batch, w_full, mtp_coef)
    metrics["total_loss"] = total
    metrics = {
        k: v if k == "total_loss" else jax.lax.stop_gradient(v)
        for k, v in metrics.items()
    }
    return total, metrics


# the learn step's phases outside the model, as a device trace's ``op_name``
# shows them (``benchmark/op_scopes.py``, PERF.md section 3)
_SCOPE_LOSS = "loss"
_SCOPE_UPDATE = "update"
_SCOPE_GUARD = "guard"


@functools.lru_cache(maxsize=None)
def _note_guard(leaves: int, state_bytes: int) -> None:
    """The guard engages on every step, so it has no hit rate: one
    zero-length program span a traced state (the cache is the "once"), so
    that a trace says which form ran and over how much state.
    ``nonfinite_grads`` is the counter of how often it refuses."""
    with tracing.span(
        "learn.guard", kind="learn", verdict="isfinite(loss, grad_norm)",
        leaves=leaves, state_bytes=state_bytes,
    ):
        pass


def make_token_ppo_learn_fn(
    model: TransformerPolicy,
    optimizer: optax.GradientTransformation,
    args,
    shard_update=None,
) -> Callable:
    """Build the pure ``(state, batch) -> (state, metrics)`` update, with
    the all-finite guard folded into it.

    Dispatches per batch LAYOUT at trace time: a batch carrying
    ``segment_ids`` takes the packed-row loss, anything else the padded
    bucket-pair loss — dict structure is static under jit, so one learn
    fn serves both paths (the padded path stays the packed path's parity
    twin) and each layout compiles exactly once.

    **The guard** (ISSUE 33).  Every other learn-fn factory wraps its update
    in ``parallel/train_step.guard_nonfinite_updates``, which builds the
    candidate state, reads all of it to judge it, and lets a ``lax.cond``
    choose between candidate and input.  This state is gigabytes and
    donated: the candidate then lives beside the old state and the chosen
    branch copies it over (598 copies, 4.9 GB a step at gpt2-medium).  So
    here the verdict is taken BEFORE the update, from two scalars the step
    computes anyway::

        ok = isfinite(loss) & isfinite(grad_norm)

    and params, both moments, ``step`` and ``tokens_seen`` are
    ``where(ok, candidate, old)`` inside the update's own elementwise pass.
    A refused step returns its input state bit for bit and counts
    ``nonfinite_grads = skipped_steps = 1``; ``ref_params`` passes through
    and is judged by nobody, for it cannot change.  ``grad_norm`` is the
    float32 norm ``optax.clip_by_global_norm`` asks for: it is written once
    here, the clip inside ``optimizer`` traces the same expression and XLA
    keeps one (on the chip, fused into the weight-gradient matmuls), so
    clip, metric and guard share it.

    *Why ``ok`` implies an all-finite new state*, for the chain
    ``TokenPPOAgent._make_optimizer`` builds (``clip_by_global_norm(G)`` then
    ``adam``, under ``fp32_optimizer_state`` or not), given a finite input
    state: (1) the norm is the root of a sum of float32 squares; a NaN or an
    infinity in any gradient leaf makes the sum NaN or infinite, so a finite
    norm means every gradient is finite.  (2) The clip leaves ``g`` alone
    when ``norm < G`` and else scales it by ``G / norm``: either way
    ``|g| <= G`` up to a rounding.  (3) ``mu`` and ``nu`` become convex
    combinations of finite numbers (``b * old + (1 - b) * g``, the same of
    ``g * g <= G * G``), and their bias corrections divide by ``1 - b**t``,
    which is at least ``1 - b > 0``.  (4) Adam's step is ``lr * mu_hat /
    (sqrt(nu_hat) + eps)``: a finite number over at least ``eps``, at most
    ``lr * G / eps`` in size, and a parameter that moves by so little a step
    cannot leave float32's range in any run (1e30 steps).  bfloat16
    parameters share float32's exponent range, so the cast down keeps finite
    numbers finite.  By induction from a finite initial or restored state,
    the committed state is finite on every step.  The converse fails in one
    place, on the conservative side: gradients whose squares overflow
    float32 while each is finite.  The post-hoc form applied the zero
    gradient the clip made of them (``g / inf``); this form refuses the
    step.  A caller who passes another ``optimizer`` keeps the refusal of
    non-finite gradients and owes its own step (2)-(4).

    ``nonfinite_guard=False`` and ``SCALERL_NONFINITE_GUARD=0`` compile the
    guard out (``train_step.nonfinite_guard_enabled``);
    ``nonfinite_check_every`` is not read here: a select inside the
    update's own pass leaves nothing to amortise.

    **The update sharded over ``dp``** (ISSUE 48).  ``shard_update`` is
    ``parallel/logical.update_sharding``'s pair of closures, which
    ``TokenPPOAgent.enable_mesh`` builds where the mesh's ``dp`` extent is
    over 1 (None anywhere else: the program is then the one above, text for
    text).  The gradients take the moments' layout BEFORE the norm reads
    them, so each weight gradient's ``dp`` reduction is a reduce-scatter,
    the norm a sum of squares over local shards and one scalar reduction,
    and clip, Adam and the guard's select a pass over this replica's rows;
    the chosen parameters are gathered back to the layout the forward
    reads.  The arithmetic is the same to the last operation but for the
    order in which two replicas' partial sums meet.
    """
    from scalerl_tpu.parallel.train_step import (
        nonfinite_guard_enabled,
        tree_all_finite,
    )

    guarded = nonfinite_guard_enabled(args)

    def learn(state: TokenPPOTrainState, batch: Dict[str, jnp.ndarray]):
        loss_fn = token_ppo_loss
        if "segment_ids" in batch:
            loss_fn = functools.partial(
                token_ppo_packed_loss,
                mtp_coef=getattr(args, "mtp_loss_coef", 0.0),
            )
        with jax.named_scope(_SCOPE_LOSS):
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(
                state.params,
                state.ref_params,
                model,
                batch,
                clip_range=args.clip_range,
                value_cost=args.value_cost,
                entropy_cost=args.entropy_cost,
                kl_cost=args.kl_cost,
                adv_norm=args.adv_norm,
                router_aux_coef=getattr(args, "router_aux_loss_coef", 0.0),
            )
        if shard_update is not None:
            grads = shard_update.scatter(grads)
        metrics["grad_norm"] = optax.global_norm(
            jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
        )
        with jax.named_scope(_SCOPE_UPDATE):
            updates, opt_state = optimizer.update(
                grads, state.opt_state, state.params
            )
            new = dict(
                params=optax.apply_updates(state.params, updates),
                opt_state=opt_state,
                step=state.step + 1,
                tokens_seen=state.tokens_seen
                + jnp.sum(batch["mask"]).astype(state.tokens_seen.dtype),
            )
        if guarded:
            with jax.named_scope(_SCOPE_GUARD):
                ok = tree_all_finite((loss, metrics["grad_norm"]))
                old = {name: getattr(state, name) for name in new}
                new = jax.tree_util.tree_map(
                    lambda n, o: jnp.where(ok, n, o), new, old
                )
                leaves = jax.tree_util.tree_leaves(old)
                _note_guard(len(leaves), sum(x.size * x.dtype.itemsize for x in leaves))
                bad = 1.0 - ok.astype(jnp.float32)
            metrics["nonfinite_grads"] = bad
            metrics["skipped_steps"] = bad
        if shard_update is not None:
            new["params"] = shard_update.gather(new["params"])
        return state.replace(**new), metrics

    return learn


class TokenPPOAgent:
    """Host-facing token-PPO agent: jitted learn + weight pub + mesh hookup.

    Not a :class:`PolicyValueAgent` — the acting path is the generation
    engine, not the recurrent per-step signature — but it speaks the same
    learner dialect: ``learn_device`` leaves metrics on device,
    ``learn`` reads them back with ONE batched transfer, ``enable_mesh``
    re-jits through ``make_parallel_learn_fn`` with the logical mp layout
    (heads/mlp/vocab over ``mp``) when the mesh has model parallelism.
    """

    def __init__(
        self,
        args,
        model: TransformerPolicy,
        key: Optional[jax.Array] = None,
    ) -> None:
        if model.vocab_size is None:
            raise ValueError(
                "TokenPPOAgent needs a token-mode TransformerPolicy "
                "(vocab_size set)"
            )
        self.args = args
        self.model = model
        key = key if key is not None else jax.random.PRNGKey(args.seed)
        dummy = jnp.zeros((1, min(2, model.max_len)), jnp.int32)
        params = model.init(key, dummy)
        self.optimizer = self._make_optimizer(args)
        from scalerl_tpu.runtime.param_server import _tree_map, jnp_copy

        self.state = TokenPPOTrainState(
            params=params,
            ref_params=_tree_map(jnp_copy, params),
            opt_state=self.optimizer.init(params),
            step=jnp.zeros((), jnp.int32),
            tokens_seen=jnp.zeros((), jnp.int32),
        )
        self._learn_fn = make_token_ppo_learn_fn(model, self.optimizer, args)
        self._learn = jax.jit(self._learn_fn)
        self._shard_batch = None
        self.mesh = None

    @staticmethod
    def _make_optimizer(args) -> optax.GradientTransformation:
        tx = optax.chain(
            optax.clip_by_global_norm(args.max_grad_norm),
            optax.adam(args.learning_rate),
        )
        if getattr(args, "bf16_params", False):
            from scalerl_tpu.parallel.train_step import fp32_optimizer_state

            tx = fp32_optimizer_state(tx)
        return tx

    def make_learn_fn(self, shard_update=None) -> Callable:
        """Learn fn from this agent's model/optimizer/args (the
        ``enable_mesh`` rebuild contract, ``agents/impala.py``)."""
        return make_token_ppo_learn_fn(
            self.model, self.optimizer, self.args, shard_update=shard_update
        )

    def enable_mesh(self, mesh_or_spec, batch_example=None) -> None:
        """Shard the learn step over a device mesh; with ``mp > 1`` the
        transformer's heads/mlp/vocab dims lay out per the logical rule
        table and inter-layer activations pin batch-over-dp.

        With ``dp > 1`` the WEIGHT UPDATE is sharded over ``dp`` as well
        (``parallel/logical.mp_param_sharding``'s ``update_axis``): each
        replica keeps, beside what ``mp`` gave it, its ``1/dp`` of both Adam
        moments, receives that share of the gradients' sum (a
        reduce-scatter where the replicated update all-reduced), clips and
        updates those rows and all-gathers the new parameters.  Parameters
        and the frozen reference stay whole over ``dp`` at rest, so what the
        forward, a checkpoint and ``get_weights`` read is laid out as
        before.  A mesh whose ``dp`` is 1 builds the program it always
        built; so does one with an ``fsdp`` or ``tp`` axis, whose state the
        heuristic rule already shards over an axis of its own."""
        from scalerl_tpu.parallel import (
            activation_constraint,
            has_mp_params,
            make_parallel_learn_fn,
            mp_param_sharding,
            resolve_mesh,
        )
        from scalerl_tpu.parallel.logical import (
            update_sharding,
            update_sharding_counts,
        )

        mesh = resolve_mesh(mesh_or_spec)
        param_specs = None
        shard_update = None
        meshed = {}  # model fields that depend on the mesh
        if self.model.segment_attn_fn is not None:
            # the packed-row kernel is a Mosaic call, which GSPMD cannot
            # partition: run it per (dp rows, mp heads) shard
            from scalerl_tpu.ops.pallas_attention import shard_segment_attn

            meshed["segment_attn_fn"] = shard_segment_attn(
                self.model.segment_attn_fn, mesh
            )
        mp = mesh.shape.get("mp", 1) > 1
        if mp:
            if not has_mp_params(self.state.params):
                raise ValueError(
                    "mesh has mp > 1 but the model carries no "
                    "model-parallel shardable params"
                )
            if self.model.constrain is None:
                meshed["constrain"] = activation_constraint(mesh)
        if mp or all(mesh.shape.get(a, 1) == 1 for a in ("fsdp", "tp")):
            shard_update = update_sharding(self.state.params, mesh, "dp")
        if mp or shard_update is not None:
            # (where ``dp`` is 1 the axis changes no leaf's spec)
            param_specs = mp_param_sharding(self.state, mesh, update_axis="dp")
        if meshed:
            self.model = self.model.clone(**meshed)
        if meshed or shard_update is not None:
            self._learn_fn = self.make_learn_fn(shard_update)
        plearn = make_parallel_learn_fn(
            self._learn_fn, mesh, self.state,
            batch_example=batch_example,
            batch_time_major=False,  # packed batches are [B, ...]
            param_specs=param_specs,
        )
        if shard_update is not None:
            # a layout engages on every step and has no hit rate: one
            # zero-length span a sharded learn program built
            with tracing.span(
                "learn.update_sharding", kind="learn",
                **update_sharding_counts(self.state.opt_state, param_specs.opt_state, "dp"),
            ):
                pass
        self.mesh = mesh
        self.state = plearn.shard_state(self.state)
        self._learn = plearn
        self._shard_batch = plearn.shard_batch

    def learn_device(self, batch) -> Dict[str, Any]:
        """One train step, metrics left as device arrays (the hot-loop
        half of the one-batched-transfer discipline)."""
        with tracing.span("learn.dispatch", kind="learn"):
            if self._shard_batch is not None:
                batch = self._shard_batch(batch)
            self.state, metrics = self._learn(self.state, batch)  # graftlint: disable=JG002 (single-threaded learner loop; genrl has no actor threads)
        return metrics

    def lower_learn(self, batch):
        """Lower the learn step for ``batch`` against the live state —
        nothing runs and nothing is donated."""
        if self._shard_batch is not None:
            batch = self._shard_batch(batch)
        return self._learn.lower(self.state, batch)

    def learn(self, batch) -> Dict[str, float]:
        from scalerl_tpu.runtime.dispatch import get_metrics

        with tracing.span("learn.step", kind="learn"):
            return get_metrics(self.learn_device(batch))  # one batched transfer

    def get_weights(self):
        return self.state.params

    def set_weights(self, weights) -> None:
        self.state = self.state.replace(params=weights)

    def save_checkpoint(self, path: str) -> str:
        return save_checkpoint(path, self.state)

    def load_checkpoint(self, path: str) -> None:
        restored = load_checkpoint(path, self.state)
        if self._shard_batch is not None and hasattr(self._learn, "shard_state"):
            restored = self._learn.shard_state(restored)
        self.state = restored
