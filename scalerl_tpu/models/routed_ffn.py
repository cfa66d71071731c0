"""Dropless token-choice routed SwiGLU experts for the token model.

The FFN of the routed block families (``models/transformer.py``
:class:`BlockSpec` ``ffn="experts"``): a linear router scores every token
against ``E`` experts, a float32 softmax over all ``E`` (or, under
``scoring="sigmoid"``, each output's own sigmoid) turns the scores into
probabilities, the ``k`` largest are kept (with their probabilities as
they are, or renormalised over the ``k`` under ``norm_topk_prob``), and
the token's output is the probability-weighted sum of those ``k`` experts'
gated MLPs
``(silu(h Wg) * (h Wu)) Wd``.  Exact at every token count: no capacity,
no dropped token, no ``[tokens, experts, capacity]`` tensor
(``models/moe.py`` is the capacity-dropping top-1 Switch layer of the
classic policies; nothing here builds on it).

One module serves prefill, decode, verify and the learner, in one of two
forms chosen by the token count the trace sees, because the two regimes
have opposite bounds:

- **streamed** (up to ``STREAMED_MAX_TOKENS``: a decode substep, one
  prompt's prefill): every expert's bank is multiplied against every token
  and the combine weights zero what was not picked.  ``lanes x k`` picks
  hit most experts anyway, so the step is bound by reading the banks once;
  the dense form reads them once, in order, with no sort, gather or
  scatter in the substep loop.
- **sorted** (more tokens: batched prefill, the learner): the ``tokens x k``
  assignments are sorted by expert and three ``lax.ragged_dot``s (which
  XLA:TPU compiles to a grouped matmul) do ``k / E`` of the dense form's
  arithmetic.

**A share of a wider layer** (the ``longcat`` and ``joyai`` families,
``held`` < ``num_experts``): the router keeps its published width, ``num_experts``
computed experts and ``zero_experts`` identity ones behind them, and this
module holds the banks of experts ``first_expert .. first_expert + held``
alone, as one rank of an expert-parallel deployment does.  A pick inside
the held range is computed here, an identity pick adds ``w h`` here (it
costs nothing, so it is computed where the token lives), and a pick of an
absent expert contributes nothing: the part of the sum another chip owns.
Both forms serve, and the sorted form's group sizes count held picks only.
But ``lax.ragged_dot`` on a v5e does not skip the sorted rows past its last
group (4,096 tokens x 8 picks with one in thirty-two held took two thirds
of what all held takes; PERF.md, PR 32), so on a share the sorted form pays
for all ``N x k`` rows; where ``held <= k`` the streamed form's ``N x held``
rows are never more, its time does not follow the router, and it runs at
every token count.
The picks are the ``k`` largest of ``p + b`` (``b`` the ``router_bias``
parameter, which only chooses) and weigh ``routed_scaling x p``; a
renormalising sum runs over all ``k`` picks, wherever their experts live,
so that the shares' parts add up.  An always-on shared expert is not
here: it is a dense SwiGLU beside this module (``_Block``).  OLMoE is the
case ``zero_experts = 0``, ``held = num_experts``, no bias,
scaling 1, and traces to the program it always did.

Per call the module also ``sow``s the router's probabilities and picks
into the ``intermediates`` collection, from which :func:`router_balance`
computes the per-expert token counts, the load-balancing loss and the
largest expert's load over the tokens a caller's mask names: a caller
that wants them applies the model with ``mutable=["intermediates"]``,
every other call pays nothing.

**A scorer in front** (the ``zaya`` family): the router need not be this
module's one matrix.  :class:`MLPRouter` scores a token through a narrow
MLP on a state that the previous layer's router handed up, and hands this
module its logits (``__call__``'s ``logits``); the softmax, the pick by
``p + b``, the weights and both forms are the same code.  Top-1 without
renormalisation is ``experts_per_token = 1`` with ``norm_topk_prob``
false: the pick weighs its own probability.
"""

from __future__ import annotations

import functools
from typing import Any, Mapping, NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

# Token count at or below which the streamed form runs.  Measured on a
# v5e at OLMoE's widths (64 experts of 2048 x 1024, 8 a token, bf16;
# PERF.md, PR 25): the streamed form takes 1.15-1.24 ms a layer from 8 to
# 256 tokens (reading the banks once is 0.98 ms) and 2.2 ms at 512, the
# sorted form 1.6 ms at 32 tokens, 2.6-3.1 ms from 64 to 512; at 2,048 it
# is 8.8 against 5.6 ms, and forward with backward 24.1 against 20.7.
STREAMED_MAX_TOKENS = 512


def _bank_init():
    # lecun-normal over a [E, in, out] bank: the fan-in is the middle axis
    return nn.initializers.variance_scaling(
        1.0, "fan_in", "truncated_normal", in_axis=1, out_axis=2, batch_axis=0
    )


def _activate(g, u):
    """An expert's hidden activation in float32: ``silu(g) * u`` of a
    SwiGLU expert, ``relu(u)^2`` of one without a gate (``g`` None)."""
    if g is None:
        return jnp.square(jax.nn.relu(u))
    return jax.nn.silu(g) * u


def _streamed(x, top_p, top_i, w_gate, w_up, w_down, expert_axis=False):
    """``w_gate`` None: the experts have no gate (``relu2``).
    ``expert_axis``: the tokens are given an explicit expert axis
    (``[E, N, d]``, a broadcast), which makes the two up products batched
    matmuls that read the banks as they are stored.  Without it XLA:TPU,
    inside the decode loop at 128 tokens, wants ``w_gate`` and ``w_up``
    with ``d`` minor and copies both whole banks of every layer once a
    macro-step (8 copies of 0.4 GB and 3.2 GB of temporaries at the
    longcat cell's sizes: AOT, PR 30).  The share path takes it; OLMoE's
    32-token program meets no such copy and stays the text it was."""
    E = w_up.shape[0]
    f32 = jnp.float32
    # [N, E] combine weights: a pick's probability at its expert, else 0
    # (a pick outside the held banks, ``top_i`` not in [0, E), is a row of
    # zeros: ``one_hot`` of an index out of range)
    combine = jnp.sum(
        jax.nn.one_hot(top_i, E, dtype=f32) * top_p[..., None], axis=1
    )
    tokens, xe = "nd", x
    if expert_axis:
        tokens, xe = "end", jnp.broadcast_to(x, (E,) + x.shape)
    up = lambda w: jnp.einsum(  # noqa: E731
        f"{tokens},edf->enf", xe, w, preferred_element_type=f32
    )
    g = None if w_gate is None else up(w_gate)
    a = _activate(g, up(w_up)) * combine.T[:, :, None]
    # one contraction over (expert, width): the experts' outputs are
    # summed in the matmul's float32 accumulator
    return jnp.einsum(
        "enf,efd->nd", a.astype(x.dtype), w_down, preferred_element_type=f32
    )


def _sorted(x, top_p, top_i, w_gate, w_up, w_down, held_rows=None):
    """``held_rows [N * k]`` (a share of a wider layer): which assignments
    fell on a bank held here; the others carry the index ``E``, sort
    behind every group, count in no group's size and add nothing.  What a
    grouped matmul leaves in a row of no group is not defined, and so is
    what its transposes leave there: the gathered tokens, the two products
    that enter the gate, the gated activations and the outputs of such rows
    are selected to zero.  Both sides of the gate: going back, a zero cotangent times the
    gate's derivative at a non-finite leftover is a NaN, which the
    weights' transposes then sum into a bank's gradient and into the
    absent picks' scores (one seed in five on the chip; PERF.md, PR 32)."""
    N, k = top_i.shape
    E = w_up.shape[0]
    f32 = jnp.float32
    expert = top_i.reshape(N * k)
    order = jnp.argsort(expert)  # stable: assignments grouped by expert
    # an index of ``E`` (not held here) is out of range and dropped
    sizes = jnp.zeros((E,), jnp.int32).at[expert].add(1)
    xs = x[order // k]
    if held_rows is not None:
        in_a_group = held_rows[order][:, None]
        xs = jnp.where(in_a_group, xs, 0)  # nothing of them goes back to ``x``
    g = None
    if w_gate is not None:
        g = lax.ragged_dot(xs, w_gate, sizes, preferred_element_type=f32)
    u = lax.ragged_dot(xs, w_up, sizes, preferred_element_type=f32)
    if held_rows is not None:
        u = jnp.where(in_a_group, u, 0.0)
        if g is not None:
            g = jnp.where(in_a_group, g, 0.0)
    a = _activate(g, u) * top_p.reshape(N * k)[order][:, None]
    if held_rows is not None:
        a = jnp.where(in_a_group, a, 0.0)
    ys = lax.ragged_dot(
        a.astype(x.dtype), w_down, sizes, preferred_element_type=f32
    )
    if held_rows is not None:
        ys = jnp.where(in_a_group, ys, 0.0)
    # back to token order by a gather, then the k picks summed
    return jnp.sum(ys[jnp.argsort(order)].reshape(N, k, -1), axis=1)


@functools.lru_cache(maxsize=None)
def _note_router_form(shape, kind, width, outputs, top, carries) -> None:
    """Which scorer a traced shape took: one zero-length program span a
    shape (the cache is the "once").  ``router`` is the scorer's kind (a
    span's own ``kind`` is its plane), ``carries`` whether a layer's
    state came in."""
    from scalerl_tpu.runtime import tracing

    with tracing.span(
        "router.form", kind="model", shape=list(shape), router=kind,
        width=width, outputs=outputs, top=top, carries=carries,
    ):
        pass


class MLPRouter(nn.Module):
    """The ``zaya`` family's router on a normed input ``g [B, T, d]`` and
    the state ``r [B, T, width]`` the previous layer's router handed up
    (None: no layer stands before this one)::

        r'     = g W_dn + b_dn  (+ gamma * r)                  gamma: learned [width]
        z      = RMSNorm(r');   logits = gelu(gelu(z W_1 + b_1) W_2 + b_2) W_3

    ``(logits [B, T, outputs] float32, r')``: the logits go to
    :class:`RoutedExperts` (its ``logits``), ``r'`` BEFORE its norm to the
    next layer's router.  Float32 throughout at ``HIGHEST`` matmul
    precision, whatever the blocks compute in: one pick decides a token's
    whole expert output, and the four products are 0.7 M parameters."""

    width: int
    outputs: int
    top: int
    norm_eps: float
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, g: jnp.ndarray, r: Optional[jnp.ndarray]):
        f32 = jnp.float32
        dense = functools.partial(
            nn.Dense, dtype=f32, param_dtype=self.param_dtype,
            precision=lax.Precision.HIGHEST,
        )
        if not self.is_initializing():
            _note_router_form(
                tuple(g.shape), "mlp", self.width, self.outputs, self.top, r is not None
            )
        with jax.named_scope("zaya_router"):
            state = dense(self.width, name="reduce")(g.astype(f32))
            if r is not None:
                state = state + self.param("carry_scale", nn.initializers.ones, (self.width,), f32) * r
            scale = self.param("norm_scale", nn.initializers.ones, (self.width,), f32)
            ms = jnp.mean(jnp.square(state), axis=-1, keepdims=True)
            z = state * lax.rsqrt(ms + self.norm_eps) * scale
            z = jax.nn.gelu(dense(self.width, name="fc1")(z), approximate=False)
            z = jax.nn.gelu(dense(self.width, name="fc2")(z), approximate=False)
            logits = dense(self.outputs, use_bias=False, name="score")(z)
        return logits, state


class RoutedExperts(nn.Module):
    """``[B, T, d] -> [B, T, d]``: router, exact top-k, SwiGLU experts.

    ``num_experts`` computed experts and ``zero_experts`` identity ones
    share one router of ``num_experts + zero_experts`` outputs; of the
    computed ones this module holds ``held`` (0: all), from
    ``first_expert`` on (see the module docstring).  ``logits [B, T, R]``:
    the router's scores where a scorer in front of this module made them
    (:class:`MLPRouter`); this module then has no router matrix."""

    num_experts: int
    experts_per_token: int
    width: int
    norm_topk_prob: bool = False
    zero_experts: int = 0
    held: int = 0
    first_expert: int = 0
    choice_bias: bool = False
    routed_scaling: float = 1.0
    scoring: str = "softmax"  # softmax | sigmoid
    act: str = "swiglu"  # swiglu (gate, up, down) | relu2 (up, down: no gate bank)
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h: jnp.ndarray, logits: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        B, T, d = h.shape
        E, k, f = self.num_experts, self.experts_per_token, self.width
        R = E + self.zero_experts  # the router's outputs
        held, first = self.held or E, self.first_expert
        if logits is None:
            router = self.param(
                "router", nn.initializers.lecun_normal(), (d, R), self.param_dtype
            )
        w_gate = None
        if self.act == "swiglu":
            w_gate = self.param("w_gate", _bank_init(), (held, d, f), self.param_dtype)
        w_up = self.param("w_up", _bank_init(), (held, d, f), self.param_dtype)
        w_down = self.param("w_down", _bank_init(), (held, f, d), self.param_dtype)
        x = h.reshape(B * T, d).astype(self.dtype)
        if logits is None:
            logits = jnp.dot(
                x, router.astype(self.dtype), preferred_element_type=jnp.float32
            )
        else:
            logits = logits.reshape(B * T, R).astype(jnp.float32)
        # float32, over all R: a softmax, or each output's own sigmoid
        if self.scoring == "sigmoid":
            probs = jax.nn.sigmoid(logits)
        else:
            probs = jax.nn.softmax(logits, axis=-1)
        if self.choice_bias:
            # the bias chooses and does not weigh
            bias = self.param(
                "router_bias", nn.initializers.zeros, (R,), jnp.float32
            )
            _, top_i = lax.top_k(probs + bias, k)
            top_p = jnp.take_along_axis(probs, top_i, axis=-1)
        else:
            top_p, top_i = lax.top_k(probs, k)
        if self.norm_topk_prob:
            # over all k picks, wherever their experts live
            top_p = top_p / (jnp.sum(top_p, axis=-1, keepdims=True) + 1e-20)
        if self.routed_scaling != 1.0:
            top_p = top_p * self.routed_scaling
        self.sow("intermediates", "router_probs", probs.reshape(B, T, R))
        self.sow("intermediates", "expert_ids", top_i.reshape(B, T, k))
        banks = tuple(
            None if w is None else w.astype(self.dtype)
            for w in (w_gate, w_up, w_down)
        )
        streamed = B * T <= STREAMED_MAX_TOKENS
        if held == R:  # every output is a bank held here
            y = (_streamed if streamed else _sorted)(x, top_p, top_i, *banks)
            return y.reshape(B, T, d).astype(self.dtype)
        here = (top_i >= first) & (top_i < first + held)
        local = jnp.where(here, top_i - first, held)  # ``held``: no bank here
        # the sorted form multiplies all ``N x k`` sorted rows however few
        # are held (see the module docstring), the streamed form ``N x
        # held``: never more where ``held <= k``, whatever the router does
        if streamed or held <= k:
            y = _streamed(x, top_p, local, *banks, expert_axis=True)
        else:
            y = _sorted(x, top_p, local, *banks, held_rows=here.reshape(-1))
        if self.zero_experts:
            # identity experts: ``w h``, computed where the token lives
            w_zero = jnp.sum(jnp.where(top_i >= E, top_p, 0.0), axis=-1)
            y = y + w_zero[:, None] * x.astype(jnp.float32)
        return y.reshape(B, T, d).astype(self.dtype)


class RouterBalance(NamedTuple):
    counts: jnp.ndarray  # [layers, E] int32: tokens each expert received
    aux_loss: jnp.ndarray  # E x sum_e f_e P_e over the masked tokens of all layers
    max_load: jnp.ndarray  # largest expert's share of assignments x E


def router_balance(
    intermediates: Mapping[str, Any], token_mask: jnp.ndarray
) -> RouterBalance:
    """What the router did with the tokens ``token_mask [B, T]`` names.

    ``intermediates`` is the collection a forward through routed blocks
    sowed (the scores are a sigmoid router's where it is one: ``P_e`` is
    then a mean score).  ``f_e`` is the share of the masked tokens' ``k`` assignments,
    all layers together, that went to expert ``e``; ``P_e`` the mean
    router probability of ``e`` over the same tokens and layers.  The loss
    is ``E x sum_e f_e P_e``: 1 when both are uniform.  The gradient
    reaches the router through ``P_e`` alone (the counts are integers).
    """
    # the stack's routed layers in order (a dense layer sows nothing),
    # then the multi-token-prediction module's layer where a forward ran it
    layers = [
        intermediates[name]["experts"]
        for name in sorted(
            (name for name in intermediates if name.startswith("block_")),
            key=lambda name: int(name.split("_")[1]),
        )
    ]
    if "mtp" in intermediates:
        layers.append(intermediates["mtp"]["block"]["experts"])
    mask = token_mask.astype(jnp.float32)
    counts, prob_sums = [], []
    for sown in layers:
        probs, ids = sown["router_probs"][0], sown["expert_ids"][0]
        E = probs.shape[-1]
        picked = jnp.sum(jax.nn.one_hot(ids, E, dtype=jnp.float32), axis=2)
        counts.append(jnp.sum(picked * mask[..., None], axis=(0, 1)))
        prob_sums.append(jnp.sum(probs * mask[..., None], axis=(0, 1)))
    counts = jnp.stack(counts)  # [layers, E]
    assignments = jnp.maximum(jnp.sum(counts), 1.0)
    tokens = jnp.maximum(jnp.sum(mask) * len(layers), 1.0)
    share = jnp.sum(counts, axis=0) / assignments
    mean_prob = jnp.sum(jnp.stack(prob_sums), axis=0) / tokens
    E = counts.shape[-1]
    return RouterBalance(
        counts=counts.astype(jnp.int32),
        aux_loss=E * jnp.sum(lax.stop_gradient(share) * mean_prob),
        max_load=E * jnp.max(share),
    )
