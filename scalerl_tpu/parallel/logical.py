"""Named logical-axis sharding rules for the big-model policy families.

The heuristic ``infer_param_spec`` (``parallel/sharding.py``) shards
"whatever dims happen to divide" — fine for conv/fc stacks, wrong for a
transformer, where the *meaning* of each dim decides its axis: attention
heads, the MLP hidden, and the vocab/action head shard over the model axis
while embeddings and residual-stream dims replicate (Megatron layout).
This module is the declarative counterpart, the SNIPPETS.md patterns made
load-bearing:

- snippet [3]'s ``DEFAULT_RULES`` table — logical axis name -> mesh axis —
  becomes :data:`LOGICAL_RULES` with ``"mp"`` as the model axis;
- parameter leaves are classified by their trailing path names (module +
  param), so the same table covers the raw params, the optimizer moments
  (whose pytree paths mirror the params), and any wrapper state without
  model surgery;
- snippet [2]'s ``make_shard_and_gather_fns`` — per-leaf pjit'd placement
  and fetch functions built from partition specs — is
  :func:`make_shard_and_gather_fns`, used by the sharded checkpoint path.

Divisibility guard: a rule only shards a dim when the mesh extent divides
it; otherwise that dim silently replicates (a 6-action policy head on
``mp=4`` replicates instead of erroring — the rule table describes *big*
models, small heads degrade gracefully).
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from scalerl_tpu.parallel.sharding import _path_names, holds_axis

# The model-parallel mesh axis of the dp×mp learner plane.
MP_AXIS = "mp"

# Logical axis -> mesh axis (None = replicated), snippet [3] shape.
LOGICAL_RULES: Dict[str, Optional[str]] = {
    "batch": "dp",
    "embed": None,   # residual stream / d_model stays replicated
    "heads": MP_AXIS,  # fused qkv output (num_heads * head_dim)
    "mlp": MP_AXIS,    # MLP hidden (mlp_ratio * d_model)
    "vocab": MP_AXIS,  # policy head output (actions / tokens)
    "experts": MP_AXIS,  # MoE expert-leading tensors (ep folded onto mp)
}

# Trailing-path-name -> per-dim logical axes.  Keys are matched against the
# last one or two path components of each leaf ((module, param) first, then
# the bare leaf name), which makes the table apply equally to
# ``params.block_0.qkv.kernel`` and the RMSProp moment
# ``opt_state[1].nu.params.block_0.qkv.kernel``.
PARAM_LOGICAL_AXES: Dict[Tuple[str, ...], Tuple[Optional[str], ...]] = {
    ("qkv", "kernel"): ("embed", "heads"),
    ("proj", "kernel"): ("heads", "embed"),
    ("mlp_in", "kernel"): ("embed", "mlp"),
    ("mlp_in", "bias"): ("mlp",),
    ("mlp_out", "kernel"): ("mlp", "embed"),
    ("mlp_out", "bias"): ("embed",),
    ("policy_head", "kernel"): ("embed", "vocab"),
    ("policy_head", "bias"): ("vocab",),
    ("value_head", "kernel"): ("embed", None),
    # MoE expert banks: the leading expert dim shards over the model axis
    # (the GShard layout — XLA derives the token all-to-alls from it).
    # The per-expert matmul dims stay unsharded: with ep folded onto mp, a
    # second mp entry would double-map the axis (and expert-internal
    # sharding buys nothing until experts outgrow a chip).
    ("w_in",): ("experts", "embed", None),
    ("w_out",): ("experts", None, "embed"),
    # The token model's routed SwiGLU experts (models/routed_ffn.py): the
    # three banks lead with the expert dim like the ones above; the router
    # is replicated (every shard scores every token against all experts).
    # The q/k norm scales of the olmoe block match no rule and replicate:
    # the norm reduces over the projection's whole width, which GSPMD
    # completes across ``mp`` with an all-reduce of the sum of squares.
    ("w_gate",): ("experts", "embed", None),
    ("w_up",): ("experts", "embed", None),
    ("w_down",): ("experts", None, "embed"),
    ("experts", "router"): ("embed", None),
    # The longcat block (models/transformer.py): the latent attention's
    # up-projections lead out to the heads and shard there, its two
    # down-projections (``q_a``, ``kv_a``: ranks, and a rotary part every
    # head shares) replicate, as do the norms over those ranks and the
    # router's choice bias; the dense SwiGLU FFNs shard their hidden width
    # like the GPT-2 MLP.  ``proj`` is the rule above.
    ("q_a", "kernel"): ("embed", None),
    ("q_b", "kernel"): (None, "heads"),
    ("kv_a", "kernel"): ("embed", None),
    ("kv_b",): (None, "heads"),
    ("gate", "kernel"): ("embed", "mlp"),
    ("up", "kernel"): ("embed", "mlp"),
    ("down", "kernel"): ("mlp", "embed"),
    # The joyai stack: its leading dense FFN (``ffn``) and each routed
    # layer's always-on shared expert (``shared``) are SwiGLUs whose three
    # kernels the rules above name (they match by the last two path
    # names), and the plain layer's latent attention (``attn``) is the
    # longcat one.  The multi-token-prediction module (``mtp/``) holds a
    # layer of the same kinds under ``mtp/block`` and one matrix of its
    # own, which takes the concatenated ``[trunk ; embedding]`` (2 x embed)
    # back to the residual stream and replicates like the stream.
    ("eh_proj", "kernel"): (None, "embed"),
    # The nemotron_h stack (single-mixer layers): grouped-head attention
    # projects ``q`` apart from ``kv``; ``q`` leads out to the heads like
    # ``qkv``, the fused ``kv`` of a few key/value heads replicates (a split
    # would fall between K and V, not between heads).  A Mamba-2 mixer's
    # ``in_proj`` output is ``[z | x | B | C | dt]``, segments of different
    # widths that no even split respects, so it replicates, with the
    # convolution's taps and the per-head ``A_log`` / ``D`` / ``dt_bias``
    # (no rule); ``out_proj`` takes the heads' outputs back to the stream
    # and shards its input like ``proj``.  The relu2 experts and shared
    # expert have ``up`` / ``down`` / ``w_up`` / ``w_down`` and no gate.
    ("q", "kernel"): ("embed", "heads"),
    ("kv", "kernel"): ("embed", None),
    ("in_proj", "kernel"): ("embed", None),
    ("out_proj", "kernel"): ("heads", "embed"),
    # The qwen3_next stack: its attention's ``q`` (a head ``[q | gate]``)
    # and ``kv`` take the two rules above; a Gated DeltaNet mixer's
    # ``in_proj`` (``[q | k | v | z]``) and ``out_proj`` take the Mamba
    # mixer's, and its ``ba_proj`` (``[beta | a]``, two values a head) and
    # the shared expert's scalar ``shared_gate`` replicate like them.
    ("ba_proj", "kernel"): ("embed", None),
    ("shared_gate", "kernel"): ("embed", None),
    # The zaya stack: its compressed convolutional attention's ``q`` and
    # ``proj`` take the rules above; ``k`` (two key heads) and the two
    # half-width value projections ``v1`` / ``v2`` replicate like ``kv``,
    # with the convolutions' taps, the grouped taps (a head's own matrix:
    # sharding them would follow ``q``'s heads, which no mesh here asks
    # for), the key temperature and the residual merges' vectors (no
    # rule).  The router is an MLP of width 256 (``reduce``, ``fc1``,
    # ``fc2``, ``score``), replicated like every other router.
    ("k", "kernel"): ("embed", None),
    ("v1", "kernel"): ("embed", None),
    ("v2", "kernel"): ("embed", None),
    ("reduce", "kernel"): ("embed", None),
    # The xing4 stack: joyai's rules, on a residual stream of rows ``[B, T,
    # n, d]``.  The stream's row axis replicates and its ``d`` axis takes
    # what a one-row ``x``'s takes (:func:`activation_constraint` pins the
    # batch axis and replicates every other, whatever the rank).  A
    # hyper-connection's ``phi`` ``[n d, n (n + 2)]`` contracts the
    # flattened stream (its long axis is ``n d``: the stream's, replicated
    # like ``embed``) into 24 columns; its ``scale``, ``b`` and ``alpha``
    # match no rule and replicate.
    ("phi",): ("embed", None),
}


def logical_to_spec(
    axes: Tuple[Optional[str], ...],
    shape: Tuple[int, ...],
    mesh: Mesh,
    rules: Optional[Dict[str, Optional[str]]] = None,
) -> P:
    """Resolve per-dim logical axes into a PartitionSpec on ``mesh``.

    A dim only shards when its mesh axis has extent > 1 AND divides the dim
    size; everything else replicates.
    """
    rules = rules if rules is not None else LOGICAL_RULES
    parts = []
    used = set()  # a mesh axis may shard at most one dim per tensor
    for dim, logical in enumerate(axes):
        mesh_axis = rules.get(logical) if logical is not None else None
        n = mesh.shape.get(mesh_axis, 1) if mesh_axis else 1
        if mesh_axis and mesh_axis not in used and n > 1 and shape[dim] % n == 0:
            parts.append(mesh_axis)
            used.add(mesh_axis)
        else:
            parts.append(None)
    return P(*parts)


def _match_axes(path: Tuple[Any, ...]) -> Optional[Tuple[Optional[str], ...]]:
    names = _path_names(path)
    for key in (tuple(names[-2:]), (names[-1],) if names else ()):
        if key and key in PARAM_LOGICAL_AXES:
            return PARAM_LOGICAL_AXES[key]
    return None


def mp_param_spec(
    path: Tuple[Any, ...],
    leaf: Any,
    mesh: Mesh,
    rules: Optional[Dict[str, Optional[str]]] = None,
) -> P:
    """PartitionSpec for one param/opt-state leaf under the logical rules.

    Unmatched leaves (embeddings, LayerNorm scales, counters, schedule
    state) replicate — safe by construction.
    """
    axes = _match_axes(path)
    if axes is None or not hasattr(leaf, "ndim") or leaf.ndim != len(axes):
        return P()
    return logical_to_spec(axes, leaf.shape, mesh, rules)


def with_update_axis(spec: P, shape: Tuple[int, ...], mesh: Mesh, axis: str) -> P:
    """``spec`` with mesh axis ``axis`` on one more dimension: the largest
    one that the axis's extent divides and that no mesh axis holds yet (the
    first of equals).  ``spec`` itself where the extent is 1 or no such
    dimension is left (a scalar, an odd width, a vector ``mp`` already has):
    that leaf stays replicated over ``axis``."""
    n = mesh.shape.get(axis, 1)
    parts = list(spec) + [None] * (len(shape) - len(spec))
    free = [d for d, held in enumerate(parts) if held is None and shape[d] % n == 0]
    if n <= 1 or not free:
        return spec
    parts[max(free, key=lambda d: shape[d])] = axis
    return P(*parts)


def mp_param_sharding(
    tree: Any,
    mesh: Mesh,
    rules: Optional[Dict[str, Optional[str]]] = None,
    update_axis: Optional[str] = None,
    update_under: Optional[Tuple[str, ...]] = ("opt_state",),
) -> Any:
    """Per-leaf ``NamedSharding`` pytree for a train state under the
    logical rule table (heads/mlp/vocab/experts over ``mp``).

    ``update_axis`` names the mesh axis the WEIGHT UPDATE is sharded over
    (cross-replica weight-update sharding, Xu et al. arXiv 2004.13336; ZeRO
    stage 1): every leaf whose path passes through a field of
    ``update_under`` (the optimiser's moments; every leaf of ``tree`` where
    it is None, for a gradient tree) takes that axis too, on the dimension
    :func:`with_update_axis` names.  Without it, and on a mesh whose
    ``update_axis`` has extent 1, every leaf is replicated over ``dp`` as
    before."""

    def leaf_sharding(path, x):
        spec = mp_param_spec(path, x, mesh, rules)
        if update_axis is not None and (
            update_under is None or set(update_under) & set(_path_names(path))
        ):
            spec = with_update_axis(spec, getattr(x, "shape", ()), mesh, update_axis)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(leaf_sharding, tree)


class UpdateSharding(NamedTuple):
    """The learn step's two layout changes where the weight update is
    sharded over a mesh axis: ``scatter`` takes a parameter-shaped tree (the
    gradients) to the moments' layout, which makes their reduction over that
    axis a reduce-scatter and everything computed from them a shard's work;
    ``gather`` takes the updated parameters back to the parameters' layout
    (an all-gather of each updated shard)."""

    scatter: Callable[[Any], Any]
    gather: Callable[[Any], Any]


def update_sharding(
    params: Any,
    mesh: Mesh,
    axis: str = "dp",
    rules: Optional[Dict[str, Optional[str]]] = None,
) -> Optional[UpdateSharding]:
    """:class:`UpdateSharding` closures for ``params`` on ``mesh``, or None
    where ``axis`` has extent 1 (one replica has nobody to share with).
    Like :func:`activation_constraint` they carry the mesh inside each
    ``NamedSharding`` and work under a plain ``jax.jit``."""
    if mesh.shape.get(axis, 1) <= 1:
        return None
    at_rest = mp_param_sharding(params, mesh, rules)
    sharded = mp_param_sharding(params, mesh, rules, update_axis=axis, update_under=None)
    return UpdateSharding(
        scatter=lambda tree: jax.lax.with_sharding_constraint(tree, sharded),
        gather=lambda tree: jax.lax.with_sharding_constraint(tree, at_rest),
    )


def update_sharding_counts(moments: Any, shardings: Any, axis: str) -> Dict[str, Any]:
    """What a weight update sharded over ``axis`` did to the optimiser's
    state, for the ``learn.update_sharding`` span: how many leaves took the
    axis, how many it divides no free dimension of (they stay replicated),
    and the state's bytes a device with the axis and without."""
    leaves = jax.tree_util.tree_leaves(moments)
    layouts = jax.tree_util.tree_leaves(shardings)
    extent = layouts[0].mesh.shape[axis]
    sharded, before, after = 0, 0, 0
    for x, sh in zip(leaves, layouts, strict=True):
        held = holds_axis(sh, axis)
        local = math.prod(sh.shard_shape(x.shape)) * x.dtype.itemsize
        sharded += held
        after += local
        before += local * (extent if held else 1)
    return dict(
        axis=axis, extent=extent, leaves_sharded=sharded,
        leaves_replicated=len(leaves) - sharded,
        moment_bytes_per_device_before=before, moment_bytes_per_device_after=after,
        params_at_rest="gathered",
    )


def has_mp_params(tree: Any) -> bool:
    """True when the pytree carries leaves the logical rule table knows how
    to shard — i.e. the model is one of the mp-aware families
    (transformer/MoE policies)."""
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        axes = _match_axes(path)
        if axes is not None and getattr(leaf, "ndim", -1) == len(axes):
            return True
    return False


def activation_constraint(mesh: Mesh, batch_axis: str = "dp") -> Callable:
    """``with_sharding_constraint`` closure for inter-layer activations.

    Pins ``[B, ...]`` tensors to batch-over-``dp``, replicated over ``mp``
    — the residual stream layout between transformer blocks.  GSPMD then
    derives the per-block reshard (split on heads/mlp inside the block,
    rejoin at the residual add) from the weight shardings alone, instead of
    guessing a layout for the whole network and paying involuntary
    reshards.  Carries the mesh inside each ``NamedSharding``, so it works
    under plain ``jax.jit`` with no ambient mesh context.
    """

    def constrain(x):
        if not hasattr(x, "ndim") or x.ndim == 0:
            return x
        spec = P(*([batch_axis] + [None] * (x.ndim - 1)))
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return constrain


def make_shard_and_gather_fns(shardings: Any) -> Tuple[Any, Any]:
    """Per-leaf placement/fetch functions from a ``NamedSharding`` pytree
    (the SNIPPETS.md [2] pattern, pjit identity with pinned out/in specs).

    Returns ``(shard_fns, gather_fns)`` pytrees matching ``shardings``:
    ``shard_fns`` place a host/device leaf into its mesh layout;
    ``gather_fns`` fetch a sharded leaf back to one host ndarray (used by
    the shard-aware checkpoint path to digest and restore state that never
    lives unsharded on any single chip).
    """

    def make_shard_fn(sh):
        placed = jax.jit(lambda x: x, out_shardings=sh)
        return lambda x: placed(x)

    def make_gather_fn(sh):
        gathered = jax.jit(
            lambda x: x, out_shardings=NamedSharding(sh.mesh, P())
        )
        return lambda x: jax.device_get(gathered(x))

    shard_fns = jax.tree_util.tree_map(make_shard_fn, shardings)
    gather_fns = jax.tree_util.tree_map(make_gather_fn, shardings)
    return shard_fns, gather_fns
