"""Versioned parameter distribution: the snapshot plane + the pull server.

Parity target: ``ParameterServer`` (``scalerl/hpc/parameter_server.py:4-33``)
— a push/pull weight holder — upgraded with what the reference lacked:
versioning (actors can skip a no-op pull), thread-safety (the reference had
no locking), and zero-copy host snapshots (device->host fetch happens once
per publish, not once per actor pull).  This is the "weight publication
without stalls" design of SURVEY.md §7: the learner publishes a snapshot;
actor pulls never block the train step.

Parameter distribution used to exist three times — ``ParameterServer``
push/pull, ``InferenceServer.push_params``, and the generation engines'
``push_params`` — each with its own tagging.  :class:`ParamSnapshotPlane`
is the ONE idiom all three now share (the ROADMAP snapshot-bus refactor):
a monotonic *generation* id, a device-side snapshot copy detached from the
learner's donated buffers, optional quantized storage
(``runtime/quantize.py``) with dequant-on-read cached per generation, a
``_place`` hook for sharding-aware re-placement, and a bounded
generation -> learner-step map backing the unified staleness definition
(learner steps behind the newest generation; docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import functools
import sys
import threading
from typing import Any, Dict, Optional, Tuple

import numpy as np


def jnp_copy(x):
    """Async device-side copy (new buffer, survives donation of ``x``).

    jax is referenced only if it is already loaded: fleet workers and
    spawn children publish/pull plain numpy trees and must not pay the
    multi-second jax import just to hold weights.
    """
    jax = sys.modules.get("jax")
    if jax is not None and isinstance(x, jax.Array):
        import jax.numpy as jnp

        return jnp.copy(x)
    return np.asarray(x)


def _to_host(tree):
    """Materialize a weight pytree on the host in ONE batched fetch.

    ``jax.device_get`` transfers the whole tree in one call (the per-leaf
    ``np.asarray`` alternative pays one blocking round trip per layer,
    dozens per pull).  Processes that
    never imported jax can only hold numpy trees; they keep the per-leaf
    stdlib walk, which is already host-local and free.
    """
    jax = sys.modules.get("jax")
    if jax is not None:
        return jax.device_get(tree)
    return _tree_map(np.asarray, tree)


def _tree_map(fn, tree):
    """``jax.tree_util.tree_map`` when jax is loaded; a stdlib-container
    fallback otherwise.  A process that never imported jax can only be
    holding dict/list/tuple/leaf weight trees (fleet workers), so the
    fallback is complete for them — and flax/custom pytrees always arrive
    with jax already in ``sys.modules``."""
    jax = sys.modules.get("jax")
    if jax is not None:
        return jax.tree_util.tree_map(fn, tree)
    if tree is None:
        return None  # match jax: None is empty structure, not a leaf
    if isinstance(tree, dict):
        return {k: _tree_map(fn, v) for k, v in tree.items()}
    if isinstance(tree, tuple) and hasattr(tree, "_fields"):
        # NamedTuple: positional-field constructor, not iterable-accepting
        return type(tree)(*(_tree_map(fn, v) for v in tree))
    if isinstance(tree, (list, tuple)):
        return type(tree)(_tree_map(fn, v) for v in tree)
    return fn(tree)


@functools.lru_cache(maxsize=None)
def _copy_program():
    """The one-program snapshot copy.  jit's own cache keys it by tree
    structure, shapes and shardings: a job's second push traces nothing."""
    import jax
    import jax.numpy as jnp

    def snapshot_copy_program(tree):  # the device program's name in a trace
        return jax.tree_util.tree_map(jnp.copy, tree)

    return jax.jit(snapshot_copy_program)


def snapshot_copy(tree) -> Tuple[Any, int]:
    """THE place a full-precision snapshot copy is made: fresh buffers that
    survive the donation of ``tree``'s, and the count of device programs
    the copy enqueued.

    A tree of ``jax.Array`` leaves that share one set of devices is copied
    by ONE compiled program (one host dispatch for the whole tree; a mesh
    learner's tree comes back on its mesh, for ``_place`` to move).
    Anything else keeps the leaf-by-leaf walk: one program a device-array
    leaf, none for numpy leaves, and the only route of a process that
    never imported jax.
    """
    jax = sys.modules.get("jax")
    arrays = 0
    if jax is not None:
        leaves = jax.tree_util.tree_leaves(tree)
        arrays = sum(isinstance(x, jax.Array) for x in leaves)
        if arrays and arrays == len(leaves):
            devices = leaves[0].sharding.device_set
            if all(x.sharding.device_set == devices for x in leaves):
                return _copy_program()(tree), 1
    return _tree_map(jnp_copy, tree), arrays


def tree_size(tree) -> Tuple[int, int]:
    """``(leaves, bytes)`` of a weight pytree, from shapes and dtypes alone
    (host values: nothing is read from a device)."""
    sizes: list = []
    _tree_map(lambda leaf: sizes.append(int(getattr(leaf, "nbytes", 0))), tree)
    return len(sizes), sum(sizes)


class ParamSnapshotPlane:
    """Generation-tagged parameter snapshots, optionally quantized.

    The shared distribution idiom (``ParameterServer``, ``InferenceServer``,
    the generation engines, the disagg learner): :meth:`push_params`
    publishes a snapshot copy with a monotonic generation bump — the copy
    (:func:`snapshot_copy`: one compiled program over a tree of device
    arrays) detaches the snapshot from the learner's donated buffers — and
    ``_snapshot_params`` hands consumers the serve-ready tree.
    ``last_push`` says what the newest push carried: ``bytes``, ``leaves``,
    ``generation``, ``programs`` (device programs the copy enqueued) and
    ``in_place`` (whether it was written over the retired snapshot, which
    only :class:`~scalerl_tpu.genrl.continuous.ContinuousEngine` does).

    ``quantize="int8" | "bf16"`` stores the ROADMAP's compressed broadcast
    format instead (``runtime/quantize.py``: per-leaf symmetric int8 with
    f32 scales, or a bf16 cast; 1-D f32-sensitive leaves pass through) and
    dequantizes ON READ, cached per generation — so a non-learner replica
    holds the small format at rest and pays one fused dequant per publish.

    Subclasses may override :meth:`_place` (sharding-aware re-placement:
    the ``InferenceServer`` re-places snapshots into the learner's live
    mesh layout) — it is applied to full-precision pushes AND to the
    dequantized read.  ``learner_step`` on a push records the bounded
    generation -> learner-step map that :meth:`staleness_steps` reads: the
    unified staleness definition is *learner steps behind the newest
    generation* (docs/OBSERVABILITY.md), and at push-per-step the
    generation delta equals it for entries that aged out of the map.

    jax-optional by design: full-precision pushes of numpy trees work in
    processes that never imported jax (:func:`snapshot_copy` falls back to
    the stdlib walk); only ``quantize=`` requires jax.
    """

    _GEN_STEPS_CAP = 64

    def _init_param_plane(self, params: Any) -> None:
        self._param_lock = threading.Lock()
        self._params = None
        if params is not None:
            self._params = self._copy_snapshot(params)[0]
        self._quantized = None
        self.generation = 0
        self._gen_steps: Dict[int, int] = {0: 0}
        self._latest_learner_step = 0
        # what the newest push carried, for the spans around it
        self.last_push: Dict[str, Any] = {}

    def _place(self, snapshot: Any) -> Any:
        """Placement hook: identity here; sharded consumers re-place the
        snapshot into their live layout (device-side reshard at worst)."""
        return snapshot

    def _copy_snapshot(self, params: Any) -> Tuple[Any, int, bool]:
        """``(placed snapshot, programs, in_place)`` of a full-precision
        push.  The plane always builds the new snapshot BESIDE the old one
        (``in_place`` False): a server's reader threads may still hold the
        tree they fetched before the push."""
        snapshot, programs = snapshot_copy(params)
        return self._place(snapshot), programs, False

    def push_params(
        self,
        params: Any,
        learner_step: Optional[int] = None,
        quantize: Optional[str] = None,
    ) -> int:
        """Publish fresh params (device-side copy or quantized snapshot +
        monotonic generation bump; no host transfer).  Returns the new
        generation."""
        if quantize is None:
            snapshot, programs, in_place = self._copy_snapshot(params)
            qsnap = None
        else:
            # round/clip/cast produce fresh buffers, so the quantized tree
            # is already detached from the learner's donated params
            from scalerl_tpu.runtime.quantize import quantize_tree

            snapshot, qsnap = None, quantize_tree(params, quantize)
            programs, in_place = 0, False  # no copy: its ops are not counted
        leaves, size = tree_size(params)
        with self._param_lock:
            self.generation += 1
            gen = self.generation
            self._params = snapshot
            self._quantized = qsnap
            self._record_step(gen, learner_step)
            self.last_push = {
                "bytes": size, "leaves": leaves, "generation": gen,
                "programs": programs, "in_place": in_place,
            }
            return gen

    def _record_step(self, gen: int, learner_step: Optional[int]) -> None:
        """Under the param lock: extend the bounded gen -> step map."""
        self._latest_learner_step = (
            int(learner_step) if learner_step is not None else gen
        )
        self._gen_steps[gen] = self._latest_learner_step
        while len(self._gen_steps) > self._GEN_STEPS_CAP:
            self._gen_steps.pop(min(self._gen_steps))

    def _snapshot_params(self) -> Tuple[Any, int]:
        with self._param_lock:
            if self._params is None and self._quantized is not None:
                # dequant-on-read, cached until the next push
                from scalerl_tpu.runtime.quantize import dequantize_tree

                self._params = self._place(dequantize_tree(self._quantized))
            return self._params, self.generation

    def staleness_steps(self, served_generation: int) -> float:
        """Lag (in learner steps) between the newest pushed params and the
        generation that produced a transition/sequence — the ONE staleness
        definition every plane reports (docs/OBSERVABILITY.md).  A
        generation older than the bounded map reports the generation delta,
        which equals learner steps at push-per-step."""
        with self._param_lock:
            newest = self._latest_learner_step
            served = self._gen_steps.get(
                int(served_generation), int(served_generation)
            )
        return float(max(newest - served, 0))


class ParameterServer(ParamSnapshotPlane):
    """The DCN fleet's pull endpoint over the shared snapshot plane.

    The bespoke version tagging this class used to carry is gone: the
    monotonic ``generation`` id, the snapshot copy, and the thread-safety
    contract all come from :class:`ParamSnapshotPlane` — ``version`` is an
    alias for the plane's generation.  What remains here is the fleet's
    *pull* shape: pullers always receive host (numpy) pytrees, with the
    device->host fetch paid once per publish (``to_host=True``) or lazily
    on first pull, cached per generation (``to_host=False``).
    """

    def __init__(self) -> None:
        self._init_param_plane(None)
        self._is_host = True

    @property
    def version(self) -> int:
        with self._param_lock:
            return self.generation

    def push(self, weights: Any, to_host: bool = True) -> int:
        """Publish new weights; returns the new version (generation).

        With ``to_host=True`` the pytree is fetched to numpy once here, so N
        actor pulls cost zero device traffic.  SEED-style learners whose
        actors run device inference should push with ``to_host=False``: the
        per-step publish is then the plane's device-side copy + generation
        bump (no host sync), and the numpy snapshot is materialized lazily —
        once, cached per version — only if some off-host consumer pulls.
        The device copy detaches the snapshot from the learner's buffers:
        mesh learn steps donate their state (``parallel/train_step.py``), so
        storing the live params would leave pullers holding deleted arrays.
        """
        if to_host:
            snapshot = _to_host(weights)
        else:
            snapshot = snapshot_copy(weights)[0]
        with self._param_lock:
            self.generation += 1
            self._params = snapshot
            self._quantized = None
            self._is_host = to_host
            self._record_step(self.generation, None)
            return self.generation

    def pull(self, have_version: int = -1) -> Tuple[Optional[Any], int]:
        """Return (numpy weights, version), or (None, version) if current.

        Pullers always receive host (numpy) pytrees regardless of how the
        weights were pushed — a ``to_host=False`` publish is materialized
        here on first pull and the conversion is cached for the version.
        Materialization happens *outside* the lock (it blocks on the device
        finishing the in-flight step), so a slow pull never stalls the
        learner's next ``push``.
        """
        with self._param_lock:
            if self._params is None or have_version == self.generation:
                return None, self.generation
            weights, version, is_host = (
                self._params, self.generation, self._is_host,
            )
        if not is_host:
            weights = _to_host(weights)
            with self._param_lock:
                if self.generation == version:
                    self._params = weights
                    self._is_host = True
        return weights, version
