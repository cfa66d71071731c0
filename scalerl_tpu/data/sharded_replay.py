"""Mesh-sharded prioritized replay: pod-scale Ape-X / R2D2 memory in HBM.

``BASELINE.md``'s Ape-X row is "replay sharded across TPU HBM — TPU pod
slice" (reference capability: ``scalerl/algorithms/apex/memory.py:11-138``
feeding DDP learner replicas).  The single-device buffers
(``data/prioritized.py`` / ``data/sequence_replay.py``) replicate their
state under pjit, so pod-scale capacity would overflow one chip's HBM.
Here the big planes shard over the mesh's ``dp``/``fsdp`` axes:

- **transitions** (Ape-X): the ENV-LANE axis shards — the actor batch is
  already lane-blocked, so inserts land on the shard that owns the lane;
- **sequences** (R2D2): the CAPACITY ring shards into ``S`` blocks.

Placement vs. semantics: inserts and priority write-backs run as ordinary
jitted global programs over sharded arrays — GSPMD lowers them to
shard-local masked scatters (indices are replicated scalars/vectors), so
the state VALUES are bit-identical to the unsharded buffers.  Only
*sampling* changes algorithmically (a global flat cumsum + searchsorted
would all-gather the whole priority plane): it runs under ``shard_map``,
each shard drawing ``B/S`` samples from its LOCAL ``p^alpha`` mass with
stratified targets, then normalizing GLOBALLY — priority mass and valid
counts by ``psum``, the importance-weight max by ``pmax``.

Sampling semantics (two-level stratified): the per-draw probability of
slot ``i`` on shard ``s`` is ``q_i = (1/S) * p_i / M_s``; importance
weights use exactly ``q_i``, so the PER estimator stays unbiased even when
shard masses ``M_s`` diverge, and as priorities mix (``M_s -> M/S``) the
distribution converges to the exact global ``p_i / M``.  This is the same
trade the reference's Ape-X makes with its per-actor buffers, with the
bias correction done exactly instead of ignored.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from scalerl_tpu.data.prioritized import (
    PrioritizedState,
    per_add,
    per_add_with_priorities,
    per_init,
    per_update_priorities,
)
from scalerl_tpu.data.replay import _logical_start, gather_transitions, transition_spec
from scalerl_tpu.data.sequence_replay import (
    SequenceReplayState,
    seq_add,
    seq_init,
    seq_update_priorities_keep_empty,
)
from scalerl_tpu.ops.pallas_per import proportional_sample


def replay_shard_axes(mesh) -> Tuple[str, ...]:
    """The mesh axes replay shards over: dp and fsdp (where present)."""
    return tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names)


def _shard_count(mesh, axes: Tuple[str, ...]) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _shard_index(axes: Tuple[str, ...], mesh) -> jnp.ndarray:
    """Linearized shard index inside shard_map (row-major over ``axes``)."""
    idx = jnp.zeros((), jnp.int32)
    for a in axes:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


# ---------------------------------------------------------------------------
# transitions (Ape-X): env-lane axis sharded


class ShardedPrioritizedReplay:
    """Lane-sharded transition PER over a device mesh.

    API mirrors ``PrioritizedReplayBuffer`` (save_to_memory /
    add_with_priorities / sample / update_priorities), so ``ApexTrainer``
    swaps it in when a mesh is active.  ``num_envs`` must divide by the
    mesh's dp*fsdp extent; lanes are blocked contiguously per shard.
    """

    def __init__(
        self,
        obs_shape: Tuple[int, ...],
        capacity: int,
        mesh,
        num_envs: int,
        obs_dtype: jnp.dtype = jnp.float32,
        alpha: float = 0.6,
        n_step: int = 1,
        gamma: float = 0.99,
        extra_fields: Optional[Dict[str, Tuple[Tuple[int, ...], jnp.dtype]]] = None,
        action_shape: Tuple[int, ...] = (),
        action_dtype: jnp.dtype = jnp.int32,
        sample_method: str = "auto",
    ) -> None:
        from scalerl_tpu.ops.pallas_per import resolve_sample_method

        # "auto" resolves NOW (env var / backend at construction), not at
        # first trace of the cached sample program
        self.sample_method = resolve_sample_method(sample_method)
        self.mesh = mesh
        self.axes = replay_shard_axes(mesh)
        if not self.axes:
            raise ValueError(
                f"mesh {mesh.axis_names} has neither a 'dp' nor an 'fsdp' "
                "axis to shard replay lanes over"
            )
        self.n_shards = _shard_count(mesh, self.axes)
        if num_envs % self.n_shards != 0:
            raise ValueError(
                f"num_envs ({num_envs}) must divide by the mesh's dp*fsdp "
                f"extent ({self.n_shards}) to shard the lane axis"
            )
        self.spec = dict(transition_spec(
            obs_shape, obs_dtype, action_dtype=action_dtype,
            action_shape=action_shape, include_boundary=n_step > 1,
        ))
        if extra_fields:
            self.spec.update(extra_fields)
        self.capacity = capacity
        self.num_envs = num_envs
        self.alpha = alpha
        self.n_step = n_step
        self.gamma = gamma

        def state_spec(x):
            # [capacity, num_envs, ...] planes shard on the lane axis;
            # pos/size/max_priority scalars replicate
            if getattr(x, "ndim", 0) >= 2:
                return P(None, self.axes)
            return P()

        state = per_init(self.spec, capacity, num_envs)
        self._state_spec = jax.tree_util.tree_map(state_spec, state)
        self._state_sh = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), self._state_spec
        )
        self.state = jax.device_put(state, self._state_sh)

        lane_sh = NamedSharding(mesh, P(self.axes))

        def step_sh(x):
            return NamedSharding(mesh, P(self.axes, *([None] * (x.ndim - 1))))

        # add/update are ordinary global programs over sharded state: GSPMD
        # lowers the replicated-index scatters to shard-local writes, so
        # state values match the unsharded buffer exactly
        self._add = jax.jit(per_add, donate_argnums=0)
        self._add_prio = jax.jit(per_add_with_priorities, donate_argnums=0)
        self._update = jax.jit(per_update_priorities, donate_argnums=0)
        self._lane_sh = lane_sh
        self._step_sh = step_sh
        self._sample_cache: Dict[int, Any] = {}

    def __len__(self) -> int:
        return int(self.state.replay.size) * self.num_envs

    def _coerce_step(self, step: Dict[str, Any]) -> Dict[str, jnp.ndarray]:
        step = {k: jnp.asarray(v) for k, v in step.items()}
        if "boundary" in self.spec:
            step.setdefault("boundary", step["done"])
        else:
            step.pop("boundary", None)
        out = {}
        for k, v in step.items():
            want = (self.num_envs,) + tuple(self.spec[k][0])
            if v.shape != want:
                v = v.reshape(want)
            out[k] = jax.device_put(v.astype(self.spec[k][1]), self._step_sh(v))
        return out

    def save_to_memory(self, obs, next_obs, action, reward, done, boundary=None) -> None:
        step = {"obs": obs, "next_obs": next_obs, "action": action,
                "reward": reward, "done": done}
        if boundary is not None:
            step["boundary"] = boundary
        self.state = self._add(self.state, self._coerce_step(step))

    def add_with_priorities(self, step: Dict[str, Any], priorities) -> None:
        p = jax.device_put(
            jnp.maximum(jnp.asarray(priorities, jnp.float32), 1e-6), self._lane_sh
        )
        self.state = self._add_prio(self.state, self._coerce_step(step), p)

    def update_priorities(self, indices, priorities) -> None:
        self.state = self._update(
            self.state, jnp.asarray(indices), jnp.asarray(priorities, jnp.float32)
        )

    # -- sampling ------------------------------------------------------
    def _build_sample(self, batch_size: int):
        if batch_size % self.n_shards != 0:
            raise ValueError(
                f"batch_size ({batch_size}) must divide by the replay shard "
                f"count ({self.n_shards})"
            )
        b_local = batch_size // self.n_shards
        axes = self.axes
        mesh = self.mesh
        n_shards = self.n_shards
        num_envs = self.num_envs
        n_step, gamma, alpha = self.n_step, self.gamma, self.alpha
        method = self.sample_method  # resolved at construction, pinned here

        def local_sample(state: PrioritizedState, key, beta):
            # state leaves here are the LOCAL blocks: [capacity, envs/S, ...]
            shard = _shard_index(axes, mesh)
            key = jax.random.fold_in(key, shard)
            capacity, local_envs = state.priorities.shape
            start = _logical_start(state.replay, capacity)
            size = state.replay.size

            logical_prio = jnp.roll(state.priorities, -start, axis=0)
            valid = (jnp.arange(capacity) < jnp.maximum(size - n_step + 1, 1))[:, None]
            p = jnp.where(valid, logical_prio, 0.0) ** alpha
            p = jnp.where(valid, jnp.maximum(p, 1e-12), 0.0)
            flat_p = p.reshape(-1)
            m_local = jnp.sum(flat_p)

            u = jax.random.uniform(key, (b_local,))
            targets = (jnp.arange(b_local) + u) / b_local * m_local
            flat_logical = proportional_sample(flat_p, targets, method=method)

            # per-draw probability under the two-level scheme
            q = flat_p[flat_logical] / jnp.maximum(m_local, 1e-12) / n_shards
            n_valid_local = jnp.sum(valid) * local_envs
            n_valid = jax.lax.psum(n_valid_local, axes).astype(jnp.float32)
            weights = (jnp.maximum(n_valid, 1.0) * jnp.maximum(q, 1e-12)) ** (-beta)
            wmax = jax.lax.pmax(jnp.max(weights), axes)
            weights = weights / jnp.maximum(wmax, 1e-12)

            logical = flat_logical // local_envs
            env_local = flat_logical % local_envs
            batch = gather_transitions(state.replay, logical, env_local, n_step, gamma)
            # rebase the physical index from local to GLOBAL lane numbering
            row0 = batch["indices"] // local_envs
            env_l = batch["indices"] % local_envs
            batch["indices"] = row0 * num_envs + shard * local_envs + env_l
            batch["weights"] = weights
            return batch

        # out: every leaf is [b_local, ...] per shard -> global [B, ...];
        # specs mirror gather_transitions' return structure (standard fields
        # + n_steps/indices + pass-through extras, no boundary) + weights
        def field_spec(name: str) -> P:
            return P(axes, *([None] * len(self.spec[name][0])))

        out_specs = {
            "obs": field_spec("obs"),
            "next_obs": field_spec("next_obs"),
            "action": field_spec("action"),
            "reward": P(axes),
            "done": P(axes),
            "n_steps": P(axes),
            "indices": P(axes),
            "weights": P(axes),
        }
        standard = {"obs", "next_obs", "action", "reward", "done", "boundary"}
        for name in self.spec:
            if name not in standard:
                out_specs[name] = field_spec(name)

        fn = shard_map(
            local_sample,
            mesh=mesh,
            in_specs=(self._state_spec, P(), P()),
            out_specs=out_specs,
            check_vma=False,
        )
        return jax.jit(fn)

    def sample(self, batch_size: int, beta: float = 0.4, key: Optional[jax.Array] = None):
        if key is None:
            key = jax.random.PRNGKey(np.random.randint(0, 2**31 - 1))
        fn = self._sample_cache.get(batch_size)
        if fn is None:
            fn = self._sample_cache[batch_size] = self._build_sample(batch_size)
        return fn(self.state, key, jnp.float32(beta))


# ---------------------------------------------------------------------------
# sequences (R2D2): capacity ring sharded


def seq_sample_sharded_local(
    state: SequenceReplayState,
    key: jax.Array,
    b_local: int,
    *,
    axes: Tuple[str, ...],
    n_shards: int,
    local_capacity: int,
    alpha: float = 0.6,
    beta: float = 0.4,
    global_size: Optional[jnp.ndarray] = None,
    method: str = "auto",
):
    """Per-shard sequence sample; call INSIDE ``shard_map`` over ``axes``.

    ``state`` leaves are the local capacity blocks ``[capacity/S, ...]``
    (``pos``/``size`` replicated).  Returns ``(fields, core, idx, weights)``
    with ``idx`` rebased to GLOBAL slot numbering; weights are globally
    normalized (``psum`` mass semantics via exact per-draw ``q``, ``pmax``
    for the max-weight divisor).  Factored out so the fused device-R2D2
    iteration can embed it in its own shard_map (``trainer/r2d2_device.py``).

    ``global_size``: total live sequences across all shards for the IS
    weight's ``N``.  Default ``state.size`` — correct when the cursor walks
    the GLOBAL ring (``ShardedSequenceReplay``); pass ``psum(size, axes)``
    when each shard keeps an independent local ring (fused loop).

    ``method``: long-lived callers pass the concrete search method they
    resolved at construction (``resolve_sample_method``), so env-var /
    backend changes after the first trace are not silently ignored.
    """
    shard = jnp.zeros((), jnp.int32)
    for a in axes:
        shard = shard * jax.lax.psum(1, a) + jax.lax.axis_index(a)
    key = jax.random.fold_in(key, shard)

    scaled = jnp.power(state.priorities, alpha)  # empty slots: 0^a = 0
    m_local = jnp.sum(scaled)
    u = jax.random.uniform(key, (b_local,))
    targets = (jnp.arange(b_local) + u) / b_local * m_local
    idx = proportional_sample(scaled, targets, method=method)

    q = scaled[idx] / jnp.maximum(m_local, 1e-9) / n_shards
    size = state.size if global_size is None else global_size
    n = jnp.maximum(size.astype(jnp.float32), 1.0)
    weights = jnp.power(n * jnp.maximum(q, 1e-9), -beta)
    # a shard whose block the ring hasn't reached yet (or an empty slot at a
    # cumsum edge) has zero mass there: its draws are garbage rows. Zero
    # their IS weights — the weighted loss then ignores them — and keep them
    # out of the global max normalization, instead of letting the 1e-9 floor
    # win the pmax and crush every real sample's weight (review r4).
    weights = jnp.where(q > 0, weights, 0.0)
    wmax = jax.lax.pmax(jnp.max(weights), axes)
    weights = weights / jnp.maximum(wmax, 1e-9)

    fields = {name: arr[idx] for name, arr in state.storage.items()}
    core = tuple((c[idx], h[idx]) for c, h in state.core)
    return fields, core, shard * local_capacity + idx, weights


class ShardedSequenceReplay:
    """Capacity-sharded sequence PER over a device mesh (R2D2 at pod scale).

    Same surface as the ``seq_*`` functional API via methods: ``add`` /
    ``sample`` / ``update_priorities``.  The ring cursor walks the GLOBAL
    capacity, so inserts sweep shard blocks in turn (values identical to
    the unsharded ring); sampling draws ``B/S`` per shard.
    """

    def __init__(
        self,
        field_shapes: Dict[str, Tuple[Tuple[int, ...], Any]],
        core_shapes: Tuple[Tuple[int, ...], ...],
        capacity: int,
        mesh,
        alpha: float = 0.6,
        beta: float = 0.4,
        sample_method: str = "auto",
    ) -> None:
        from scalerl_tpu.ops.pallas_per import resolve_sample_method

        # construction-time resolution (see PrioritizedReplayBuffer)
        self.sample_method = resolve_sample_method(sample_method)
        self.mesh = mesh
        self.axes = replay_shard_axes(mesh)
        if not self.axes:
            raise ValueError(
                f"mesh {mesh.axis_names} has neither a 'dp' nor an 'fsdp' "
                "axis to shard sequence capacity over"
            )
        self.n_shards = _shard_count(mesh, self.axes)
        if capacity % self.n_shards != 0:
            raise ValueError(
                f"capacity ({capacity}) must divide by the mesh's dp*fsdp "
                f"extent ({self.n_shards}) to shard the ring"
            )
        self.capacity = capacity
        self.alpha = alpha
        self.beta = beta

        def state_spec(x):
            if getattr(x, "ndim", 0) >= 1:
                return P(self.axes, *([None] * (x.ndim - 1)))
            return P()

        state = seq_init(field_shapes, core_shapes, capacity)
        self._state_spec = jax.tree_util.tree_map(state_spec, state)
        self._state_sh = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), self._state_spec
        )
        self.state = jax.device_put(state, self._state_sh)
        # global programs over sharded state (see module docstring)
        self._add = jax.jit(seq_add, donate_argnums=0)
        # keep-empty write-back: zero-weight garbage draws from unreached
        # shard blocks must not resurrect empty slots into the distribution
        self._update = jax.jit(seq_update_priorities_keep_empty, donate_argnums=0)
        self._sample_cache: Dict[int, Any] = {}

    def __len__(self) -> int:
        return int(self.state.size)

    def add(self, batch: Dict[str, jnp.ndarray], core: Tuple, priorities) -> None:
        self.state = self._add(
            self.state, batch, core, jnp.asarray(priorities, jnp.float32)
        )

    def update_priorities(self, idx, priorities) -> None:
        self.state = self._update(
            self.state, jnp.asarray(idx), jnp.asarray(priorities, jnp.float32)
        )

    def _build_sample(self, batch_size: int):
        if batch_size % self.n_shards != 0:
            raise ValueError(
                f"batch_size ({batch_size}) must divide by the replay shard "
                f"count ({self.n_shards})"
            )
        b_local = batch_size // self.n_shards
        axes, n_shards = self.axes, self.n_shards
        local_capacity = self.capacity // self.n_shards
        alpha, beta = self.alpha, self.beta

        method = self.sample_method

        def local(state, key):
            return seq_sample_sharded_local(
                state, key, b_local,
                axes=axes, n_shards=n_shards, local_capacity=local_capacity,
                alpha=alpha, beta=beta, method=method,
            )

        # fields/core: [b_local, T1/dim, ...] -> sharded dim 0; idx/weights 1-D
        fields_spec = {
            name: P(axes, *([None] * (arr.ndim - 1)))
            for name, arr in self.state.storage.items()
        }
        core_spec = tuple((P(axes, None), P(axes, None)) for _ in self.state.core)
        out_specs = (fields_spec, core_spec, P(axes), P(axes))

        fn = shard_map(
            local,
            mesh=self.mesh,
            in_specs=(self._state_spec, P()),
            out_specs=out_specs,
            check_vma=False,
        )
        return jax.jit(fn)

    def sample(self, batch_size: int, key: Optional[jax.Array] = None):
        if key is None:
            key = jax.random.PRNGKey(np.random.randint(0, 2**31 - 1))
        fn = self._sample_cache.get(batch_size)
        if fn is None:
            fn = self._sample_cache[batch_size] = self._build_sample(batch_size)
        return fn(self.state, key)
