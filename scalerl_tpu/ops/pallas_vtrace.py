"""Fused V-trace targets as a single Pallas kernel (scan-free recursion).

The reference implementation (``ops/vtrace.py``) runs the backward-time
recursion ``acc_t = delta_t + discount_t * c_t * acc_{t+1}`` as a
``lax.scan(reverse=True)`` — T sequential XLA loop steps, each paying loop
overhead around a [B]-wide vector op, with the rho/c clipping and the two
delta/advantage passes as separate fused regions around it.  This kernel
fuses the WHOLE computation — exp, clipping, deltas, the backward
recursion, and the policy-gradient advantages — into one Pallas program:
the [T, B] planes live in VMEM end to end and the recursion is a
``fori_loop`` of VPU row ops with no loop-carried HBM traffic.

Numerics: every arithmetic step matches the reference op exactly (same
order, same f32), so the interpret-mode CPU fallback agrees with
``vtrace_from_importance_weights`` to float32 round-off — asserted at
1e-5 in ``tests/test_ops.py``.  Gradients never flow through V-trace (the
reference ``stop_gradient``s its outputs, matching the torch
``no_grad``), so the kernel needs no VJP rule; inputs are detached before
the call to keep AD from tracing into it.

Selection: ``RLArguments.use_pallas`` routes ``agents/impala.py``'s loss
through :func:`vtrace_from_importance_weights_pallas`; ``interpret=None``
auto-resolves to interpreter mode off-TPU so the same flag works in CPU
tests and TPU runs.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp


def _vtrace_kernel(
    log_rhos_ref,
    discounts_ref,
    rewards_ref,
    values_ref,
    bootstrap_ref,
    vs_ref,
    pg_ref,
    acc_scratch,
    rho_clip: Optional[float],
    pg_rho_clip: Optional[float],
    c_clip: float,
):
    """One grid step: the full [T, B] V-trace computation in VMEM."""
    T = log_rhos_ref.shape[0]

    rhos = jnp.exp(log_rhos_ref[:])
    clipped_rhos = jnp.minimum(rho_clip, rhos) if rho_clip is not None else rhos
    cs = jnp.minimum(c_clip, rhos)

    values = values_ref[:]
    boot = bootstrap_ref[0, :]  # [B]
    discounts = discounts_ref[:]
    rewards = rewards_ref[:]

    # V(x_{t+1}) with the bootstrap in the last row.
    values_t_plus_1 = jnp.concatenate([values[1:], boot[None]], axis=0)
    deltas = clipped_rhos * (rewards + discounts * values_t_plus_1 - values)
    disc_cs = discounts * cs

    # Backward recursion, scan-free: rows are read/written through the
    # scratch refs so the time index stays a cheap VMEM dynamic slice.
    acc_scratch[0, :] = deltas
    acc_scratch[1, :] = disc_cs

    def backward(i, acc):
        t = T - 1 - i
        acc = acc_scratch[0, t, :] + acc_scratch[1, t, :] * acc
        vs_ref[t, :] = acc  # vs_minus_v for now; +values below
        return acc

    jax.lax.fori_loop(0, T, backward, jnp.zeros_like(boot))

    vs = vs_ref[:] + values
    vs_ref[:] = vs

    # Policy-gradient advantages: r + gamma * vs_{t+1} - V(x_t).
    vs_t_plus_1 = jnp.concatenate([vs[1:], boot[None]], axis=0)
    if pg_rho_clip is not None:
        clipped_pg_rhos = jnp.minimum(pg_rho_clip, rhos)
    else:
        clipped_pg_rhos = rhos
    pg_ref[:] = clipped_pg_rhos * (rewards + discounts * vs_t_plus_1 - values)


def vtrace_from_importance_weights_pallas(
    log_rhos: jnp.ndarray,
    discounts: jnp.ndarray,
    rewards: jnp.ndarray,
    values: jnp.ndarray,
    bootstrap_value: jnp.ndarray,
    clip_rho_threshold: Optional[float] = 1.0,
    clip_pg_rho_threshold: Optional[float] = 1.0,
    clip_c_threshold: float = 1.0,
    interpret: Optional[bool] = None,
):
    """Drop-in fused replacement for
    ``ops.vtrace.vtrace_from_importance_weights``.

    ``interpret=None`` resolves to ``True`` off-TPU (pure-Python Pallas
    interpreter — the CPU fallback the parity tests run) and ``False`` on
    TPU (compiled Mosaic kernel).
    """
    import jax.experimental.pallas as pl

    from scalerl_tpu.ops.vtrace import VTraceOutput

    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    # Gradients never flow through V-trace (outputs are stop_gradient-ed,
    # reference contract) — detach the inputs so AD never needs a VJP rule
    # for the pallas_call.
    log_rhos, discounts, rewards, values, bootstrap_value = map(
        jax.lax.stop_gradient,
        (log_rhos, discounts, rewards, values, bootstrap_value),
    )

    T, B = log_rhos.shape
    f32 = partial(jnp.asarray, dtype=jnp.float32)
    kernel = partial(
        _vtrace_kernel,
        rho_clip=(
            float(clip_rho_threshold) if clip_rho_threshold is not None else None
        ),
        pg_rho_clip=(
            float(clip_pg_rho_threshold)
            if clip_pg_rho_threshold is not None
            else None
        ),
        c_clip=float(clip_c_threshold),
    )
    vs, pg = pl.pallas_call(
        kernel,
        name="vtrace",
        out_shape=(
            jax.ShapeDtypeStruct((T, B), jnp.float32),
            jax.ShapeDtypeStruct((T, B), jnp.float32),
        ),
        scratch_shapes=[
            # [deltas; discounts*cs] rows for the recursion's dynamic reads
            _vmem_scratch((2, T, B), interpret),
        ],
        interpret=interpret,
    )(
        f32(log_rhos),
        f32(discounts),
        f32(rewards),
        f32(values),
        f32(bootstrap_value)[None, :],  # [1, B]: keep every operand 2D+
    )
    return VTraceOutput(
        vs=jax.lax.stop_gradient(vs),
        pg_advantages=jax.lax.stop_gradient(pg),
    )


def _vmem_scratch(shape, interpret: bool):
    """A VMEM scratch allocation that also works under the interpreter on
    backends without the TPU plugin (plain pltpu.VMEM is fine on both, but
    import it lazily so jax-free consumers never pull Pallas)."""
    from jax.experimental.pallas import tpu as pltpu

    del interpret  # pltpu.VMEM works in both compiled and interpret modes
    return pltpu.VMEM(shape, jnp.float32)
