"""The Mamba-2 decode update alone, at ``nemotron_group_rollout``'s shape,
on the chip: ``[lanes, 64, 64, 128]`` float32 state a layer, 8 groups.

    python benchmark/tools/ssm_decode_probe.py [--lanes 96] [--layers 23] [--calls 20]

Prints one JSON line: microseconds a call (one layer's update of every
lane) for ``ssm_decode_update`` (plain ``jax.numpy``, one XLA fusion), the
state carried IN PLACE through a jitted loop over ``--layers`` separate
state arrays and ``--calls`` steps (as the decode program carries a layer's
state through its substeps, donated), the bytes a call has to move (the
state in and out) and the time they would take at the HBM peak.  The time
is wall clock over the loop, ending in one blocking read.  With ``--layers``
the states do not fit any cache and each call streams its own from HBM.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--lanes", type=int, default=96)
    ap.add_argument("--layers", type=int, default=23)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--seed", type=int, default=40)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from scalerl_tpu.models.transformer import ssm_decode_update

    L, H, P, N, G = args.lanes, 64, 64, 128, 8
    k = jax.random.split(jax.random.PRNGKey(args.seed), 6)
    x = jax.random.normal(k[1], (L, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(k[2], (L, H)) - 3.0)
    A = -jnp.exp(jax.random.uniform(k[3], (H,), minval=0.0, maxval=2.7))
    Bm = jax.random.normal(k[4], (L, G, N), jnp.float32)
    Cm = jax.random.normal(k[5], (L, G, N), jnp.float32)
    D = jnp.ones((H,), jnp.float32)

    def chain(states):
        def body(_i, carry):
            states, acc = carry
            new = []
            for s in states:
                y, s = ssm_decode_update(s, x + acc[:, :, :1] * 0.0, dt, A, Bm, Cm, D)
                acc = acc + y
                new.append(s)
            return tuple(new), acc
        return jax.lax.fori_loop(0, args.calls, body, (states, jnp.zeros((L, H, P), jnp.float32)))

    run = jax.jit(chain, donate_argnums=0)
    states = tuple(
        jax.random.normal(jax.random.fold_in(k[0], i), (L, H, P, N), jnp.float32)
        for i in range(args.layers)
    )
    states, _ = jax.block_until_ready(run(states))
    t0 = time.perf_counter()
    jax.block_until_ready(run(states))
    moved = 2.0 * L * H * P * N * 4
    print(json.dumps({
        "lanes": L, "layers": args.layers, "calls": args.calls,
        "update_us": 1e6 * (time.perf_counter() - t0) / (args.calls * args.layers),
        "state_bytes_moved": moved, "hbm_floor_us": 1e6 * moved / 819e9,
        "device": jax.devices()[0].device_kind,
    }), flush=True)


if __name__ == "__main__":
    main()
