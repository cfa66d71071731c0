"""The Gated DeltaNet decode update alone, at ``qwen3next_group_rollout``'s
shape, on the chip: ``[lanes, 32, 128, 128]`` float32 state a layer, 16 key
heads.

    python benchmark/tools/gdn_decode_probe.py [--lanes 96] [--layers 18] [--calls 20] [--block-heads 16 32]

Prints one JSON line: microseconds a call (one layer's update of every
lane) and the share of the HBM peak (819 GB/s) at which the state's bytes,
ONCE in and once out, moved, for three forms: ``plain`` (the update's
lines in plain ``jax.numpy``, kept here alone: XLA makes a reduction
fusion and a write fusion of them, two reads of the state a layer), ``pallas``
(``ops/pallas_gdn.py``: one read, one write) and ``copy`` (a Pallas kernel
over the same blocks that only copies them in and out: what the memory
system gives that grid with no arithmetic at all); the last two once a
``--block-heads`` value.  The state is carried IN PLACE through a jitted
loop over ``--layers`` separate state arrays and ``--calls`` steps (as the
decode program carries a layer's state through its substeps, donated); the
time is wall clock over the loop, ending in one blocking read.  With
``--layers`` the states do not fit any cache and each call streams its own
from HBM.  This is how PERF.md's kernel decision (PR 42) is reproduced.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

HBM_BYTES_PER_S = 819e9


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--lanes", type=int, default=96)
    ap.add_argument("--layers", type=int, default=18)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--block-heads", type=int, nargs="+", default=[16, 32])
    ap.add_argument("--seed", type=int, default=42)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from scalerl_tpu.ops import pallas_gdn

    L, H, N, P, G = args.lanes, 32, 128, 128, 16
    key = jax.random.split(jax.random.PRNGKey(args.seed), 6)
    unit = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    q = unit(jax.random.normal(key[1], (L, G, N))) * N ** -0.5
    k = unit(jax.random.normal(key[2], (L, G, N)))
    v = jax.random.normal(key[3], (L, H, P))
    g = -jax.nn.softplus(jax.random.normal(key[4], (L, H)) - 3.0)
    beta = jax.nn.sigmoid(jax.random.normal(key[5], (L, H)))

    def plain(state, q, k, v, g, beta):
        """The kernel's lines in ``jax.numpy``, the form the program dropped."""
        per = v.shape[1] // k.shape[1]
        qh, kh = jnp.repeat(q, per, axis=1), jnp.repeat(k, per, axis=1)  # [L, H, N]
        decay = jnp.exp(g)[:, :, None]
        u = decay * jnp.sum(state * kh[..., None], axis=2)  # [L, H, P]
        read = jnp.sum(state * qh[..., None], axis=2)
        d = beta[:, :, None] * (v - u)
        o = decay * read + jnp.sum(kh * qh, axis=-1, keepdims=True) * d
        return o, decay[..., None] * state + kh[..., None] * d[:, :, None, :]

    def copy_blocks(hb):
        def kernel(s_ref, o_ref):
            o_ref[...] = s_ref[...]

        def copy(state, *_operands):
            block = pl.BlockSpec((None, hb, N, P), lambda lane, b: (lane, b, 0, 0))
            new = pl.pallas_call(
                kernel, grid=(L, H // hb), in_specs=[block], out_specs=block,
                out_shape=jax.ShapeDtypeStruct(state.shape, state.dtype),
                input_output_aliases={0: 0},
                compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
                name="gdn_state_copy",
            )(state)
            return new[:, :, 0, :], new

        return copy

    def timed(update):
        def chain(states):
            def body(_i, carry):
                states, acc = carry
                new = []
                for s in states:
                    o, s = update(s, q, k, v + acc[:, :, :1] * 0.0, g, beta)
                    acc = acc + o
                    new.append(s)
                return tuple(new), acc

            return jax.lax.fori_loop(0, args.calls, body, (states, jnp.zeros((L, H, P), jnp.float32)))

        run = jax.jit(chain, donate_argnums=0)
        states = tuple(
            0.1 * jax.random.normal(jax.random.fold_in(key[0], i), (L, H, N, P), jnp.float32)
            for i in range(args.layers)
        )
        states, _ = jax.block_until_ready(run(states))
        t0 = time.perf_counter()
        jax.block_until_ready(run(states))
        us = 1e6 * (time.perf_counter() - t0) / (args.calls * args.layers)
        return {"update_us": us, "hbm_share": moved / HBM_BYTES_PER_S / (us / 1e6)}

    moved = 2.0 * L * H * N * P * 4
    out = {
        "lanes": L, "layers": args.layers, "calls": args.calls, "state_bytes_moved": moved,
        "hbm_floor_us": 1e6 * moved / HBM_BYTES_PER_S, "device": jax.devices()[0].device_kind,
        "plain": timed(plain),
    }
    for hb in args.block_heads:
        pallas_gdn._BLOCK_BYTES = 4 * hb * N * P
        out[f"pallas_{hb}"] = timed(pallas_gdn.gdn_decode_update_pallas)
        out[f"copy_{hb}"] = timed(copy_blocks(hb))
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
