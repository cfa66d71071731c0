"""The absorbed latent decode kernel alone, at ``longcat_group_rollout``'s
shape, on the chip: ``[lanes, 1, 64, 576]`` queries against a 16,385-page
float32 pool 640 lanes wide, context lengths drawn as the cell's are
(a prompt of 64-256 and a geometric response, capped).

    python benchmark/tools/latent_decode_probe.py [--lanes 128] [--calls 50]

Prints one JSON line: microseconds a call for the kernel as committed, for
"copies only" (the two products replaced by a read of one value of the
block: the walk, its descriptors and its waits, no arithmetic), for
"arithmetic only" (no page is copied: the block is whatever VMEM held),
and for the XLA gather twin; the bytes the rows hold and the time they
would take at the HBM peak.  Times are wall clock over a jitted chain of
``--calls`` calls ending in one blocking read.  The two variants are made
by patching this process's copy of the module; the program has no switch.
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
    ap.add_argument("--lanes", type=int, default=128)
    ap.add_argument("--calls", type=int, default=50)
    ap.add_argument("--seed", type=int, default=30)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas import tpu as pltpu

    from scalerl_tpu.ops import pallas_paged_attention as ppa

    B, H, W, VW, ps, M = args.lanes, 64, 576, 512, 8, 128
    N = B * M + 1
    rng = np.random.default_rng(args.seed)
    k1, k2 = jax.random.split(jax.random.PRNGKey(args.seed))
    q = jax.random.normal(k1, (B, 1, H, W), jnp.float32)
    pool = jax.random.normal(k2, (N, ps, ppa.latent_pool_width(W)), jnp.float32)
    table = jnp.asarray(rng.permutation(np.arange(1, N))[: B * M].reshape(B, M), jnp.int32)
    # a lane's context mid-flight: its prompt and a uniform share of a
    # geometric response (mean 330, cap 768)
    resp = np.minimum(rng.geometric(1 / 384, B), 768) * rng.uniform(0, 1, B)
    lengths = jnp.asarray(rng.integers(64, 257, B) + resp.astype(np.int64) + 1, jnp.int32)
    scale = 192 ** -0.5

    def timed(fn):
        @jax.jit
        def chain(q):
            def body(_i, carry):
                out = fn(q + carry[:, :, :, :1] * 0.0, pool, table, lengths, VW, scale)
                return carry + out
            return jax.lax.fori_loop(0, args.calls, body, jnp.zeros((B, 1, H, VW), jnp.float32))

        jax.block_until_ready(chain(q))
        t0 = time.perf_counter()
        jax.block_until_ready(chain(q))
        return 1e6 * (time.perf_counter() - t0) / args.calls

    def kernel(*a):
        return ppa.paged_decode_latent(*a, interpret=False)

    out = {"lanes": B, "calls": args.calls, "mean_context": float(np.mean(np.asarray(lengths)))}
    out["committed_us"] = timed(kernel)
    with jax.default_matmul_precision("highest"):
        ref = ppa.paged_latent_attention_reference(q, pool, table, lengths, VW, scale)
    out["max_err_vs_highest_reference"] = float(
        jnp.max(jnp.abs(kernel(q, pool, table, lengths, VW, scale) - ref))
    )
    out["xla_gather_us"] = timed(ppa.paged_latent_attention_reference)

    real_dot = ppa._dot_terms

    real_terms = ppa._bf16_terms

    def no_arithmetic(a_terms, b_terms, contract):
        # one reduction of the block (so that it is read) in place of the
        # two products; the split into bfloat16 terms is a plain cast
        rows = a_terms.shape[0] // 3
        cols = b_terms[0].shape[1 - contract[1][0]]
        return jnp.full((rows, cols), jnp.max(b_terms[0].astype(jnp.float32)))

    ppa._dot_terms = no_arithmetic
    ppa._bf16_terms = lambda x: (x.astype(jnp.bfloat16),) * 3
    try:
        out["copies_only_us"] = timed(kernel)
    except Exception as e:  # noqa: BLE001 - a variant the compiler refuses is a finding
        out["copies_only_us"] = f"failed: {type(e).__name__}: {str(e)[:200]}"
    ppa._dot_terms, ppa._bf16_terms = real_dot, real_terms

    class _NoCopy:
        def start(self):
            pass

        def wait(self):
            pass

    real_copy = pltpu.make_async_copy
    pltpu.make_async_copy = lambda *_a, **_k: _NoCopy()
    try:
        out["arithmetic_only_us"] = timed(kernel)
    except Exception as e:  # noqa: BLE001
        out["arithmetic_only_us"] = f"failed: {type(e).__name__}: {str(e)[:200]}"
    finally:
        pltpu.make_async_copy = real_copy

    row_bytes = float(np.sum(np.asarray(lengths))) * W * 4
    out["row_bytes"] = row_bytes
    out["hbm_floor_us"] = 1e6 * row_bytes / 819e9
    out["device"] = jax.devices()[0].device_kind
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
