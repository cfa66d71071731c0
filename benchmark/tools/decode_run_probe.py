"""The paged decode kernels alone on the chip, by how adjacent the table's
pages are, at each rollout cell's geometry.

    python benchmark/tools/decode_run_probe.py [--cell gpt2m ...] [--checkout DIR ...]

For every cell named (all by default) and every table it prints one JSON
line: microseconds a call of the kernel (wall clock over a jitted chain of
``--calls`` calls ending in one blocking read), the live pages, the copies
a pool's walk issues for the table (``table_copies``, where the checkout
has it), the cached bytes the lanes hold and their time at the HBM peak.
Context lengths are drawn as the cells' are (a prompt of 64-256 shared by
a group of 8, a uniform share of a geometric response, mean 330, cap 768).

Tables: ``pages`` (no two entries adjacent: a page a copy), ``engine``
(what the allocator gives under churn: a group's shared prompt run, then
own runs of about 18 pages that start anywhere) and ``runs`` (a lane's own
pages one run).  ``--checkout`` times another checkout's kernel in the
same process beside this one's (its ``scalerl_tpu`` is imported under
another name), so that two commits meet the same tables on the same chip.

``--sizes 16,4,1 16,1`` times this tree's kernel again with each of those
sets of pages a copy in place of its own (``_RUN_SIZES``; the kernels run
under a ``jax.jit`` of their own, so the caches are cleared around each).
``--variants`` adds "copies only" and "arithmetic only" as
``latent_decode_probe.py`` makes them, by patching the module.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

# cell -> (lanes, query heads, key/value heads, head size, bytes an element); the
# latent kernel's row is ``head size`` wide and its value 512
CELLS = {
    "gpt2m": (16, 16, 16, 64, 4),
    "olmoe": (32, 16, 16, 128, 2),
    "nemotron": (96, 32, 2, 128, 2),
    "qwen3next": (96, 16, 2, 256, 2),
    "zaya": (40, 8, 2, 128, 2),
    "longcat": (128, 64, 1, 576, 2),
}
PS, SLOTS, GROUP = 8, 128, 8


def tables(rng, lanes):
    """name -> ``[lanes, SLOTS]`` table, every page of the pool at most once
    but for a group's shared prompt pages; and the lanes' lengths."""
    import numpy as np

    N = lanes * SLOTS + 1
    prompt = np.repeat(rng.integers(64, 257, -(-lanes // GROUP)), GROUP)[:lanes]
    resp = np.minimum(rng.geometric(1 / 384, lanes), 768) * rng.uniform(0, 1, lanes)
    lengths = prompt + resp.astype(np.int64) + 1
    out = {"pages": rng.permutation(np.arange(1, N))[: lanes * SLOTS].reshape(lanes, SLOTS)}
    for name, mean_run in (("engine", 18), ("runs", 10 * SLOTS)):
        table = np.zeros((lanes, SLOTS), np.int64)
        # each lane's own stretch of the pool, its runs laid end to end in
        # shuffled order; the group's leader's first pages are the prompt's
        for lane in range(lanes):
            first = 1 + lane * SLOTS
            cuts = np.cumsum(rng.geometric(1 / mean_run, SLOTS))
            runs = np.split(np.arange(first, first + SLOTS), cuts[cuts < SLOTS])
            order = rng.permutation(len(runs))
            table[lane] = np.concatenate([runs[i] for i in order])
        for lane in range(lanes):
            shared = prompt[lane] // PS
            table[lane, :shared] = table[lane - lane % GROUP, :shared]
        out[name] = table
    return out, lengths


def load_kernels(checkout: Path):
    """``scalerl_tpu.ops.pallas_paged_attention`` of ``checkout``, under a
    name of its own when it is not this tree's."""
    if checkout.resolve() == ROOT:
        from scalerl_tpu.ops import pallas_paged_attention as ppa

        return ppa
    path = checkout / "scalerl_tpu" / "ops" / "pallas_paged_attention.py"
    spec = importlib.util.spec_from_file_location(f"ppa_{abs(hash(str(checkout)))}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", nargs="*", default=sorted(CELLS))
    ap.add_argument("--checkout", nargs="*", default=[], help="other checkouts to time beside this tree")
    ap.add_argument("--calls", type=int, default=50)
    ap.add_argument("--seed", type=int, default=50)
    ap.add_argument("--variants", action="store_true")
    ap.add_argument("--sizes", nargs="*", default=[], help="sets of pages a copy, as 16,4,1")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas import tpu as pltpu

    trees = [("tree", load_kernels(ROOT))] + [(d, load_kernels(Path(d))) for d in args.checkout]
    for cell in args.cell:
        lanes, H, KV, D, itemsize = CELLS[cell]
        dtype = jnp.float32 if itemsize == 4 else jnp.bfloat16
        rng = np.random.default_rng(args.seed)
        by_name, lengths = tables(rng, lanes)
        N = lanes * SLOTS + 1
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 3)
        latent = cell == "longcat"
        width = trees[0][1].latent_pool_width(D) if latent else KV * D
        q = jax.random.normal(keys[0], (lanes, 1, H, D), dtype)
        pools = [jax.random.normal(k, (N, PS, width), dtype) for k in keys[1 : 2 if latent else 3]]
        ln = jnp.asarray(lengths, jnp.int32)
        live = -(-lengths // PS)

        def timed(ppa, table):
            def call(q):
                if latent:
                    return ppa.paged_decode_latent(q, pools[0], table, ln, 512, 192**-0.5, interpret=False)
                return ppa.paged_decode_attention(q, *pools, table, ln, interpret=False)

            @jax.jit
            def chain(q):
                def body(_i, carry):
                    return carry + call(q + (carry[:, :, :, :1] * 0.0).astype(q.dtype)).astype(jnp.float32)

                return jax.lax.fori_loop(0, args.calls, body, jnp.zeros(call(q).shape, jnp.float32))

            jax.block_until_ready(chain(q))
            t0 = time.perf_counter()
            jax.block_until_ready(chain(q))
            return 1e6 * (time.perf_counter() - t0) / args.calls

        row_bytes = float(lengths.sum()) * width * itemsize * len(pools)
        for name, table in by_name.items():
            line = {
                "cell": cell, "table": name, "lanes": lanes, "live_pages": int(live.sum()),
                "mean_context": float(lengths.mean()), "row_bytes": row_bytes,
                "hbm_floor_us": 1e6 * row_bytes / 819e9, "device": jax.devices()[0].device_kind,
            }
            t = jnp.asarray(table, jnp.int32)
            for label, ppa in trees:
                line[f"{label}_us"] = timed(ppa, t)
                if hasattr(ppa, "table_copies"):
                    line[f"{label}_copies"] = ppa.table_copies(table, live, PS, width, itemsize, N)
            ppa = trees[0][1]
            for sizes in args.sizes:
                own = ppa._RUN_SIZES
                try:
                    jax.clear_caches()
                    ppa._RUN_SIZES = tuple(int(n) for n in sizes.split(","))
                    line[f"sizes_{sizes}_us"] = timed(ppa, t)
                    line[f"sizes_{sizes}_copies"] = ppa.table_copies(table, live, PS, width, itemsize, N)
                finally:
                    ppa._RUN_SIZES = own
                    jax.clear_caches()
            if args.variants:
                real = ppa._dot_terms, ppa._bf16_terms, pltpu.make_async_copy

                def no_arithmetic(a_terms, b_terms, contract):
                    rows, cols = a_terms.shape[0] // 3, b_terms[0].shape[1 - contract[1][0]]
                    return jnp.full((rows, cols), jnp.max(b_terms[0].astype(jnp.float32)))

                class _NoCopy:
                    start = wait = lambda self: None

                try:
                    ppa._dot_terms, ppa._bf16_terms = no_arithmetic, lambda x: (x.astype(jnp.bfloat16),) * 3
                    line["copies_only_us"] = timed(ppa, t)
                    ppa._dot_terms, ppa._bf16_terms = real[:2]
                    pltpu.make_async_copy = lambda *_a, **_k: _NoCopy()
                    line["arithmetic_only_us"] = timed(ppa, t)
                except Exception as e:  # noqa: BLE001 - a variant the compiler refuses is a finding
                    line["variant_failed"] = f"{type(e).__name__}: {str(e)[:200]}"
                finally:
                    ppa._dot_terms, ppa._bf16_terms, pltpu.make_async_copy = real
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
