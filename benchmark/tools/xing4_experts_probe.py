"""The routed FFN at Xing4.0's widths alone, on the chip: the streamed form
against the sorted form by token count.  Outside any cell.

    python benchmark/tools/xing4_experts_probe.py [--seed n] [--tokens 8 96 ...]

``zaya_experts_probe.py``'s way at another shape.  ``models/routed_ffn.py``
takes the streamed form (every bank times every token, masked) up to
``STREAMED_MAX_TOKENS`` tokens and the sorted form (three grouped matmuls
over the picks) above.  The constant was measured at OLMoE's widths (64
banks of 2048 x 1024, 8 picks a token) and again at ZAYA1's (16 banks of
2048 x 2048, one pick).  Here are 64 banks of 3584 x 1024 and 4 picks: the
sorted form does a sixteenth of the arithmetic, and reading the banks once
is 1.41 GB, 1.7 ms.  One layer's ``RoutedExperts`` as the family builds it
(its own sigmoid scorer and choice bias, renormalised picks times 2),
bfloat16, forward only (rollout: a substep's 96 tokens, a prompt's 64-256),
each form jitted on the same inputs and timed over ``--reps`` calls after a
warm-up call.  Prints one JSON object: microseconds a call of each form at
each token count, the HBM floor of reading the banks once, and the count at
which the sorted form first wins.  Fails off the chip (``--platform cpu``
rehearses at the cell's tiny sizes and prints no time).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tokens", type=int, nargs="+", default=[8, 96, 128, 256, 512, 1024, 2048])
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--platform", default="tpu", choices=("tpu", "cpu"))
    ns = ap.parse_args(argv)

    import jax

    jax.config.update("jax_platforms", "cpu" if ns.platform == "cpu" else "tpu,cpu")
    import jax.numpy as jnp

    import harness
    import work
    from scalerl_tpu.models import routed_ffn

    if jax.default_backend() != ns.platform:
        raise SystemExit(f"the probe needs a {ns.platform} backend, JAX gave {jax.default_backend()!r}")
    cfg = dict(harness.load_json("configs", "xing4.0-29b-a4b"))
    small = ns.platform == "cpu"
    if small:
        cfg.update(harness.load_json("workloads", "xing4_group_rollout")["rehearse_config"])
    d, f, e = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    dtype = jnp.float32 if small else jnp.bfloat16
    module = routed_ffn.RoutedExperts(
        e, cfg["num_experts_per_tok"], f, bool(cfg["norm_topk_prob"]), choice_bias=True,
        routed_scaling=float(cfg["routed_scaling_factor"]), scoring=cfg["scoring_func"],
        dtype=dtype, param_dtype=dtype,
    )
    key = jax.random.PRNGKey(ns.seed)
    params = jax.jit(module.init)(key, jnp.zeros((1, 2, d), dtype))
    rows = {}
    for n in ns.tokens:
        h = jax.random.normal(jax.random.fold_in(key, n), (1, n, d), dtype)
        took = {}
        for form, limit in (("streamed", 1 << 30), ("sorted", 0)):
            routed_ffn.STREAMED_MAX_TOKENS = limit  # read when the call is traced
            call = jax.jit(lambda p, x: module.apply(p, x))
            jax.block_until_ready(call(params, h))
            t0 = time.perf_counter()
            for _ in range(ns.reps):
                out = call(params, h)
            jax.block_until_ready(out)
            took[form] = 1e6 * (time.perf_counter() - t0) / ns.reps
        rows[n] = took
    out = {"device": jax.devices()[0].device_kind, "experts": e, "width": [d, f], "tokens": sorted(rows)}
    if not small:
        peaks = work.peaks(jax.devices()[0].device_kind)
        out["banks_once_us"] = 1e6 * 3 * e * d * f * 2 / peaks["hbm_bytes_per_s"]
        out["us_a_call"] = {str(n): {k: round(v, 1) for k, v in rows[n].items()} for n in sorted(rows)}
        wins = [n for n in sorted(rows) if rows[n]["sorted"] < rows[n]["streamed"]]
        out["sorted_first_wins_at"] = wins[0] if wins else None
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
