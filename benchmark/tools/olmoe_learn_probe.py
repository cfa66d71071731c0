"""One learn step of the OLMoE block at published widths, on the chip,
followed on the host by the plain reference.  Outside any cell.

    python benchmark/tools/olmoe_learn_probe.py [--seed n] [--layers 1]

The learner cannot hold a meaningful depth of ``olmoe-1b-7b`` on one chip
(20 B a parameter with gradients, Adam and the frozen copy), so no cell
trains it yet (PERF.md, section 7).  This holds the learner to the
reference where one chip can: ``TokenPPOAgent`` built from the program's
own arguments (``--block-family olmoe`` at the configuration's widths,
``--bf16-params true``, ``--learner-packing``, ``--dp-size 1``), ONE layer,
one packed 1,024-token row holding one sequence (prompt 200, response
500: rotary positions far past a page, the segment-flash kernel at head
size 128 with a pad tail, the sorted expert form forward and backward)
beside an all-pad row.  The reference (``reference/olmoe.py`` through
``reference/token_ppo.py``) computes the same loss and its gradient in
float32 at ``highest`` on the host's CPU backend from the weights as they
were before the step.  Prints one JSON object: both losses, both
gradient norms, the load-balancing term and the largest load on each
side.  Fails off the chip.
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
    ap.add_argument("--layers", type=int, default=1)
    ap.add_argument("--platform", default="tpu", choices=("tpu", "cpu"))
    ns = ap.parse_args(argv)

    import jax

    jax.config.update("jax_platforms", "cpu" if ns.platform == "cpu" else "tpu,cpu")
    import jax.numpy as jnp
    import numpy as np

    import harness
    from scalerl_tpu.agents.token_ppo import TokenPPOAgent
    from scalerl_tpu.config import GenRLArguments, parse_args
    from scalerl_tpu.genrl.rollout import pack_learner_batch
    from scalerl_tpu.parallel.train_step import maybe_enable_mesh_from_args
    from scalerl_tpu.trainer.sequence_rl import build_genrl_model

    if jax.default_backend() != ns.platform:
        raise SystemExit(f"the probe needs a {ns.platform} backend, JAX gave {jax.default_backend()!r}")
    cfg = dict(harness.load_json("configs", "olmoe-1b-7b"), num_hidden_layers=ns.layers)
    if ns.platform == "cpu":  # the tiny rehearsal of the cell's file
        cfg.update(harness.load_json("workloads", "olmoe_group_rollout")["rehearse_config"])
        cfg["num_hidden_layers"] = ns.layers
    ref = harness.load_module("reference", "olmoe")
    ref_ppo = harness.load_module("reference", "token_ppo")
    small = ns.platform == "cpu"
    S, P, R = (64, 12, 30) if small else (1024, 200, 500)
    args = parse_args(
        GenRLArguments,
        ref.program_argv(cfg)
        + ["--logger-backend", "none", "--learner-packing", "true", "--learner-pack-len", str(S),
           "--prompt-len", str(S // 4), "--max-new-tokens", str(S - S // 4),
           "--dp-size", "1", "--seed", str(ns.seed), "--platform", ns.platform]
        + ([] if small else ["--bf16-params", "true"]),
    )
    args.validate()
    t0 = time.perf_counter()
    agent = TokenPPOAgent(args, build_genrl_model(args))
    maybe_enable_mesh_from_args(agent, args)
    before = jax.tree_util.tree_map(
        lambda x: np.asarray(x, np.float32), jax.device_get(agent.state.params)
    )
    rng = np.random.default_rng(ns.seed)
    vocab = args.vocab_size
    prompt = rng.integers(0, vocab, P).astype(np.int32)
    resp = rng.integers(0, vocab, R).astype(np.int32)
    logp = (-np.log(vocab) + 0.3 * rng.normal(size=R)).astype(np.float32)
    val = (0.1 * rng.normal(size=R)).astype(np.float32)
    reward = np.asarray([0.7], np.float32)
    rows = pack_learner_batch(
        [prompt], [resp], [logp], [val], reward, np.zeros(1, np.int32), pack_len=S
    ).bucketed(2)
    fields, _prios = rows.fields()
    metrics = agent.learn({k: jnp.asarray(v) for k, v in fields.items()})
    took = time.perf_counter() - t0
    got = {k: float(metrics[k]) for k in ("total_loss", "grad_norm", "moe_aux_loss", "moe_max_load")}

    hyper = dict(
        clip_range=args.clip_range, value_cost=args.value_cost, entropy_cost=args.entropy_cost,
        kl_cost=args.kl_cost, adv_norm=args.adv_norm, router_aux_loss_coef=args.router_aux_loss_coef,
    )
    zeros = np.zeros(P, np.float32)
    with jax.default_device(jax.devices("cpu")[0]):
        seq = {
            "tokens": jnp.asarray(np.concatenate([prompt, resp])),
            "mask": jnp.asarray(np.r_[zeros, np.ones(R, np.float32)]),
            "behavior_logp": jnp.asarray(np.r_[zeros, logp]),
            "value": jnp.asarray(np.r_[zeros, val]),
            "reward": jnp.full((P + R,), reward[0], jnp.float32),
        }
        geo = ref.geometry(cfg)
        (total, parts), grads = jax.value_and_grad(
            lambda w: ref.ppo_loss(ref_ppo, w, w, seq, geo, hyper), has_aux=True
        )(jax.tree_util.tree_map(jnp.asarray, before))
        norm = float(sum(float(jnp.sum(jnp.square(g))) for g in jax.tree_util.tree_leaves(grads))) ** 0.5
    want = {
        "total_loss": float(total), "grad_norm": norm,
        "moe_aux_loss": float(parts["moe_aux_loss"]), "moe_max_load": float(parts["moe_max_load"]),
    }
    out = {
        "device": jax.devices()[0].device_kind, "layers": ns.layers,
        "params": int(sum(x.size for x in jax.tree_util.tree_leaves(before))),
        "learner": got, "reference": want,
        "loss_abs_diff": abs(got["total_loss"] - want["total_loss"]),
        "grad_norm_rel_diff": abs(got["grad_norm"] - want["grad_norm"]) / want["grad_norm"],
        "aux_abs_diff": abs(got["moe_aux_loss"] - want["moe_aux_loss"]),
        "learn_s_with_compile": took,
        "peak_bytes": int((jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0)),
    }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
