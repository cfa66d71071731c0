"""Host-actor-plane Breakout ablation matrix (VERDICT r4 next-round #2).

Round-4 standing result: the fused device loop crosses windowed return 20
on Breakout at ~1M frames, while five host-plane runs (seeds/budgets/
entropy/queue-depth varied) plateaued at the one-bounce-rally level
(~3-5.6).  This harness isolates the cause by running one arm per
hypothesis — all through THE shared recipe
(``curves/impala.py:run_host_breakout_arm``, the same code path as the
recorded baseline), same budget and seed:

- ``geom_1x16``  — 1 actor x 16 lanes, batch = ONE slot of 16 lanes,
  minimal queue (depth 2).  This is the fused arm's exact data geometry
  (16 distinct lanes per update, lag <= 1 learner step) on the host
  plane; it is simultaneously the VERDICT's "fused hyperparameters
  transplanted exactly" and "slot-queue depth 1" arm.
- ``geom_4x4``   — 4 actors x 4 lanes: each update batches 4 slots from 4
  different actors (decorrelated), vs the baseline's 2 slots from 2.
- ``lag_rho1``   — baseline geometry, but behavior logits are replaced by
  the target policy's own before each update (the off-policy-lag proof's
  rho=1 trick): if V-trace's rho/c clipping under queue lag is what
  starves the breakthrough, forcing exact on-policyness removes it.
- ``entropy_sched`` — baseline geometry, entropy cost annealed 0.03 ->
  0.005 over 1M frames (``ImpalaArguments.entropy_cost_end``): high-early
  exploration through the rally plateau, low-late exploitation.
- ``bt_B32``     — batch 32 lanes (4 slots of 8): 640 frames/update.
- ``bt_T10``     — unroll 10 (half the chunk): halves worst-case lag in
  env steps and doubles update frequency at fixed frames/sec.

Each arm records a TensorBoard curve (``work_dirs/learning_curves/
host_ablation/``) and a summary row; the combined matrix lands in
``work_dirs/learning_curves/host_ablation.json`` and the conclusion in
``docs/LEARNING_CURVES.md``.

Run: ``python examples/curves/host_ablation.py [--arms a,b] [--max-frames N]``
Arms already present in the summary JSON are skipped (crash-resume);
``--force`` re-runs them.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax

jax.config.update("jax_platforms", "cpu")  # a CPU study, whatever the env says

OUT_DIR = Path(__file__).resolve().parents[2] / "work_dirs" / "learning_curves"

ARMS = {
    "geom_1x16": dict(num_actors=1, envs_per_actor=16),
    "geom_4x4": dict(num_actors=4, envs_per_actor=4),
    "lag_rho1": dict(force_on_policy_rhos=True),
    "entropy_sched": dict(
        entropy_cost=0.03, entropy_cost_end=0.005, entropy_anneal_frames=1_000_000
    ),
    "bt_B32": dict(batch_size=32),
    "bt_T10": dict(rollout_length=10),
}

# lag-isolation arms: the FUSED loop with an artificially stale behavior
# snapshot (everything else identical to the passing impala_breakout) —
# run via curves.impala.run_fused_lagged_breakout, not the host recipe
FUSED_LAG_ARMS = {
    "fused_lag1": dict(pull_every=1),  # control: == the fused loop
    "fused_lag2": dict(pull_every=2),  # one chunk of lag (host-plane floor)
}


def main() -> None:
    from curves.impala import run_fused_lagged_breakout, run_host_breakout_arm

    p = argparse.ArgumentParser()
    p.add_argument("--arms", default="all", help="comma list or 'all'")
    p.add_argument("--max-frames", type=int, default=1_500_000)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--force", action="store_true",
        help="re-run arms already present in host_ablation.json",
    )
    args = p.parse_args()
    all_arms = {**ARMS, **FUSED_LAG_ARMS}
    names = list(all_arms) if args.arms == "all" else args.arms.split(",")
    out_path = OUT_DIR / "host_ablation.json"
    rows = json.loads(out_path.read_text()) if out_path.exists() else []
    done = {r["arm"] for r in rows}
    to_run = [n for n in names if args.force or n not in done]
    for skipped in set(names) - set(to_run):
        print(f"=== arm {skipped}: already recorded, skipping (--force to re-run)")
    for name in to_run:
        print(f"=== arm {name} ===", flush=True)
        if name in FUSED_LAG_ARMS:
            row = run_fused_lagged_breakout(
                name, max_frames=args.max_frames, seed=args.seed,
                **FUSED_LAG_ARMS[name],
            )
        else:
            row = run_host_breakout_arm(
                name,
                max_frames=args.max_frames,
                seed=args.seed,
                work_dir=OUT_DIR / "host_ablation",
                # timestamped run dir: a deterministic name would stack a
                # re-run's TB events next to the old run's, and the
                # crossing scan would read both
                run_name=f"host_ablation_{name}_{int(time.time())}",
                **ARMS[name],
            )
        rows = [r for r in rows if r["arm"] != name] + [row]
        print(json.dumps(row), flush=True)
        out_path.parent.mkdir(parents=True, exist_ok=True)
        out_path.write_text(json.dumps(rows, indent=2) + "\n")
    print(f"wrote {out_path}")


if __name__ == "__main__":
    main()
