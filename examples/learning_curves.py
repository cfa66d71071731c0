"""Learning-curve evidence harness (VERDICT r1 "Next round" #3).

Runs each algorithm family to a reward threshold and records the full
reward-vs-frames curve as TensorBoard events plus a machine-readable
summary — the evidence artifact the reference never produced (its IMPALA
trained to scores at runtime, ``scalerl/algorithms/impala/impala_atari.py:
403-494``, but recorded nothing).

The experiments live in ``examples/curves/`` (one module per algorithm
family; see ``curves/__init__.py`` for the registry).  This entry point
only pins the backend, resolves names, and writes the artifacts:

Artifacts land in ``work_dirs/learning_curves/<name>/`` (tb events) and
``work_dirs/learning_curves/summary.json``; ``docs/LEARNING_CURVES.md``
holds the human-readable table.

Usage::

    python examples/learning_curves.py            # all experiments
    python examples/learning_curves.py impala_synthetic dqn_cartpole
    python examples/learning_curves.py impala_synthetic_northstar --tpu
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

if "--tpu" not in sys.argv:
    # Pin CPU before any backend init (same effect as JAX_PLATFORMS=cpu):
    # without --tpu this harness must stay off the chip.
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
    import jax

    jax.config.update("jax_platforms", "cpu")
else:
    import jax

from curves import EXPERIMENTS  # noqa: E402
from curves.common import OUT_DIR  # noqa: E402
from curves.report import _write_markdown  # noqa: E402

# Shared harnesses re-exported at their historical location: the regression
# tests (tests/test_offpolicy_lag.py, test_r2d2.py, test_sac.py, test_td3.py)
# assert over the SAME calibrated setups the recorded curves use, importing
# them from here.
from curves.continuous import run_sac_pendulum, run_td3_pendulum  # noqa: E402,F401
from curves.impala import run_lagged_arm  # noqa: E402,F401
from curves.r2d2 import run_r2d2_recall, run_r2d2_recall_device  # noqa: E402,F401


def main() -> None:
    names = [a for a in sys.argv[1:] if not a.startswith("-")]
    if not names:
        names = list(EXPERIMENTS)
        if jax.default_backend() == "cpu":
            # accelerator-scale runs (~hours on CPU at these shapes):
            # request explicitly, or run with --tpu on the chip
            names.remove("impala_synthetic_northstar")
            names.remove("impala_breakout_84")
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    summary_path = OUT_DIR / "summary.json"
    results = []
    if summary_path.exists():
        results = [
            r for r in json.loads(summary_path.read_text()) if r["experiment"] not in names
        ]
    for name in names:
        print(f"=== {name} ===", flush=True)
        r = EXPERIMENTS[name]()
        print(json.dumps(r), flush=True)
        results.append(r)
        results.sort(key=lambda r: r["experiment"])
        summary_path.write_text(json.dumps(results, indent=2))
        _write_markdown(results)


if __name__ == "__main__":
    main()
