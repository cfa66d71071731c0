"""Elastic-fleet soak: a seeded preemption wave against a live fleet.

The elastic soak (standalone, like the chaos
soak): run a short pipe fleet through a seeded ``mass_kill`` wave with the
autoscaler backfilling, then emit a one-line JSON verdict a caller gates
on — ``lost`` episodes (exact unique accounting over the PR 4 dedup keys +
task-level requeue) and ``decisions_per_min`` (autoscaler flap rate).

jax-free on purpose: the driver exercises the fleet/autoscaler planes only,
so gathers fork cheaply and the soak stays bounded (~1 min) on any CI
host, chip or not.

Run: ``python tools/elastic_soak.py`` (options below).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from scalerl_tpu.fleet import ClusterExecutor, FleetConfig, LocalCluster, WorkerServer
from scalerl_tpu.runtime import chaos, telemetry
from scalerl_tpu.runtime.autoscaler import (
    Autoscaler,
    AutoscalerConfig,
    fleet_signal_source,
)


def _soak_runner(task, weights, worker_id):
    """Module-level (spawn/fork-picklable): a short fake episode whose
    payload is just its seed — uniqueness accounting needs nothing more."""
    time.sleep(0.2)
    return {"seed": int(task.get("seed", 0))}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--tasks", type=int, default=96)
    parser.add_argument("--workers", type=int, default=4)
    parser.add_argument("--workers-per-gather", type=int, default=1)
    parser.add_argument("--seed", type=int, default=1234)
    parser.add_argument("--kills", type=int, default=0,
                        help="victims per wave (0 = half the gathers)")
    parser.add_argument("--deadline-s", type=float, default=240.0)
    args = parser.parse_args()

    # seeded wave: ~30% chance per supervisor poll (0.5 s cadence), capped at
    # one wave — it lands a couple of seconds into the run, mid-stream
    os.environ.setdefault(
        chaos.ENV_VAR, f"{args.seed}:mass_kill=0.5@1,kills={args.kills}"
    )
    chaos.clear()

    n_tasks = args.tasks
    counter = {"i": 0}
    lock = threading.Lock()

    def source():
        with lock:
            if counter["i"] >= n_tasks:
                return None
            counter["i"] += 1
            return {"role": "rollout", "seed": counter["i"]}

    config = FleetConfig(
        num_workers=args.workers,
        workers_per_gather=args.workers_per_gather,
        upload_batch=1,
        heartbeat_interval_s=0.5,
    )
    server = WorkerServer(config, source)
    server.start(listen=False)
    # max_restarts=0: the AUTOSCALER (floor rule), not the respawn budget,
    # must backfill the wave — that is the property this soak certifies.
    # spawn, not fork: the parent is heavily threaded (hub pumps, autoscaler,
    # supervisor) and forked children inherit held locks and every live pipe
    # fd — a SIGTERMed gather's workers then never see EOF and linger as
    # orphans on the CI host
    cluster = LocalCluster(server, config, _soak_runner, mp_context="spawn",
                           max_restarts=0)
    cluster.start()
    autoscaler = Autoscaler(
        AutoscalerConfig(
            min_workers=args.workers,
            max_workers=2 * args.workers,
            interval_s=0.25,
            cooldown_s=1.0,
            up_hysteresis=1,
            down_hysteresis=2,
            # floor backfill is the property under test: disable the
            # starved rule (a drain-to-verdict consumer keeps occupancy at
            # 0 permanently, which would just push the fleet to max)
            low_occupancy=-1.0,
        ),
        executor=ClusterExecutor(server, cluster),
        signal_source=fleet_signal_source(server),
    ).start()

    t0 = time.monotonic()
    results = []
    try:
        deadline = t0 + args.deadline_s
        while len(results) < n_tasks and time.monotonic() < deadline:
            r = server.get_result(timeout=0.2)
            if r is not None:
                results.append(r)
    finally:
        autoscaler.stop()
        cluster.join()
        server.stop()

    elapsed = time.monotonic() - t0
    seeds = [r.get("seed") for r in results]
    unique = len(set(seeds))
    mass_kills = telemetry.get_recorder().events("mass_kill")
    killed = sum(len(e.get("victims", [])) for e in mass_kills)
    actions = autoscaler.scale_ups + autoscaler.scale_downs
    # rate over at least a minute: a 10 s run with one backfill is not a
    # "6/min" flap, it is one action
    rate_window_min = max(elapsed, 60.0) / 60.0
    verdict = {
        "metric": "elastic_soak",
        "expected": n_tasks,
        "received": len(results),
        "unique": unique,
        "lost": n_tasks - unique,
        # duplicates that REACHED the consumer (must be 0: the dedup layers
        # absorb redelivery); absorbed ones are the dedup working as designed
        "duplicates": len(results) - unique,
        "absorbed_duplicates": server.duplicate_results + server.duplicate_tasks,
        "requeued_tasks": server.requeued_tasks,
        "gathers_killed": killed,
        "waves": len(mass_kills),
        "scale_ups": autoscaler.scale_ups,
        "scale_downs": autoscaler.scale_downs,
        "decisions_per_min": round(actions / rate_window_min, 2),
        "elapsed_s": round(elapsed, 1),
        "chaos": os.environ.get(chaos.ENV_VAR, ""),
    }
    print(json.dumps(verdict), flush=True)
    # the soak proves nothing unless the wave landed AND no episode was lost
    ok = verdict["lost"] == 0 and killed > 0 and autoscaler.scale_ups >= 1
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
