"""Disaggregated-dataflow soak: a seeded preemption wave mid-decode.

The disagg soak (standalone, like the chaos and
elastic soaks): a jax-free pipe fleet of 2 generation hosts (scripted
engines — deterministic payloads, so bit-exactness is checkable) streams
sequences into a :class:`SequenceLearner`; a seeded ``mass_kill`` wave
SIGTERMs half the hosts while lanes are mid-decode, and the autoscaler's
floor rule backfills.  One JSON verdict line gates the step: ``lost``
sequences (exact unique accounting over the lease ids + the
(host, epoch, seq) dedup keys), consumer-visible ``duplicates``, and
``payload_mismatches`` (every accepted byte re-derived from the lease seed).

jax-free on purpose: the generation hosts are spawn children that never
import jax, so the soak stays bounded (~1 min) on any CI host, chip or
not, while still exercising the full wire/lease/ack/drain machinery.

Run: ``python tools/disagg_soak.py`` (options below).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from scalerl_tpu.genrl.disagg import (
    DisaggConfig,
    GenerationTierExecutor,
    LocalGenerationFleet,
    ScriptedEngineFactory,
    SequenceLearner,
    disagg_signal_source,
    scripted_sequence_payload,
)
from scalerl_tpu.genrl.disagg import record_consumption_trace
from scalerl_tpu.runtime import chaos, telemetry, tracing
from scalerl_tpu.runtime.autoscaler import Autoscaler, AutoscalerConfig

RESPONSE_LEN = 8
VOCAB = 32
LEARN_BATCH = 8  # pseudo learn-round size for the traced consumption loop


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--leases", type=int, default=96)
    parser.add_argument("--hosts", type=int, default=2)
    parser.add_argument("--lanes", type=int, default=2)
    parser.add_argument("--seed", type=int, default=1234)
    parser.add_argument("--kills", type=int, default=0,
                        help="victims per wave (0 = half the hosts)")
    parser.add_argument("--warmup", type=int, default=6,
                        help="sequences collected before the wave lands")
    parser.add_argument("--deadline-s", type=float, default=240.0)
    parser.add_argument(
        "--trace-dir", default="",
        help="arm SCALERL_TRACE_SAMPLE=1.0 + per-host span export, then "
        "run tools/trace_report.py over the merged files (the trace "
        "soak): every completed sequence must yield one "
        "root-to-learn-step trace with zero orphan spans",
    )
    args = parser.parse_args()

    # the wave fires on the FIRST chaos_poll draw (rate 1.0@1) — the soak
    # lands it deliberately after warmup, so the kill is provably
    # mid-decode rather than mid-boot
    os.environ.setdefault(
        chaos.ENV_VAR, f"{args.seed}:mass_kill=1.0@1,kills={args.kills}"
    )
    chaos.clear()

    if args.trace_dir:
        # spawn children inherit the env, so every generation host samples
        # at 1.0 and appends spans to its own file as they finish (a
        # SIGTERM'd host loses at most the line in flight)
        os.makedirs(args.trace_dir, exist_ok=True)
        for stale in os.listdir(args.trace_dir):
            if stale.startswith("spans_") or stale == "trace_events.json":
                os.unlink(os.path.join(args.trace_dir, stale))
        os.environ[tracing.ENV_SAMPLE] = "1.0"
        os.environ[tracing.ENV_DIR] = args.trace_dir
        tracing.reset()

    n = args.leases
    counter = {"i": 0}
    lock = threading.Lock()

    def source():
        with lock:
            if counter["i"] >= n:
                return None
            counter["i"] += 1
            return {"seed": counter["i"], "length": 4}

    cfg = DisaggConfig(
        num_hosts=args.hosts,
        lanes_per_host=args.lanes,
        upload_batch=1,
        heartbeat_interval_s=0.5,
    )
    learner = SequenceLearner(cfg, source)
    learner.start()
    rng = np.random.default_rng(0)
    weights = {"w": rng.standard_normal((32, 32)).astype(np.float32)}
    learner.publish(weights, learner_step=0)
    # slow scripted decode (one token per step + a sleep) so sequences are
    # genuinely in flight when the wave lands.  spawn, not fork: a
    # SIGTERMed fork child inherits live pipe fds and lingers (the
    # elastic_soak verdict); spawn children boot in well under a second
    # because the shells never import jax.
    fleet = LocalGenerationFleet(
        learner,
        cfg,
        ScriptedEngineFactory(
            lanes=args.lanes,
            response_len=RESPONSE_LEN,
            tokens_per_step=1,
            step_sleep_s=0.02,
            vocab=VOCAB,
        ),
        mp_context="spawn",
        auto_chaos=False,  # the soak times the wave itself (post-warmup)
    )
    fleet.start()
    # max restarts are nobody's job here: the AUTOSCALER's floor rule must
    # backfill the wave — that is the property this soak certifies
    autoscaler = Autoscaler(
        AutoscalerConfig(
            min_workers=args.hosts,
            max_workers=2 * args.hosts,
            interval_s=0.25,
            cooldown_s=1.0,
            up_hysteresis=1,
            down_hysteresis=2,
            low_occupancy=-1.0,  # floor backfill only (see elastic_soak)
        ),
        executor=GenerationTierExecutor(learner, fleet),
        signal_source=disagg_signal_source(learner),
    ).start()

    t0 = time.monotonic()
    seqs = []
    killed = []
    pending_learn = []
    learn_steps = 0

    def pseudo_learn(batch) -> None:
        # the soak is jax-free, so the "learn step" is a stamp-only twin of
        # DisaggSequenceRLTrainer's: the same record_consumption_trace call
        # with monotonic stamps around the (trivial) consumption work —
        # every accepted sequence's trace still ends in seq.learn_step
        nonlocal learn_steps
        learn_steps += 1
        now = time.monotonic()
        record_consumption_trace(
            batch, now, now, now, now, time.monotonic(), learn_steps
        )

    try:
        deadline = t0 + args.deadline_s
        while len(seqs) < n and time.monotonic() < deadline:
            s = learner.get_sequence(timeout=0.2)
            if s is not None:
                seqs.append(s)
                if args.trace_dir:
                    pending_learn.append(s)
                    if len(pending_learn) >= LEARN_BATCH:
                        pseudo_learn(pending_learn)
                        pending_learn = []
            if not killed and len(seqs) >= args.warmup:
                # the seeded wave: half the generation hosts, mid-decode
                killed = fleet.chaos_poll()
        if args.trace_dir and pending_learn:
            pseudo_learn(pending_learn)
    finally:
        autoscaler.stop()
        learner.stop()
        fleet.join()

    elapsed = time.monotonic() - t0
    lease_ids = [s.get("lease_id") for s in seqs]
    unique = len(set(lease_ids))
    mismatches = 0
    for s in seqs:
        expect = scripted_sequence_payload(
            s["seed"], RESPONSE_LEN, VOCAB, s["generation"]
        )
        for key in ("prompt", "response_tokens", "behavior_logp", "values"):
            if not np.array_equal(s[key], expect[key]):
                mismatches += 1
                break
    waves = telemetry.get_recorder().events("mass_kill")
    verdict = {
        "metric": "disagg_soak",
        "expected": n,
        "received": len(seqs),
        "unique": unique,
        "lost": n - unique,
        # duplicates that REACHED the consumer (must be 0: the dedup
        # layers absorb redelivery); absorbed ones are the design working
        "duplicates": len(seqs) - unique,
        "payload_mismatches": mismatches,
        "absorbed_duplicates": learner.duplicate_sequences
        + learner.duplicate_leases,
        "requeued_leases": learner.requeued_leases,
        "hosts_killed": len(killed),
        "waves": len(waves),
        "scale_ups": autoscaler.scale_ups,
        "scale_downs": autoscaler.scale_downs,
        "snapshot_wire_bytes": learner.snapshot_wire_bytes,
        "elapsed_s": round(elapsed, 1),
        "chaos": os.environ.get(chaos.ENV_VAR, ""),
    }
    print(json.dumps(verdict), flush=True)
    ok = (
        verdict["lost"] == 0
        and verdict["duplicates"] == 0
        and verdict["payload_mismatches"] == 0
        and len(killed) > 0
        and autoscaler.scale_ups >= 1
    )
    if args.trace_dir:
        # merge the per-host span files and gate on trace completeness:
        # every accepted sequence must have one root-to-learn-step trace
        # with zero orphan spans (the trace_report verdict line printed
        # here is what a caller gates on)
        tracing.export_skew()
        from tools.trace_report import build_report, print_report, write_chrome

        report = build_report(args.trace_dir)
        tv = report["verdict"]
        tv["chrome"] = write_chrome(
            report, os.path.join(args.trace_dir, "trace_events.json")
        )
        tv["expected_sequences"] = len(seqs)
        print_report(report)
        print(json.dumps(tv), flush=True)
        ok = ok and (
            tv["orphan_spans"] == 0
            and tv["incomplete"] == 0
            and tv["sequence_traces"] >= len(seqs)
        )
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
