"""Preemption soak: kill the learner mid-decode, restart it, close the ledger.

The preempt soak (standalone, like the chaos and
disagg soaks): a jax-free THREAD fleet of generation hosts (scripted
engines — deterministic payloads, so bit-exactness is checkable) streams
sequences into a :class:`SequenceLearner` backed by a durable ledger.  A
seeded ``preempt`` draw (the :class:`PreemptionGuard` chaos hook — the same
code path the trainer's learn loop polls) trips mid-consume; the soak runs
the save-and-exit protocol (stop serving, ``save_ledger``), boots a SECOND
learner from the ledger (epoch + 1), and points the fleet's reconnect seam
at it.  Surviving hosts park their in-flight work, redial with capped
backoff, re-handshake via ``gen_welcome``, and resend retained uploads into
the restored dedup tables.

One JSON verdict line gates the step: the ledger must close EXACTLY —
``lost == 0`` (every issued lease's sequence reached the consumer once),
``duplicates == 0`` (consumer-visible; absorbed redelivery is the design
working), ``payload_mismatches == 0`` (every accepted byte re-derived from
the lease seed), ``orphaned_leases == 0`` after the drain, and the restarted
learner's epoch is the predecessor's + 1.

jax-free on purpose: thread-mode hosts never touch jax, so the soak stays
bounded (~1 min) on any CI host, chip or not, while still exercising the
full ledger/epoch/reconnect machinery.

Run: ``python tools/preempt_soak.py`` (options below).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from scalerl_tpu.genrl.disagg import (
    DisaggConfig,
    LocalGenerationFleet,
    ScriptedEngineFactory,
    SequenceLearner,
    scripted_sequence_payload,
)
from scalerl_tpu.runtime import chaos, telemetry
from scalerl_tpu.runtime.supervisor import PreemptionGuard

RESPONSE_LEN = 8
VOCAB = 32


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--leases", type=int, default=72)
    parser.add_argument("--hosts", type=int, default=2)
    parser.add_argument("--lanes", type=int, default=2)
    parser.add_argument("--seed", type=int, default=1234)
    parser.add_argument("--warmup", type=int, default=10,
                        help="sequences consumed before the guard may trip")
    parser.add_argument("--deadline-s", type=float, default=240.0)
    parser.add_argument("--ledger-dir", default="",
                        help="ledger directory (default: a fresh tempdir)")
    args = parser.parse_args()

    # the preempt draw fires on the FIRST guard poll (rate 1.0@1) — the
    # soak polls deliberately after warmup, so the kill is provably
    # mid-decode (open leases, queued sequences) rather than mid-boot
    os.environ.setdefault(chaos.ENV_VAR, f"{args.seed}:preempt=1.0@1")
    chaos.clear()

    scratch = args.ledger_dir or tempfile.mkdtemp(prefix="preempt_soak_")
    ledger_path = os.path.join(scratch, "learner_ledger")

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
    learner = SequenceLearner(cfg, source, ledger_path=ledger_path)
    learner.start()
    rng = np.random.default_rng(0)
    weights = {"w": rng.standard_normal((32, 32)).astype(np.float32)}
    learner.publish(weights, learner_step=0)
    # slow scripted decode (one token per step + a sleep) so leases are
    # genuinely open when the preemption lands.  Thread-mode hosts: the
    # reconnect seam (fleet._dial) is how survivors re-join the restarted
    # learner — the exact elastic-membership path the docs diagram.
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
        use_threads=True,
        auto_chaos=False,  # the guard poll times the kill itself
    )
    fleet.start()

    guard = PreemptionGuard()  # not installed: threads simulate the signal
    t0 = time.monotonic()
    seqs = []
    preempted_at = -1
    epoch_before = learner.learner_epoch
    restarted = None

    try:
        deadline = t0 + args.deadline_s
        while len(seqs) < n and time.monotonic() < deadline:
            active = restarted if restarted is not None else learner
            s = active.get_sequence(timeout=0.2)
            if s is not None:
                seqs.append(s)
            if restarted is None and len(seqs) >= args.warmup:
                if guard.poll_chaos("learner"):
                    # save-and-exit, exactly the trainer's protocol: stop
                    # serving (hosts lose their uplink and start parking),
                    # persist the full plane, boot the successor from the
                    # ledger, then hand the reconnect seam the new learner
                    preempted_at = len(seqs)
                    learner.stop()
                    learner.save_ledger()
                    restarted = SequenceLearner(
                        cfg, source, ledger_path=ledger_path
                    )
                    restarted.start()
                    fleet.adopt_learner(restarted)
    finally:
        for ln in (learner, restarted):
            if ln is not None:
                ln.stop()
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
    post = restarted if restarted is not None else learner
    orphaned = len(post._outstanding)
    resumes = telemetry.get_recorder().events("preemption_resume")
    verdict = {
        "metric": "preempt_soak",
        "expected": n,
        "received": len(seqs),
        "unique": unique,
        "lost": n - unique,
        # duplicates that REACHED the consumer (must be 0: the restored
        # dedup watermarks + completed-lease table absorb redelivery)
        "duplicates": len(seqs) - unique,
        "payload_mismatches": mismatches,
        "orphaned_leases": orphaned,
        "preempted_at": preempted_at,
        "reissued": post.resumed_sequences_reissued,
        "resume_duplicates_dropped": post.resumed_duplicates_dropped,
        "absorbed_duplicates": post.duplicate_sequences
        + post.duplicate_leases,
        "epoch": post.learner_epoch,
        "epoch_bumped": post.learner_epoch == epoch_before + 1,
        "resume_events": len(resumes),
        "ledger_balanced": (
            n - unique == 0 and len(seqs) - unique == 0 and orphaned == 0
        ),
        "elapsed_s": round(elapsed, 1),
        "chaos": os.environ.get(chaos.ENV_VAR, ""),
    }
    print(json.dumps(verdict), flush=True)
    if not args.ledger_dir:
        shutil.rmtree(scratch, ignore_errors=True)
    ok = (
        verdict["ledger_balanced"]
        and verdict["payload_mismatches"] == 0
        and verdict["epoch_bumped"]
        and restarted is not None
        and verdict["resume_events"] >= 1
    )
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
