"""Diurnal traffic replay over real sockets: the front door's soak harness.

Drives the :class:`~scalerl_tpu.serving.router.ServingRouter` with an
OPEN-LOOP arrival process shaped like real traffic — a diurnal sinusoid
modulating a Poisson stream, with periodic burst overlays — through
thousands of :class:`RemotePolicyClient` instances dialing the router's
REAL listening socket (not in-process pipes: the codec framing, the
accept path, and the ``route_sock`` chaos site are all on the wire).
Replicas are jax-free SCRIPTED servers (seeded service-time
distribution, serial worker queue) so the harness measures the
*traffic plane* — routing, queueing, failover — not model math, and runs
in CI without an accelerator.

While the replay runs, the streaming tier attribution
(:class:`~scalerl_tpu.runtime.attribution.TierLedger`) decomposes every
sampled request into named tier edges ONLINE — per-edge durations sum to
the end-to-end latency exactly — and the final verdict names the
``bottleneck_tier`` (largest p95 share of the critical path).  The last
stdout line is a one-line JSON verdict (``{"metric": "traffic_replay",
...}``) for a caller to gate the traffic-replay soak
on:

- **exact accounting**: ``admitted == answered + shed + orphaned`` at
  quiesce (the chaos e2e's equation);
- **attribution completeness**: every sampled root decomposed, zero
  orphaned traces, ``max_sum_err`` at float-noise level;
- **digest honesty**: the log-bucket digest's p99 within its configured
  relative-error bound of the exact percentile over the SAME samples.

Fault sites: ``--kill-replica-at`` closes one scripted replica's link
mid-run (death verdict -> eject -> re-dispatch), ``--rollout-at`` runs a
rolling weight rollout mid-run (drain/push/readmit phase events land in
the flight recorder), and the links carry chaos sites
(``route_sock`` on client sockets, ``replay_replica`` on replica pipes)
so the chaos injector's env knobs compose with the replay unchanged.

Arrivals: ``rate(t) = base_rps * (1 + depth * sin(2*pi*t / period))``
thinned from a max-rate Poisson stream (Lewis-Shedler), plus ``burst_n``
back-to-back requests every ``burst_every_s``; ``--trace-file`` replays
recorded arrival offsets (one float seconds-from-start per line)
instead.  Latency is measured from the SCHEDULED arrival, so schedule
slip counts against the tier.  Everything is seeded (``--seed``).

jax-free: imports serving submodules directly (the package __init__
pulls the jitted server).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from scalerl_tpu.fleet.transport import PipeConnection, connect_socket
from scalerl_tpu.runtime import telemetry, tracing
from scalerl_tpu.runtime.attribution import TierLedger
from scalerl_tpu.runtime.supervisor import is_heartbeat, make_pong
from scalerl_tpu.serving.client import RemotePolicyClient
from scalerl_tpu.serving.router import (
    ReplicaHandle,
    RouterConfig,
    ServingRouter,
)

# the replay's observation shape: tiny on purpose — the codec cost per
# frame should be wire overhead, not payload serialization
LANES, OBS_DIM, NUM_ACTIONS = 1, 8, 4

PHASE_NAMES = ("rise", "peak", "fall", "trough")


def replica_pair() -> Tuple[PipeConnection, PipeConnection]:
    """A duplex pipe pair for the router<->scripted-replica link, under
    its own chaos site so the injector can fault replica links without
    touching the client sockets."""
    import multiprocessing as mp

    a, b = mp.Pipe(duplex=True)
    return (
        PipeConnection(a, chaos_site="replay_replica"),
        PipeConnection(b, chaos_site="replay_replica"),
    )


class ScriptedReplica:
    """A jax-free stand-in for ``InferenceServer`` behind the router.

    A reader thread enqueues act frames with their arrival stamp; ONE
    serial worker pops them, sleeps a seeded lognormal service time, and
    replies — so queueing under bursts is real, and the replica records
    the same ``serve.queue_wait`` / ``serve.flush`` spans the real server
    stamps (the tier ledger cannot tell them apart).  Speaks the router's
    control frames (``router_hello``, ``health``, ping/pong) and exposes
    ``push_params`` so rolling rollouts exercise the drain protocol.
    """

    def __init__(
        self,
        name: str,
        conn: PipeConnection,
        service_ms: float = 2.0,
        jitter: float = 0.25,
        seed: int = 0,
    ) -> None:
        self.name = name
        self.conn = conn
        self.service_s = service_ms / 1e3
        self.jitter = jitter
        self.gen = 0
        self.served = 0
        self.killed = False
        self._rng = np.random.default_rng(seed)
        self._queue: "List[Tuple[Dict[str, Any], float]]" = []
        self._cv = threading.Condition()
        self._stop = threading.Event()
        self._send_lock = threading.Lock()
        self._threads = [
            threading.Thread(target=self._read_loop, daemon=True,
                             name=f"{name}-reader"),
            threading.Thread(target=self._work_loop, daemon=True,
                             name=f"{name}-worker"),
        ]

    def start(self) -> None:
        for t in self._threads:
            t.start()

    def stop(self) -> None:
        self._stop.set()
        with self._cv:
            self._cv.notify_all()
        try:
            self.conn.close()
        except Exception:  # noqa: BLE001 — teardown
            pass
        for t in self._threads:
            t.join(timeout=3.0)

    def kill(self) -> None:
        """The seeded fault: drop the link mid-run.  The router's reader
        sees the dead pipe, ejects, and re-dispatches the in-flight."""
        self.killed = True
        self._stop.set()
        with self._cv:
            self._cv.notify_all()
        try:
            self.conn.close()
        except Exception:  # noqa: BLE001 — the fault IS the close
            pass

    def push_params(self, params: Any, learner_step: Optional[int] = None) -> int:
        self.gen += 1
        return self.gen

    def _send(self, msg: Dict[str, Any]) -> None:
        with self._send_lock:
            self.conn.send(msg)

    def _read_loop(self) -> None:
        while not self._stop.is_set():
            try:
                msg = self.conn.recv(timeout=0.2)
            except TimeoutError:
                continue
            except (ConnectionError, EOFError, OSError, ValueError):
                return
            if not isinstance(msg, dict):
                continue
            if is_heartbeat(msg):
                if msg.get("kind") == "ping":
                    try:
                        self._send(make_pong(msg))
                    except (ConnectionError, OSError):
                        return
                continue
            kind = msg.get("kind")
            try:
                if kind == "router_hello":
                    self._send({"kind": "router_hello", "req": msg.get("req"),
                                "host": self.name, "gen": self.gen})
                elif kind == "health":
                    self._send({
                        "kind": "health_result", "req": msg.get("req"),
                        "p95_ms": self.service_s * 1e3, "shed_total": 0,
                        "pending": len(self._queue), "gen": self.gen,
                        "host": self.name,
                    })
                elif kind == "core_init":
                    self._send({"kind": "core_init", "req": msg.get("req"),
                                "core": (), "gen": self.gen})
                elif kind == "act":
                    with self._cv:
                        self._queue.append((msg, time.monotonic()))
                        self._cv.notify()
            except (ConnectionError, OSError):
                return

    def _work_loop(self) -> None:
        while True:
            with self._cv:
                while not self._queue and not self._stop.is_set():
                    self._cv.wait(timeout=0.2)
                if self._stop.is_set():
                    return
                msg, t_enq = self._queue.pop(0)
            t_flush0 = time.monotonic()
            # seeded lognormal-ish service time: mean service_s, a real tail
            dt = self.service_s * float(
                self._rng.lognormal(mean=0.0, sigma=self.jitter)
            )
            time.sleep(dt)
            t_done = time.monotonic()
            ctx = tracing.extract(msg)
            if ctx is not None:
                # the same two spans the real server stamps per request
                tracing.record_span(
                    "serve.queue_wait", parent=ctx, t_start=t_enq,
                    t_end=t_flush0, kind="serving", replica=self.name,
                )
                tracing.record_span(
                    "serve.flush", parent=ctx, t_start=t_flush0,
                    t_end=t_done, kind="serving", replica=self.name, batch=1,
                )
            batch = int(np.asarray(msg["obs"]).shape[0]) or 1
            try:
                self._send({
                    "kind": "act_result", "req": msg["req"],
                    "action": np.zeros(batch, np.int32),
                    "logits": np.zeros((batch, NUM_ACTIONS), np.float32),
                    "core": (), "gen": self.gen,
                })
                self.served += 1
            except (ConnectionError, OSError):
                return


def _raise_nofile(need: int) -> None:
    """Each socket client costs two fds (client + router side); lift the
    soft RLIMIT_NOFILE toward the hard cap before dialing thousands."""
    try:
        import resource

        soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
        want = min(max(need, soft), hard)
        if want > soft:
            resource.setrlimit(resource.RLIMIT_NOFILE, (want, hard))
    except (ImportError, ValueError, OSError):
        pass


def diurnal_rate(t: float, base: float, depth: float, period: float) -> float:
    return base * (1.0 + depth * math.sin(2.0 * math.pi * t / period))


def phase_of(t: float, period: float) -> str:
    return PHASE_NAMES[int(4.0 * ((t % period) / period)) % 4]


def make_schedule(
    duration_s: float,
    base_rps: float,
    depth: float,
    period_s: float,
    burst_every_s: float,
    burst_n: int,
    seed: int,
    trace_file: Optional[str] = None,
) -> np.ndarray:
    """The full arrival schedule, seconds from start, sorted.  Diurnal x
    Poisson by Lewis-Shedler thinning (draw at the peak rate, accept with
    probability rate(t)/peak), plus burst overlays — or the replayed
    offsets from ``trace_file``."""
    if trace_file:
        offs = []
        with open(trace_file) as f:
            for line in f:
                line = line.strip()
                if line:
                    offs.append(float(line))
        return np.sort(np.asarray(offs, dtype=np.float64))
    rng = np.random.default_rng(seed)
    peak = base_rps * (1.0 + abs(depth))
    arrivals: List[float] = []
    t = 0.0
    while t < duration_s:
        t += float(rng.exponential(1.0 / peak))
        if t >= duration_s:
            break
        if rng.random() * peak <= diurnal_rate(t, base_rps, depth, period_s):
            arrivals.append(t)
    if burst_every_s > 0 and burst_n > 0:
        tb = burst_every_s
        while tb < duration_s:
            arrivals.extend([tb] * burst_n)
            tb += burst_every_s
    return np.sort(np.asarray(arrivals, dtype=np.float64))


class Harvest:
    """One request's outcome, recorded at reply-poll time."""

    __slots__ = ("t_sched", "lat_s", "outcome")

    def __init__(self, t_sched: float, lat_s: float, outcome: str) -> None:
        self.t_sched = t_sched
        self.lat_s = lat_s
        self.outcome = outcome


def run_replay(args: argparse.Namespace) -> Dict[str, Any]:
    _raise_nofile(2 * args.clients + 256)
    tracer = tracing.get_tracer()
    tracer.sample_rate = args.trace_sample
    ledger = TierLedger(
        relative_error=args.relative_error,
        max_pending=max(8192, 4 * args.clients),
        registry=telemetry.get_registry(),
    ).attach(tracer)

    # -- topology: scripted replicas behind a socket-listening router ----
    replicas: List[ScriptedReplica] = []
    handles: List[ReplicaHandle] = []
    for i in range(args.replicas):
        router_end, replica_end = replica_pair()
        rep = ScriptedReplica(
            f"replica{i}", replica_end, service_ms=args.service_ms,
            seed=args.seed + 100 + i,
        )
        rep.start()
        replicas.append(rep)
        handles.append(ReplicaHandle(rep.name, router_end, server=rep))
    router = ServingRouter(
        handles,
        RouterConfig(hedge_budget=2, probe_backoff_s=0.05,
                     drain_timeout_s=2.0, hub_maxsize=4096,
                     seed=args.seed),
    )
    router.start(listen_port=args.listen_port)
    port = router._listen_sock.getsockname()[1]
    print(f"router listening on :{port}; dialing {args.clients} socket "
          f"clients ...", flush=True)

    clients = [
        RemotePolicyClient(
            connect=lambda: connect_socket("127.0.0.1", port, retries=10),
            request_timeout_s=60.0,
        )
        for _ in range(args.clients)
    ]

    # -- the open-loop drive ---------------------------------------------
    schedule = make_schedule(
        args.duration_s, args.base_rps, args.diurnal_depth,
        args.diurnal_period_s, args.burst_every_s, args.burst_n,
        args.seed, args.trace_file,
    )
    duration = float(schedule[-1]) + 0.5 if schedule.size else args.duration_s
    shards = max(1, min(args.shards, args.clients))
    shard_sched = [schedule[i::shards] for i in range(shards)]
    shard_clients = [
        [c for j, c in enumerate(clients) if j % shards == i]
        for i in range(shards)
    ]
    results: List[List[Harvest]] = [[] for _ in range(shards)]
    fired = [0] * shards
    sampled = [0] * shards
    unharvested = [0] * shards
    la = np.zeros(LANES, np.int32)
    rew = np.zeros(LANES, np.float32)
    done_arr = np.zeros(LANES, bool)
    go = threading.Event()
    abort = threading.Event()

    def shard_loop(i: int) -> None:
        local = np.random.default_rng(args.seed + 500 + i)
        mine, sched = shard_clients[i], shard_sched[i]
        inflight: List[Tuple[Any, float, Any]] = []
        go.wait()
        t0 = time.perf_counter()
        k = 0

        def sweep(final: bool = False) -> None:
            deadline = time.perf_counter() + (args.drain_timeout_s if final
                                              else 0.0)
            while True:
                still: List[Tuple[Any, float, Any]] = []
                for pending, t_sched, span in inflight:
                    if not pending.done():
                        still.append((pending, t_sched, span))
                        continue
                    t_done = time.perf_counter()
                    try:
                        reply = pending.result(timeout=0)
                    except (TimeoutError, ConnectionError):
                        span.end(outcome="lost")
                        results[i].append(Harvest(t_sched, 0.0, "lost"))
                        continue
                    if reply.get("shed"):
                        span.end(outcome="shed")
                        results[i].append(Harvest(t_sched, 0.0, "shed"))
                    else:
                        span.end(outcome="ok")
                        results[i].append(
                            Harvest(t_sched, t_done - (t0 + t_sched), "ok")
                        )
                inflight[:] = still
                if not final or not inflight or time.perf_counter() > deadline:
                    break
                time.sleep(0.005)
            if final:
                # anything still pending never came back: end the span so
                # the trace decomposes (never an attribution orphan), and
                # count it against the harness, not the router ledger
                for pending, t_sched, span in inflight:
                    span.end(outcome="lost")
                    results[i].append(Harvest(t_sched, 0.0, "lost"))
                    unharvested[i] += 1
                inflight.clear()

        while k < sched.size and not abort.is_set():
            now = time.perf_counter() - t0
            while k < sched.size and float(sched[k]) <= now:
                t_sched = float(sched[k])
                c = mine[k % len(mine)]
                span = tracing.start_span("traffic.request", kind="serving",
                                          phase=phase_of(
                                              t_sched, args.diurnal_period_s))
                msg = c._act_msg(
                    local.normal(size=(LANES, OBS_DIM)).astype(np.float32),
                    la, rew, done_arr, (),
                )
                tracing.inject(msg, span)
                try:
                    inflight.append((c._submit(msg), t_sched, span))
                except ConnectionError:
                    span.end(outcome="dial_lost")
                    results[i].append(Harvest(t_sched, 0.0, "lost"))
                fired[i] += 1
                if span.sampled:
                    sampled[i] += 1
                k += 1
            sweep()
            nxt = float(sched[k]) if k < sched.size else now
            time.sleep(min(0.002, max(nxt - (time.perf_counter() - t0), 0.0)))
        sweep(final=True)

    threads = [
        threading.Thread(target=shard_loop, args=(i,), daemon=True,
                         name=f"replay-shard{i}")
        for i in range(shards)
    ]
    for t in threads:
        t.start()

    killer: Optional[threading.Thread] = None
    if args.kill_replica_at > 0:
        victim = replicas[args.kill_replica % len(replicas)]

        def kill() -> None:
            go.wait()
            time.sleep(args.kill_replica_at)
            print(f"[fault] killing {victim.name} at t={args.kill_replica_at:g}s",
                  flush=True)
            victim.kill()

        killer = threading.Thread(target=kill, daemon=True, name="replay-kill")
        killer.start()

    roller: Optional[threading.Thread] = None
    if args.rollout_at > 0:

        def roll() -> None:
            go.wait()
            time.sleep(args.rollout_at)
            print(f"[rollout] rolling weights at t={args.rollout_at:g}s",
                  flush=True)
            router.rollout(params=None, learner_step=1)

        roller = threading.Thread(target=roll, daemon=True, name="replay-roll")
        roller.start()

    t_start = time.perf_counter()
    go.set()
    for t in threads:
        t.join(timeout=duration + 120.0)
        if t.is_alive():
            abort.set()
    elapsed = time.perf_counter() - t_start
    if killer is not None:
        killer.join(timeout=5.0)
    if roller is not None:
        roller.join(timeout=30.0)

    # quiesce the router before reading the accounting ledger
    deadline = time.monotonic() + 10.0
    while router.stats()["inflight"] > 0 and time.monotonic() < deadline:
        time.sleep(0.01)
    stats = router.stats()
    ledger.drain()

    # -- verdict assembly ------------------------------------------------
    all_h = [h for shard in results for h in shard]
    ok_lat = np.sort(np.asarray(
        [h.lat_s for h in all_h if h.outcome == "ok"], dtype=np.float64))
    answered = int(ok_lat.size)
    shed_total = sum(1 for h in all_h if h.outcome == "shed")
    lost_total = sum(1 for h in all_h if h.outcome == "lost")
    good = int(np.searchsorted(ok_lat, args.slo_ms / 1e3, side="right"))

    def _q(arr: np.ndarray, q: float) -> float:
        if not arr.size:
            return 0.0
        return float(arr[min(int(q * (arr.size - 1)), arr.size - 1)])

    # digest honesty check: the SAME samples through the sketch vs exact.
    # The sketch guarantees |est - exact| <= relerr * exact at any count
    # (exact = the lower-rank order statistic the bucket walk targets)
    from scalerl_tpu.runtime.attribution import LatencyDigest

    check = LatencyDigest(relative_error=args.relative_error)
    check.observe_array(ok_lat)
    p99_exact = _q(ok_lat, 0.99)
    p99_digest = check.quantile(0.99)
    digest_rel_err = (abs(p99_digest - p99_exact) / p99_exact
                      if p99_exact > 0 else 0.0)
    digest_ok = digest_rel_err <= args.relative_error + 1e-9

    # per-phase goodput/SLO accounting (diurnal quadrants)
    phases: Dict[str, Dict[str, Any]] = {}
    period = args.diurnal_period_s
    phase_time: Dict[str, float] = {p: 0.0 for p in PHASE_NAMES}
    grid = np.arange(0.0, duration, 1e-2)
    for tt in grid:
        phase_time[phase_of(float(tt), period)] += 1e-2
    for h in all_h:
        p = phases.setdefault(phase_of(h.t_sched, period), {
            "offered": 0, "answered": 0, "good": 0, "shed": 0, "lost": 0,
        })
        p["offered"] += 1
        if h.outcome == "ok":
            p["answered"] += 1
            if h.lat_s <= args.slo_ms / 1e3:
                p["good"] += 1
        elif h.outcome == "shed":
            p["shed"] += 1
        else:
            p["lost"] += 1
    for name, p in phases.items():
        secs = phase_time.get(name, 0.0) or 1.0
        p["goodput_rps"] = round(p["good"] / secs, 1)
        p["offered_rps"] = round(p["offered"] / secs, 1)

    total_fired = sum(fired)
    total_sampled = sum(sampled)
    balanced = (stats["answered"] + stats["shed"] + stats["orphaned"]
                == stats["admitted"])
    bn = ledger.bottleneck()
    attribution_complete = (
        bn["decomposed"] == total_sampled and bn["orphans"] == 0
    )

    verdict: Dict[str, Any] = {
        "metric": "traffic_replay",
        "clients": args.clients,
        "replicas": args.replicas,
        "shards": shards,
        "duration_s": round(elapsed, 2),
        "base_rps": args.base_rps,
        "diurnal_depth": args.diurnal_depth,
        "diurnal_period_s": args.diurnal_period_s,
        "seed": args.seed,
        "fired": total_fired,
        "answered": answered,
        "good": good,
        "shed": shed_total,
        "lost": lost_total,
        "unharvested": sum(unharvested),
        "goodput_rps": round(good / elapsed, 1) if elapsed else 0.0,
        "offered_rps": round(total_fired / elapsed, 1) if elapsed else 0.0,
        "slo_ms": args.slo_ms,
        "p50_ms": round(_q(ok_lat, 0.50) * 1e3, 3),
        "p95_ms": round(_q(ok_lat, 0.95) * 1e3, 3),
        "p99_ms": round(_q(ok_lat, 0.99) * 1e3, 3),
        "router": {
            "admitted": stats["admitted"],
            "answered": stats["answered"],
            "shed": stats["shed"],
            "orphaned": stats["orphaned"],
            "retries": stats["retries"],
            "redispatches": stats["redispatches"],
            "duplicate_replies": stats["duplicate_replies"],
            "ejections": stats["ejections"],
            "readmissions": stats["readmissions"],
            "rollouts": stats["rollouts"],
            "breaker": stats["breaker"],
        },
        "accounting_balanced": balanced,
        "bottleneck_tier": bn["bottleneck_tier"],
        "tiers": bn["tiers"],
        "attribution": {
            "sampled": total_sampled,
            "decomposed": bn["decomposed"],
            "orphans": bn["orphans"],
            "late_spans": bn["late_spans"],
            "max_sum_err_s": bn["max_sum_err_s"],
            "complete": attribution_complete,
        },
        "digest_check": {
            "p99_exact_ms": round(p99_exact * 1e3, 3),
            "p99_digest_ms": round(p99_digest * 1e3, 3),
            "rel_err": round(digest_rel_err, 5),
            "bound": args.relative_error,
            "ok": digest_ok,
        },
        "phases": phases,
        "fault": (
            {"kill_replica": replicas[args.kill_replica % len(replicas)].name,
             "at_s": args.kill_replica_at}
            if args.kill_replica_at > 0 else None
        ),
    }

    # teardown
    for c in clients:
        c.close()
    router.stop()
    for rep in replicas:
        rep.stop()
    ledger.detach(tracer)
    return verdict


def print_verdict(v: Dict[str, Any], out=sys.stdout) -> None:
    print(
        f"traffic replay: {v['fired']} fired over {v['duration_s']}s "
        f"({v['offered_rps']} rps offered) -> {v['answered']} answered, "
        f"{v['shed']} shed, {v['lost']} lost; goodput "
        f"{v['goodput_rps']} rps within {v['slo_ms']:g}ms SLO "
        f"(p50={v['p50_ms']}ms p95={v['p95_ms']}ms p99={v['p99_ms']}ms)",
        file=out,
    )
    r = v["router"]
    print(
        f"router ledger: admitted={r['admitted']} answered={r['answered']} "
        f"shed={r['shed']} orphaned={r['orphaned']} "
        f"(balanced={v['accounting_balanced']}) retries={r['retries']} "
        f"redispatches={r['redispatches']} dup={r['duplicate_replies']} "
        f"ejections={r['ejections']} readmissions={r['readmissions']}",
        file=out,
    )
    a = v["attribution"]
    print(
        f"attribution: {a['decomposed']}/{a['sampled']} sampled traces "
        f"decomposed, {a['orphans']} orphans, {a['late_spans']} late spans, "
        f"max sum error {a['max_sum_err_s'] * 1e6:.3f}us",
        file=out,
    )
    for tier, row in sorted(
        v["tiers"].items(), key=lambda kv: -kv[1]["share"]
    ):
        print(
            f"  {tier:<16} {100 * row['share']:5.1f}%  "
            f"p50={row['p50_ms']:.2f}ms p95={row['p95_ms']:.2f}ms "
            f"p99={row['p99_ms']:.2f}ms  (n={row['count']})",
            file=out,
        )
    d = v["digest_check"]
    print(
        f"digest check: p99 exact={d['p99_exact_ms']}ms "
        f"digest={d['p99_digest_ms']}ms rel_err={d['rel_err']} "
        f"(bound {d['bound']}, ok={d['ok']})",
        file=out,
    )
    for name in PHASE_NAMES:
        p = v["phases"].get(name)
        if p:
            print(
                f"  phase {name:<7} offered={p['offered_rps']}rps "
                f"goodput={p['goodput_rps']}rps good={p['good']}/"
                f"{p['answered']} shed={p['shed']}",
                file=out,
            )
    print(f"bottleneck tier: {v['bottleneck_tier']}", file=out)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--clients", type=int, default=1000)
    p.add_argument("--replicas", type=int, default=3)
    p.add_argument("--shards", type=int, default=16,
                   help="firing threads; each drives clients/shards clients")
    p.add_argument("--duration-s", type=float, default=20.0)
    p.add_argument("--base-rps", type=float, default=300.0)
    p.add_argument("--diurnal-period-s", type=float, default=8.0,
                   help="one compressed 'day' of the sinusoid")
    p.add_argument("--diurnal-depth", type=float, default=0.6)
    p.add_argument("--burst-every-s", type=float, default=2.5)
    p.add_argument("--burst-n", type=int, default=40)
    p.add_argument("--trace-file", default=None,
                   help="replay recorded arrival offsets instead of the "
                   "synthetic diurnal process (one float per line)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--slo-ms", type=float, default=250.0)
    p.add_argument("--service-ms", type=float, default=2.0,
                   help="scripted replica mean service time")
    p.add_argument("--kill-replica-at", type=float, default=0.0,
                   help="seconds into the run to kill one replica (0 = off)")
    p.add_argument("--kill-replica", type=int, default=0)
    p.add_argument("--rollout-at", type=float, default=0.0,
                   help="seconds into the run to trigger a rolling weight "
                   "rollout (0 = off)")
    p.add_argument("--listen-port", type=int, default=0,
                   help="router listening port (0 = ephemeral)")
    p.add_argument("--trace-sample", type=float, default=1.0)
    p.add_argument("--relative-error", type=float, default=0.01)
    p.add_argument("--drain-timeout-s", type=float, default=30.0)
    return p


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    verdict = run_replay(args)
    print_verdict(verdict)
    # the gate line LAST: callers read the newest matching object
    print(json.dumps(verdict), flush=True)
    ok = (
        verdict["accounting_balanced"]
        and verdict["attribution"]["complete"]
        and verdict["digest_check"]["ok"]
        and bool(verdict["bottleneck_tier"])
    )
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
