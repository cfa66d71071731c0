"""Measurement entry point: one process, on the chip, or a non-zero exit.

``python bench.py [--mode ...]`` runs one measurement in THIS process and
prints one JSON line stamped with the device it ran on (``platform``,
``device_kind``, ``device_count``).  The backend is pinned to ``tpu``: with
no chip JAX's own initialization error ends the process — there is no
fallback, no retry and no second process, so a line under a device metric
name always came from the device.

``--cpu`` is an explicit request for the toy-shape CPU run that the tier-1
schema tests also make in-process through the ``_run_*_measurement``
functions; its line says ``"platform": "cpu"`` and carries no FLOP/s or
MFU field (those are device metrics).

Default mode: the fully-fused on-device actor-learner loop
(``scalerl_tpu/runtime/device_loop.py``: env step + AtariNet forward +
action sample + V-trace learner update, all one XLA program) on the
synthetic Atari-shaped pixel env at real frame shapes ``[84, 84, 4]``.
``vs_baseline`` is measured frames/sec/chip over the BASELINE.json north
star (>=100k env-frames/sec aggregate on a v5e-16, i.e. 6,250 per chip).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

BASELINE_FPS_PER_CHIP = 100_000 / 16  # v5e-16 north star, per chip

# Peak dense bf16 FLOP/s of one chip, keyed by the ``device_kind`` JAX
# reports (Google Cloud TPU documentation, per-generation system pages).
# Used only to turn achieved FLOP/s into an MFU fraction; a kind that is
# not listed is an error, never a default.
_PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,  # v5e
    "TPU v5e": 197e12,
    "TPU v6 lite": 918e12,  # v6e / Trillium
    "TPU v6e": 918e12,
    "TPU v5p": 459e12,
    "TPU v5": 459e12,  # v5p as older runtimes report it
    "TPU v4": 275e12,
}


def _peak_flops(device_kind: str) -> float:
    try:
        return _PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s on record for device_kind {device_kind!r}; add "
            "it to bench._PEAK_BF16_FLOPS with its source before reporting "
            "an MFU on it"
        ) from None


def _stamp_mfu(
    result: dict, achieved_flops_per_s: float, platform: str,
    device_kind: str, n_dev: int = 1,
) -> None:
    """Achieved FLOP/s and MFU (over the peak of all ``n_dev`` chips) are
    device metrics: accelerator runs only, and the kind must be known."""
    if platform == "cpu":
        return
    result["achieved_tflops_per_s"] = round(achieved_flops_per_s / 1e12, 2)
    result["mfu"] = round(
        achieved_flops_per_s / (_peak_flops(device_kind) * n_dev), 4
    )


def _emit(result: dict) -> None:
    """Print the one result line, stamped with the device JAX reports."""
    import jax

    devices = jax.devices()
    result["platform"] = devices[0].platform
    result["device_kind"] = devices[0].device_kind
    result["device_count"] = len(devices)
    print(json.dumps(result), flush=True)


def _cost_analysis_flops(compiled) -> float | None:
    """Per-call FLOPs from XLA's cost analysis; None if it reports none."""
    ca = compiled.cost_analysis()
    flops = ca.get("flops") if isinstance(ca, dict) else None
    if flops is None or flops <= 0:
        return None
    return float(flops)


def _run_learn_measurement() -> None:
    """Learner-step-only benchmark: MFU of the IMPALA training update.

    The fused-loop MFU (~0.9% witnessed) is env-step/HBM-bound by design
    — most of its wall-clock is the pixel env scan, not matmuls.  This
    mode isolates the LEARN step (AtariNet forward + V-trace + backward +
    RMSProp over a [T+1, B] trajectory at the north-star shape, bf16
    torso) and reports ITS throughput and MFU — the number comparable to
    supervised-training MFU figures.
    """
    import jax
    import jax.numpy as jnp

    from scalerl_tpu.agents.impala import ImpalaAgent
    from scalerl_tpu.config import ImpalaArguments
    from scalerl_tpu.data.trajectory import Trajectory

    platform = jax.default_backend()
    device_kind = jax.devices()[0].device_kind
    on_accel = platform in ("tpu", "gpu")

    T = 20
    B = 256 if on_accel else 8
    args = ImpalaArguments(
        use_lstm=False, hidden_size=512, rollout_length=T, batch_size=B,
        max_timesteps=0,
        compute_dtype="bfloat16" if on_accel else "float32",
    )
    agent = ImpalaAgent(args, obs_shape=(84, 84, 4), num_actions=6)
    learn = agent.make_learn_fn()
    key = jax.random.PRNGKey(0)
    traj = Trajectory(
        obs=jax.random.randint(key, (T + 1, B, 84, 84, 4), 0, 255, jnp.uint8),
        action=jax.random.randint(key, (T + 1, B), 0, 6, jnp.int32),
        reward=jax.random.normal(key, (T + 1, B), jnp.float32),
        done=jnp.zeros((T + 1, B), jnp.bool_),
        logits=jax.random.normal(key, (T + 1, B, 6), jnp.float32),
        core_state=agent.initial_state(B),
    )
    # AOT-compile once: the same executable runs the measurement and
    # yields XLA's FLOPs estimate (the MFU numerator)
    run_fn = jax.jit(learn).lower(agent.state, traj).compile()
    flops_per_step = _cost_analysis_flops(run_fn)
    from scalerl_tpu.runtime.dispatch import MetricsPipeline

    state, m = run_fn(agent.state, traj)
    float(m["total_loss"])  # warmup barrier: a host fetch of an output
    target_s = 15.0 if on_accel else 4.0
    # pipelined driver: 2 steps in flight, ONE batched metric read per step
    # (lagged — the read blocks on a step the device already finished);
    # drain() is the final host-fetch sync before the clock stops
    pipe = MetricsPipeline(depth=2)
    t0 = time.perf_counter()
    steps = 0
    while time.perf_counter() - t0 < target_s or steps < 2:
        state, m = run_fn(state, traj)
        steps += 1
        pipe.push(steps, m)
    pipe.drain()
    elapsed = time.perf_counter() - t0
    frames = steps * T * B
    result = {
        "metric": "impala_learn_step_frames_per_sec",
        "value": round(frames / elapsed, 1),
        "unit": f"train frames/sec ({platform})",
        "device_kind": device_kind,
        "batch": B,
        "unroll": T,
        "steps_per_sec": round(steps / elapsed, 2),
        "measured_s": round(elapsed, 1),
    }
    if flops_per_step is not None:
        achieved = flops_per_step * steps / elapsed
        _stamp_mfu(result, achieved, platform, device_kind)
    _emit(result)


def _run_sharded_measurement(mesh_spec: str | None) -> None:
    """``--mode sharded``: the dp×mp pjit train step on the transformer
    policy — the big-model learner plane's headline number.

    Builds an IMPALA learn step over ``TransformerPolicyNet`` with the
    policy's heads/mlp/vocab dims sharded over the named ``mp`` axis
    (``parallel/logical.py`` rules), activations constrained batch-over-dp,
    and the state donated; measures train frames/sec and MFU from the
    pjit executable's own cost analysis.  The artifact carries
    ``params_total`` / ``params_per_chip`` / ``mesh`` so a comparison
    stays like-for-like across mesh shapes: a dp=8 number is never held
    against a dp=4,mp=2 run.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from scalerl_tpu.agents.impala import ImpalaAgent
    from scalerl_tpu.config import ImpalaArguments
    from scalerl_tpu.data.trajectory import Trajectory

    platform = jax.default_backend()
    device_kind = jax.devices()[0].device_kind
    on_accel = platform in ("tpu", "gpu")
    n_dev = len(jax.devices())

    spec = mesh_spec or os.environ.get("BENCH_SHARD_MESH")
    if not spec:
        mp = 2 if n_dev % 2 == 0 and n_dev >= 2 else 1
        spec = f"dp={n_dev // mp},mp={mp}" if mp > 1 else f"dp={n_dev}"
    dp = _mesh_axis(spec, "dp")

    # model sized to make the matmuls the story on accelerators; the
    # explicit CPU run proves the code path at toy scale
    if on_accel:
        T, B_chip = 16, 8
        d_model, n_layers, n_heads = 1024, 8, 16
    else:
        T, B_chip = 8, 2
        d_model, n_layers, n_heads = 64, 2, 4
    B = B_chip * dp
    args = ImpalaArguments(
        policy_arch="transformer",
        d_model=d_model, n_layers=n_layers, n_heads=n_heads,
        bf16_params=on_accel,
        rollout_length=T, batch_size=B, use_lstm=False, max_timesteps=0,
        num_actors=1, num_buffers=2,
    )
    obs_dim = 64
    agent = ImpalaAgent(
        args, obs_shape=(obs_dim,), num_actions=16, obs_dtype=jnp.float32
    )
    agent.enable_mesh(spec)

    key = jax.random.PRNGKey(0)
    traj = agent._shard_batch(Trajectory(
        obs=jax.random.normal(key, (T + 1, B, obs_dim), jnp.float32),
        action=jax.random.randint(key, (T + 1, B), 0, 16, jnp.int32),
        reward=jax.random.normal(key, (T + 1, B), jnp.float32),
        done=jnp.zeros((T + 1, B), jnp.bool_),
        logits=jax.random.normal(key, (T + 1, B, 16), jnp.float32),
        core_state=(),
    ))

    def _leaf_elems(x):
        return int(np.prod(x.shape)) if hasattr(x, "shape") else 0

    def _leaf_local_elems(x):
        if not hasattr(x, "sharding"):
            return _leaf_elems(x)
        return int(np.prod(x.sharding.shard_shape(x.shape)))

    p_leaves = jax.tree_util.tree_leaves(agent.state.params)
    params_total = sum(_leaf_elems(x) for x in p_leaves)
    params_per_chip = sum(_leaf_local_elems(x) for x in p_leaves)

    run_fn = agent._learn.lower(agent.state, traj).compile()
    flops_per_step = _cost_analysis_flops(run_fn)

    state, m = run_fn(agent.state, traj)
    float(m["total_loss"])  # warmup barrier: a host fetch of an output

    from scalerl_tpu.runtime.dispatch import MetricsPipeline

    target_s = 15.0 if on_accel else 4.0
    pipe = MetricsPipeline(depth=2)
    t0 = time.perf_counter()
    steps = 0
    while time.perf_counter() - t0 < target_s or steps < 2:
        state, m = run_fn(state, traj)
        steps += 1
        pipe.push(steps, m)
    pipe.drain()
    elapsed = time.perf_counter() - t0
    frames = steps * T * B
    result = {
        "metric": "sharded_train_step_frames_per_sec",
        "mode": "sharded",
        "value": round(frames / elapsed, 1),
        "unit": f"train frames/sec ({platform}, mesh {spec})",
        "mesh": spec,
        "device_kind": device_kind,
        "batch": B,
        "unroll": T,
        "d_model": d_model,
        "num_layers": n_layers,
        "params_total": params_total,
        "params_per_chip": params_per_chip,
        "steps_per_sec": round(steps / elapsed, 2),
        "measured_s": round(elapsed, 1),
    }
    if flops_per_step is not None:
        achieved = flops_per_step * steps / elapsed
        _stamp_mfu(result, achieved, platform, device_kind, n_dev)
    _emit(result)


def _run_serving_measurement() -> None:
    """``--mode serving``: the centralized inference plane's headline
    numbers — act requests/sec through the InferenceServer's dynamic
    batcher, the latency SLO quantiles (p50/p95/p99) from the serving
    histogram, and mean batch occupancy.

    Hermetic in-process shape: N client threads over codec pipe pairs
    hammer a small MLP policy — every byte flows through the same framing/
    batching/flush path remote env-shell hosts use over sockets, so the
    number measures the serving machinery (admission, bucketing, one
    upload + one read per flush), not env dynamics.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from scalerl_tpu.agents.impala import ImpalaAgent
    from scalerl_tpu.config import ImpalaArguments
    from scalerl_tpu.serving import (
        InferenceServer,
        RemotePolicyClient,
        ServingConfig,
        local_pair,
    )

    platform = jax.default_backend()
    device_kind = jax.devices()[0].device_kind
    on_accel = platform in ("tpu", "gpu")
    obs_dim, num_actions = 64, 16
    if on_accel:
        n_clients, lanes, max_batch, target_s = 16, 16, 256, 10.0
    else:
        n_clients, lanes, max_batch = 4, 4, 32
        target_s = float(os.environ.get("BENCH_SERVING_TARGET_S", "4.0"))

    args = ImpalaArguments(
        use_lstm=False, hidden_size=256, rollout_length=8, batch_size=4,
        num_actors=1, num_buffers=2, max_timesteps=0, logger_backend="none",
    )
    agent = ImpalaAgent(
        args, obs_shape=(obs_dim,), num_actions=num_actions,
        obs_dtype=jnp.float32,
    )
    server = InferenceServer(
        agent, ServingConfig(max_batch=max_batch, max_wait_s=0.002)
    )
    server.start()
    clients = []
    for _ in range(n_clients):
        c_end, s_end = local_pair()
        server.add_connection(s_end)
        clients.append(RemotePolicyClient(conn=c_end, request_timeout_s=60.0))

    rng = np.random.default_rng(0)
    obs = rng.normal(size=(lanes, obs_dim)).astype(np.float32)
    la = np.zeros(lanes, np.int32)
    rew = np.zeros(lanes, np.float32)
    done = np.zeros(lanes, bool)

    # warmup: every client round-trips once so the flush buckets compile
    # before the measured window (the steady-state guard arms after this)
    for c in clients:
        c.act(obs, la, rew, done, ())

    stop = threading.Event()
    counts = [0] * n_clients

    def hammer(i: int) -> None:
        c = clients[i]
        while not stop.is_set():
            c.act(obs, la, rew, done, ())
            counts[i] += 1

    threads = [
        threading.Thread(target=hammer, args=(i,), daemon=True)
        for i in range(n_clients)
    ]
    flushes0 = server.flushes
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(target_s)
    stop.set()
    for t in threads:
        t.join(timeout=10.0)
    elapsed = time.perf_counter() - t0
    requests = sum(counts)
    slo = server.slo()
    occ = slo["batch_occupancy_mean"]
    result = {
        "metric": "serving_requests_per_sec",
        "mode": "serving",
        "value": round(requests / elapsed, 1),
        "unit": f"act requests/sec ({platform}, {n_clients} clients x "
                f"{lanes} lanes)",
        "lane_steps_per_sec": round(requests * lanes / elapsed, 1),
        "p50_ms": round(slo["p50_ms"], 3),
        "p95_ms": round(slo["p95_ms"], 3),
        "p99_ms": round(slo["p99_ms"], 3),
        "batch_occupancy": round(occ, 4),
        "flushes": server.flushes - flushes0,
        "shed_total": server.batcher.shed_total,
        "n_clients": n_clients,
        "lanes": lanes,
        "max_batch": max_batch,
        "device_kind": device_kind,
        "measured_s": round(elapsed, 1),
    }
    for c in clients:
        c.close()
    server.stop()
    _emit(result)


def _run_traffic_measurement() -> None:
    """``--mode traffic``: the serving front door's headline number —
    goodput under SLO (requests answered within ``BENCH_TRAFFIC_SLO_MS``
    per second) through the :class:`ServingRouter` over N in-process
    replicas, under OPEN-LOOP arrivals.

    Open-loop is the honest load model for a front door: each client fires
    on a Poisson schedule (plus periodic bursts) regardless of whether the
    previous reply came back, so queueing delay compounds the way real
    traffic makes it compound — a closed loop would self-throttle and hide
    exactly the latency the SLO gate exists to catch.  Latency is measured
    from the request's SCHEDULED arrival, so schedule slip (the client
    thread falling behind) counts against the tier, and every request
    carries a head-sampled trace (the PR 13 context keys), so the router's
    ``router.route`` spans land under each ``traffic.request`` root.

    Exact accounting is asserted before the verdict line: admitted ==
    answered + shed + orphaned at quiesce, the same equation the chaos e2e
    gates on.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from scalerl_tpu.agents.impala import ImpalaAgent
    from scalerl_tpu.config import ImpalaArguments
    from scalerl_tpu.runtime import tracing
    from scalerl_tpu.runtime.attribution import TierLedger
    from scalerl_tpu.serving import (
        InferenceServer,
        RemotePolicyClient,
        RouterConfig,
        ServingConfig,
        ServingRouter,
        connect_replica,
        local_pair,
    )

    platform = jax.default_backend()
    device_kind = jax.devices()[0].device_kind
    on_accel = platform in ("tpu", "gpu")
    obs_dim, num_actions, lanes = 64, 16, 4
    if on_accel:
        n_replicas, n_clients, rps, target_s, slo_ms = 3, 16, 200.0, 10.0, 100.0
    else:
        n_replicas = int(os.environ.get("BENCH_TRAFFIC_REPLICAS", "3"))
        n_clients = int(os.environ.get("BENCH_TRAFFIC_CLIENTS", "4"))
        rps = float(os.environ.get("BENCH_TRAFFIC_RPS", "60"))
        target_s = float(os.environ.get("BENCH_TRAFFIC_TARGET_S", "4.0"))
        slo_ms = float(os.environ.get("BENCH_TRAFFIC_SLO_MS", "250"))

    args = ImpalaArguments(
        use_lstm=False, hidden_size=256, rollout_length=8, batch_size=4,
        num_actors=1, num_buffers=2, max_timesteps=0, logger_backend="none",
    )
    agent = ImpalaAgent(
        args, obs_shape=(obs_dim,), num_actions=num_actions,
        obs_dtype=jnp.float32,
    )
    servers = [
        InferenceServer(agent, ServingConfig(max_batch=32, max_wait_s=0.002))
        for _ in range(n_replicas)
    ]
    for s in servers:
        s.start()
    router = ServingRouter(
        [connect_replica(s, f"replica{i}") for i, s in enumerate(servers)],
        RouterConfig(hedge_budget=2, probe_backoff_s=0.05, seed=0),
    )
    router.start()
    # streaming tier attribution: every sampled traffic.request decomposes
    # online into named tier edges (exact sum), so the goodput verdict can
    # also NAME the bottleneck tier — zero extra round-trips, the spans
    # already flow
    ledger = TierLedger().attach(tracing.get_tracer())
    clients = []
    for _ in range(n_clients):
        c_end, r_end = local_pair()
        router.add_client(r_end)
        clients.append(RemotePolicyClient(conn=c_end, request_timeout_s=60.0))

    rng = np.random.default_rng(0)
    la = np.zeros(lanes, np.int32)
    rew = np.zeros(lanes, np.float32)
    done = np.zeros(lanes, bool)

    # warmup: keep acting until EVERY replica has flushed at least once —
    # affinity routing can pin early traffic to one replica, and a replica
    # that first compiles inside the window torches the latency tail
    warm_deadline = time.monotonic() + 120.0
    while (any(s.flushes == 0 for s in servers)
           and time.monotonic() < warm_deadline):
        for c in clients:
            c.act(rng.normal(size=(lanes, obs_dim)).astype(np.float32),
                  la, rew, done, ())

    per_client_rps = rps / n_clients
    burst_every_s, burst_n = 1.0, max(2, int(per_client_rps // 4))
    stop = threading.Event()
    lat_s: list[list[float]] = [[] for _ in range(n_clients)]
    sheds = [0] * n_clients

    import queue as queue_mod

    def open_loop(i: int) -> None:
        local = np.random.default_rng(1000 + i)
        c = clients[i]
        inflight: queue_mod.Queue = queue_mod.Queue()

        # companion drain: harvests replies AS THEY LAND (per-client reply
        # streams are FIFO-demuxed), so t_done is delivery time, not the
        # end of the window — blocking result() on the oldest first
        def drain() -> None:
            while True:
                item = inflight.get()
                if item is None:
                    return
                pending, t_sched, span = item
                try:
                    reply = pending.result(timeout=30.0)
                except (TimeoutError, ConnectionError):
                    span.end(outcome="lost")
                    continue
                t_done = time.perf_counter()
                if reply.get("shed"):
                    sheds[i] += 1
                    span.end(outcome="shed")
                else:
                    lat_s[i].append(t_done - t_sched)
                    span.end(outcome="ok")

        drainer = threading.Thread(target=drain, daemon=True)
        drainer.start()

        def fire(t_sched: float) -> None:
            span = tracing.start_span("traffic.request", kind="serving")
            msg = c._act_msg(
                local.normal(size=(lanes, obs_dim)).astype(np.float32),
                la, rew, done, (),
            )
            tracing.inject(msg, span)
            inflight.put((c._submit(msg), t_sched, span))

        t0 = time.perf_counter()
        next_poisson = t0 + local.exponential(1.0 / per_client_rps)
        next_burst = t0 + burst_every_s
        while not stop.is_set():
            now = time.perf_counter()
            # fire everything the schedule owes us — open loop never waits
            # on a reply to advance the clock
            while next_poisson <= now:
                fire(next_poisson)
                next_poisson += local.exponential(1.0 / per_client_rps)
            if next_burst <= now:
                for _ in range(burst_n):
                    fire(next_burst)
                next_burst += burst_every_s
            time.sleep(min(0.002, max(next_poisson - now, 0.0)))
        inflight.put(None)
        drainer.join(timeout=60.0)

    threads = [
        threading.Thread(target=open_loop, args=(i,), daemon=True)
        for i in range(n_clients)
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(target_s)
    stop.set()
    for t in threads:
        t.join(timeout=90.0)
    elapsed = time.perf_counter() - t0

    # quiesce, then assert the chaos e2e's accounting equation
    deadline = time.monotonic() + 10.0
    while router.stats()["inflight"] > 0 and time.monotonic() < deadline:
        time.sleep(0.01)
    stats = router.stats()
    balanced = (
        stats["answered"] + stats["shed"] + stats["orphaned"]
        == stats["admitted"]
    )

    ledger.drain()
    ledger.detach(tracing.get_tracer())
    bn = ledger.bottleneck()

    lat = np.sort(np.concatenate([np.asarray(v) for v in lat_s])
                  if any(lat_s) else np.zeros(0))
    answered = int(lat.size)
    good = int(np.searchsorted(lat, slo_ms / 1e3, side="right"))
    shed_total = sum(sheds)

    def _q(q: float) -> float:
        return float(lat[min(int(q * lat.size), lat.size - 1)]) * 1e3 if lat.size else 0.0

    result = {
        "metric": "traffic_goodput_rps",
        "mode": "traffic",
        "value": round(good / elapsed, 1),
        "unit": f"requests answered within {slo_ms:g} ms SLO per sec "
                f"({platform}, {n_replicas} replicas)",
        "offered_rps": round((answered + shed_total) / elapsed, 1),
        "answered": answered,
        "good": good,
        "shed": shed_total,
        "slo_ms": slo_ms,
        "p50_ms": round(_q(0.50), 3),
        "p95_ms": round(_q(0.95), 3),
        "p99_ms": round(_q(0.99), 3),
        "retries": stats["retries"],
        "ejections": stats["ejections"],
        "accounting_balanced": balanced,
        "n_replicas": n_replicas,
        "n_clients": n_clients,
        "lanes": lanes,
        "device_kind": device_kind,
        "measured_s": round(elapsed, 1),
        # the tier verdict (empty when tracing is head-sampled out —
        # SCALERL_TRACE_SAMPLE gates how many requests decompose)
        "bottleneck_tier": bn["bottleneck_tier"],
        "tiers": bn["tiers"],
        "attribution": {
            "decomposed": bn["decomposed"],
            "orphans": bn["orphans"],
            "late_spans": bn["late_spans"],
            "max_sum_err_s": bn["max_sum_err_s"],
        },
    }
    for c in clients:
        c.close()
    router.stop()
    for s in servers:
        s.stop()
    _emit(result)


def _run_genrl_continuous_measurement() -> None:
    """``--mode genrl --continuous``: the continuous-batching decode plane
    vs the fixed-cohort engine, like-for-like (same model, same params,
    same mixed-length prompt distribution, same EOS geometry), in ONE
    artifact — the ISSUE 11 acceptance comparison.

    Workload shape: mixed-length prompts and an EOS token the policy
    actually samples, so response lengths vary — the regime continuous
    batching exists for.  The cohort engine pays the full response bucket
    for every lane regardless (its decode loop is one fused program);
    the continuous engine backfills freed lanes from a Poisson arrival
    queue, so its decode steps stay near-full occupancy of LIVE lanes.
    Decode tokens/s counts REAL (mask=1) tokens for both engines over
    whole-phase wall clock — an honest end-to-end rate, not a
    padding-subtracted estimate.

    ``BENCH_GENRL_GROUP=n`` (ISSUE 14) switches arrivals to GROUP shape:
    every Poisson arrival is one prompt submitted via ``submit_group`` for
    ``n`` completions (the GRPO workload the prefix-CoW fork exists for) —
    the artifact then carries ``group: n`` so the perf gate compares
    like-for-like at the same group shape, and the
    ``prefill_tokens_saved_ratio`` / ``prefix_hit_rate`` fields report how
    much full-page prefix prefill the cache + CoW sharing skipped.
    """
    import jax
    import numpy as np

    from scalerl_tpu.genrl.continuous import (
        ContinuousConfig,
        ContinuousEngine,
    )
    from scalerl_tpu.genrl.engine import GenerationConfig, GenerationEngine
    from scalerl_tpu.models.transformer import TransformerPolicy
    from scalerl_tpu.runtime import telemetry

    platform = jax.default_backend()
    device_kind = jax.devices()[0].device_kind
    on_accel = platform in ("tpu", "gpu")

    # the regime continuous batching exists for: a LONG response budget
    # with a real EOS rate (small vocab => the random-init policy actually
    # samples EOS), so response lengths land well short of the budget —
    # the cohort engine still pays every budget step, the continuous
    # engine backfills the freed lanes
    if on_accel:
        V, d_model, n_layers, n_heads = 32, 256, 4, 8
        P_max, R, lanes = 128, 256, 256
        page_size, macro_steps, min_free = 16, 16, 32
        target_s = 10.0
    else:
        V, d_model, n_layers, n_heads = 8, 64, 1, 4
        P_max, R, lanes = 16, 64, 64
        page_size, macro_steps, min_free = 8, 4, 8
        # schema tests shrink the window (and optionally the lane pool) to
        # stay cheap on the tier-1 clock; the real CPU shape is the default
        target_s = float(os.environ.get("BENCH_GENRL_TARGET_S", "3.0"))
        lanes = int(os.environ.get("BENCH_GENRL_LANES", lanes))
        R = int(os.environ.get("BENCH_GENRL_RESPONSE", R))
    # group-arrival mode: n completions per arriving prompt (1 = the
    # ungrouped workload; its artifact carries no "group" key, so the two
    # shapes never gate each other)
    group = max(int(os.environ.get("BENCH_GENRL_GROUP", "1")), 1)

    base = dict(
        vocab_size=V, max_prompt_len=P_max, max_new_tokens=R,
        temperature=1.0, eos_token=1, seed=0,
    )
    model = TransformerPolicy(
        num_actions=V, vocab_size=V, d_model=d_model, num_heads=n_heads,
        num_layers=n_layers, max_len=2 * (P_max + R),
    )
    params = model.init(
        jax.random.PRNGKey(0),
        jax.numpy.zeros((1, 2), jax.numpy.int32),
    )
    rng = np.random.default_rng(0)

    def sample_prompts(n):
        lengths = rng.integers(2, P_max + 1, size=n).astype(np.int32)
        prompts = rng.integers(2, V, size=(n, P_max)).astype(np.int32)
        return prompts, lengths

    def sample_prompt_batch(n):
        """Group mode tiles each distinct prompt ``group`` times — the
        cohort twin of submit_group, so both phases see the SAME prompt
        distribution at the same group shape."""
        if group > 1:
            k = max(n // group, 1)
            prompts, lengths = sample_prompts(k)
            reps = -(-n // k)
            prompts = np.repeat(prompts, reps, axis=0)[:n]
            lengths = np.repeat(lengths, reps, axis=0)[:n]
            return prompts, lengths
        return sample_prompts(n)

    # phase 1: fixed-cohort rounds at the same lane count
    cohort = GenerationEngine(model, params, GenerationConfig(**base))
    prompts, lengths = sample_prompt_batch(lanes)
    cohort.generate(prompts, lengths)  # warm/compile
    t0 = time.perf_counter()
    cohort_tokens = 0
    cohort_rounds = 0
    while time.perf_counter() - t0 < target_s or cohort_rounds < 2:
        prompts, lengths = sample_prompt_batch(lanes)
        result = cohort.generate(prompts, lengths)
        cohort_tokens += result.decode_tokens
        cohort_rounds += 1
    cohort_elapsed = time.perf_counter() - t0
    cohort_tps = cohort_tokens / cohort_elapsed
    cohort_seq_per_s = cohort_rounds * lanes / cohort_elapsed

    # phase 2: the continuous engine under Poisson prompt arrivals at
    # ~2x the cohort completion rate (saturating: the queue stays fed,
    # admission latency is the congestion signal in the artifact)
    engine = ContinuousEngine(
        model, params,
        ContinuousConfig(
            lanes=lanes, page_size=page_size, steps_per_macro=macro_steps,
            min_free_lanes=min_free,
            # ONE admission prompt bucket: a prefill dispatch per group
            # per bucket is the dominant overhead at CPU shapes, and the
            # pad waste of the collapsed ladder is far cheaper (measured)
            prompt_buckets=(P_max,),
            **base,
        ),
    )
    # arrivals in SEQUENCES stay at ~2x the cohort completion rate; in
    # group mode each Poisson arrival is one prompt fanned into `group`
    # lanes via submit_group (the GRPO shape the prefix-CoW fork serves)
    rate = 2.0 * cohort_seq_per_s / group
    # warm: churn several lane-fills through so the decode program AND the
    # admission (prompt, admit) bucket programs all compile off the clock
    n_warm = max(6 * lanes // group, 2)
    prompts, lengths = sample_prompts(n_warm)
    for i in range(n_warm):
        engine.submit_group(prompts[i], group, lengths[i])
    while engine.live_lanes or engine.pending or engine._inflight:
        engine.step()
    t0 = time.perf_counter()
    next_arrival = rng.exponential(1.0 / rate)
    cont_tokens = 0
    completed = 0
    occ0, macro0 = engine._occupancy_sum, engine.macro_steps
    while time.perf_counter() - t0 < target_s or completed < 2:
        now = time.perf_counter() - t0
        n_new = 0
        while next_arrival <= now:
            n_new += 1
            next_arrival += rng.exponential(1.0 / rate)
        if n_new:
            prompts, lengths = sample_prompts(n_new)
            for i in range(n_new):
                engine.submit_group(prompts[i], group, lengths[i])
        if engine.live_lanes == 0 and engine.pending == 0:
            continue  # idle until the next arrival lands
        done = engine.step()
        completed += len(done)
        cont_tokens += sum(len(c.response_tokens) for c in done)
    cont_elapsed = time.perf_counter() - t0
    cont_tps = cont_tokens / cont_elapsed
    # ratios (not rates): computed over the engine's whole lifetime —
    # warmup included, which runs the same group shape — so a short
    # measured window can never report an empty 0/0 sample
    saved = engine.prefix_tokens_saved
    total = engine.prefix_tokens_total
    hit_num = hit_den = 0
    if engine._prefix_cache is not None:
        hit_num = engine._prefix_cache.hits
        hit_den = hit_num + engine._prefix_cache.misses
    admit_hist = telemetry.get_registry().histogram(
        "genrl.admission_latency_s"
    )

    result_obj = {
        "metric": "genrl_decode_tokens_per_sec_per_chip",
        "mode": "genrl-continuous",
        "value": round(cont_tps, 1),
        "unit": f"decode tokens/sec/chip ({platform}, continuous)",
        "decode_tokens_per_sec": round(cont_tps, 1),
        "cohort_decode_tokens_per_sec": round(cohort_tps, 1),
        "speedup_vs_cohort": round(cont_tps / max(cohort_tps, 1e-9), 3),
        "lane_occupancy_mean": round(
            (engine._occupancy_sum - occ0)
            / max(engine.macro_steps - macro0, 1),
            4,
        ),
        "admission_latency_p50_ms": round(
            admit_hist.quantile(0.50) * 1e3, 3
        ),
        "admission_latency_p95_ms": round(
            admit_hist.quantile(0.95) * 1e3, 3
        ),
        "admission_latency_p99_ms": round(
            admit_hist.quantile(0.99) * 1e3, 3
        ),
        "completed_sequences": completed,
        "arrival_rate_per_s": round(rate, 2),
        "shed_total": engine._batcher.shed_total,
        # shared-prefix reuse (ISSUE 14): fraction of admitted full-page
        # prefix tokens whose prefill was skipped (cache hits + CoW group
        # shares), and the admission-level cache hit rate
        "prefill_tokens_saved_ratio": round(saved / max(total, 1), 4),
        "prefix_hit_rate": round(hit_num / max(hit_den, 1), 4),
        "steps_in_flight": engine.config.steps_in_flight,
        "lanes": lanes,
        "page_size": page_size,
        "macro_steps": macro_steps,
        "pages_capacity": engine.allocator.capacity,
        "vocab": V,
        "d_model": d_model,
        "num_layers": n_layers,
        "prompt_max": P_max,
        "response_budget": R,
        "iter_mode": engine.iter_mode,
        "device_kind": device_kind,
        "measured_s": round(cohort_elapsed + cont_elapsed, 1),
    }
    if group > 1:
        # the group shape keys its own like-for-like perf-gate history
        result_obj["group"] = group
    # packed-learner A/B fields (ISSUE 15) ride this artifact too — the
    # continuous plane feeds the same learner, so its artifact reports
    # the learn-side pad economics alongside the decode ones (the
    # token_ppo_learn_tokens_per_sec_per_chip field).  BENCH_SKIP_LEARN_AB=1 drops the phase for callers that
    # only exercise the decode planes (the group-shape schema test).
    if not os.environ.get("BENCH_SKIP_LEARN_AB"):
        result_obj.update(_packed_learn_phase(on_accel))
    _emit(result_obj)


def _run_disagg_measurement() -> None:
    """``--mode disagg``: the disaggregated dataflow's headline numbers —
    end-to-end sequences/s through the full wire path (generation hosts
    behind jax-free shells -> codec-v2 pipe frames -> lease/ack/dedup ->
    the learner's accepted-sequence queue) and snapshot-push latency
    (``SequenceLearner.publish`` of an int8-quantized wire snapshot ->
    first accepted sequence decoded under the new generation).

    Hosts run as in-process threads with REAL fixed-cohort engines: the
    wire, lease accounting, and quantized snapshot adoption all flow
    exactly as in the process topology, without charging the bench two
    jax process spin-ups.
    """
    import threading as _threading

    import jax
    import numpy as np

    from scalerl_tpu.config import GenRLArguments
    from scalerl_tpu.genrl.disagg import (
        DisaggConfig,
        LocalGenerationFleet,
        SequenceLearner,
    )
    from scalerl_tpu.genrl.task import TokenRecallTask
    from scalerl_tpu.trainer.sequence_rl import (
        _CohortShellFactory,
        build_genrl_model,
    )

    platform = jax.default_backend()
    device_kind = jax.devices()[0].device_kind
    on_accel = platform in ("tpu", "gpu")

    if on_accel:
        V, d_model, n_layers, n_heads = 1024, 256, 4, 8
        P, R, lanes = 128, 128, 32
        target_s = 10.0
    else:
        V, d_model, n_layers, n_heads = 32, 32, 1, 4
        P, R, lanes = 8, 4, 4
        target_s = float(os.environ.get("BENCH_DISAGG_TARGET_S", "3.0"))

    args = GenRLArguments(
        vocab_size=V, prompt_len=P, max_new_tokens=R,
        d_model=d_model, n_layers=n_layers, n_heads=n_heads,
        telemetry_interval_s=0.0, logger_backend="none",
    )
    task = TokenRecallTask(vocab_size=V, prompt_len=P, response_len=R)
    model = build_genrl_model(args)
    params = model.init(
        jax.random.PRNGKey(0), jax.numpy.zeros((1, 2), jax.numpy.int32)
    )
    host_weights = jax.device_get(params)

    rng = np.random.default_rng(0)
    lease_lock = _threading.Lock()
    lease_seq = {"i": 0}

    def source():
        with lease_lock:
            lease_seq["i"] += 1
            prompts, lengths = task.sample_prompts(1, rng)
        n = int(lengths[0])
        return {
            "seed": lease_seq["i"],
            "prompt": prompts[0, :n].astype(np.int32),
            "length": n,
        }

    cfg = DisaggConfig(
        num_hosts=2, lanes_per_host=lanes, upload_batch=lanes,
        snapshot_quantize="int8", seq_maxsize=16 * lanes,
    )
    learner = SequenceLearner(cfg, source)
    learner.start()
    t_pub0 = time.perf_counter()
    learner.publish(host_weights, learner_step=0)
    quantize_ms = (time.perf_counter() - t_pub0) * 1e3
    fleet = LocalGenerationFleet(
        learner, cfg, _CohortShellFactory(args, lanes), use_threads=True
    )
    fleet.start()

    def drain_one(timeout=0.2):
        return learner.get_sequence(timeout=timeout)

    # warmup: both hosts compile their round program off the clock
    warm = 0
    warm_deadline = time.monotonic() + 300
    while warm < 4 * lanes and time.monotonic() < warm_deadline:
        if drain_one() is not None:
            warm += 1

    # measured window: accepted sequences over wall clock, with snapshot
    # pushes fired at quarter-window marks to measure publish->adoption
    t0 = time.perf_counter()
    accepted = 0
    push_lat_ms = []
    next_push = t0 + target_s / 4
    pending_push = None  # (generation, t_pub)
    step_count = 0
    while time.perf_counter() - t0 < target_s or accepted < 2:
        s = drain_one()
        now = time.perf_counter()
        if s is not None:
            accepted += 1
            if pending_push is not None and s["generation"] >= pending_push[0]:
                push_lat_ms.append((now - pending_push[1]) * 1e3)
                pending_push = None
        if pending_push is None and now >= next_push:
            step_count += 1
            gen = learner.publish(host_weights, learner_step=step_count)
            pending_push = (gen, time.perf_counter())
            next_push = now + target_s / 4
    elapsed = time.perf_counter() - t0
    learner.stop()
    fleet.join()

    result_obj = {
        "metric": "disagg_sequences_per_sec",
        "mode": "disagg",
        "value": round(accepted / elapsed, 2),
        "unit": f"end-to-end sequences/sec ({platform}, 2 hosts over the "
        "pipe wire)",
        "sequences_per_sec": round(accepted / elapsed, 2),
        "snapshot_push_latency_ms_p50": round(
            float(np.median(push_lat_ms)), 2
        )
        if push_lat_ms
        else None,
        # real tail quantiles (exact percentile over every sample, not the
        # reservoir max standing in for one)
        "snapshot_push_latency_ms_p95": round(
            float(np.percentile(push_lat_ms, 95)), 2
        )
        if push_lat_ms
        else None,
        "snapshot_push_latency_ms_p99": round(
            float(np.percentile(push_lat_ms, 99)), 2
        )
        if push_lat_ms
        else None,
        "snapshot_push_latency_ms_max": round(max(push_lat_ms), 2)
        if push_lat_ms
        else None,
        "snapshot_quantize_ms": round(quantize_ms, 2),
        "snapshot_wire_bytes": learner.snapshot_wire_bytes,
        "snapshot_pushes": step_count,
        "accepted_sequences": accepted,
        "duplicates_absorbed": learner.duplicate_sequences
        + learner.duplicate_leases,
        "dropped_stale": learner.dropped_sequences,
        # preemption plane (ISSUE 19): a fresh bench learner sits at
        # epoch 1 with zero resume traffic — the fields exist so a bench
        # run that ever rides a restored ledger is distinguishable
        "learner_epoch": learner.learner_epoch,
        "resumed_sequences_reissued": learner.resumed_sequences_reissued,
        "resumed_duplicates_dropped": learner.resumed_duplicates_dropped,
        "hosts": cfg.num_hosts,
        "lanes_per_host": lanes,
        "vocab": V,
        "d_model": d_model,
        "num_layers": n_layers,
        "prompt_bucket": P,
        "response_bucket": R,
        "device_kind": device_kind,
        "measured_s": round(elapsed, 1),
    }
    _emit(result_obj)


def _packed_learn_phase(on_accel: bool) -> dict:
    """Packed-vs-padded token-PPO learn A/B (ISSUE 15) on a MIXED-length
    workload (mean true length <= half the bucket — the regime the
    bin-packer exists for).

    The same agent runs both layouts: its learn fn dispatches on the
    batch's ``segment_ids`` key, so the A/B holds params, optimizer, and
    metric discipline constant and varies ONLY the input layout.  Both
    rates count REAL (response, mask=1) tokens over wall clock — the
    padded path is penalized exactly by the pad FLOPs it burns, which is
    the honest comparison.  Returns the artifact fields; the headline
    ``token_ppo_learn_tokens_per_sec_per_chip`` is the PACKED rate.
    """
    import jax
    import numpy as np

    from scalerl_tpu.agents.token_ppo import TokenPPOAgent
    from scalerl_tpu.config import GenRLArguments
    from scalerl_tpu.genrl.rollout import pack_learner_batch
    from scalerl_tpu.runtime.dispatch import MetricsPipeline
    from scalerl_tpu.trainer.sequence_rl import build_genrl_model
    from scalerl_tpu.utils.buckets import bucket_for, default_buckets

    if on_accel:
        V, d_model, n_layers, n_heads = 1024, 256, 4, 8
        P = R = 128
        B = 64
        target_s = 5.0
    else:
        V, d_model, n_layers, n_heads = 32, 32, 1, 4
        P = R = 32
        B = 16
        target_s = 0.75
    target_s = float(os.environ.get("BENCH_LEARN_TARGET_S", target_s))

    args = GenRLArguments(
        vocab_size=V, prompt_len=P, max_new_tokens=R,
        d_model=d_model, n_layers=n_layers, n_heads=n_heads,
        genrl_batch=B, genrl_sample_batch=B,
        genrl_buffer_sequences=2 * B, learner_packing=True,
        telemetry_interval_s=0.0, logger_backend="none",
    )
    agent = TokenPPOAgent(args, build_genrl_model(args))
    S = P + R
    rng = np.random.default_rng(0)
    # mixed lengths, mean <= half the bucket on both axes
    plens = rng.integers(1, P // 2 + 1, B)
    rlens = rng.integers(1, R // 2 + 1, B)
    prompts = [rng.integers(1, V, n).astype(np.int32) for n in plens]
    resps = [rng.integers(1, V, n).astype(np.int32) for n in rlens]
    logps = [
        np.log(rng.uniform(0.05, 0.5, n)).astype(np.float32)
        for n in rlens
    ]
    vals = [rng.normal(0, 0.1, n).astype(np.float32) for n in rlens]
    rewards = rng.uniform(0, 1, B).astype(np.float32)
    gens = np.zeros(B, np.int32)

    # padded bucket-pair layout (the parity twin)
    tokens = np.zeros((B, S), np.int32)
    blogp = np.zeros((B, R), np.float32)
    bval = np.zeros((B, R), np.float32)
    mask = np.zeros((B, R), np.float32)
    for i in range(B):
        n, r = int(plens[i]), int(rlens[i])
        tokens[i, P - n : P] = prompts[i]
        tokens[i, P : P + r] = resps[i]
        blogp[i, :r] = logps[i]
        bval[i, :r] = vals[i]
        mask[i, :r] = 1.0
    padded = jax.device_put({
        "tokens": tokens, "behavior_logp": blogp, "value": bval,
        "mask": mask, "reward": rewards,
        "prompt_len": plens.astype(np.int32), "generation": gens,
    })
    pk = pack_learner_batch(
        prompts, resps, logps, vals, rewards, gens, pack_len=S
    )
    pk = pk.bucketed(bucket_for(max(pk.rows, 1), default_buckets(B)))
    fields, _prio = pk.fields()
    packed = jax.device_put(fields)
    real_tokens = int(mask.sum())

    def _measure(batch):
        m = agent.learn_device(batch)
        float(jax.device_get(m["total_loss"]))  # compile + sync
        pipe = MetricsPipeline(depth=2)
        t0 = time.perf_counter()
        steps = 0
        while time.perf_counter() - t0 < target_s or steps < 2:
            m = agent.learn_device(batch)
            steps += 1
            pipe.push(steps, m)
        pipe.drain()
        return steps * real_tokens / (time.perf_counter() - t0)

    padded_tps = _measure(padded)
    packed_tps = _measure(packed)
    return {
        "token_ppo_learn_tokens_per_sec_per_chip": round(packed_tps, 1),
        "padded_learn_tokens_per_sec": round(padded_tps, 1),
        "learn_speedup_vs_padded": round(
            packed_tps / max(padded_tps, 1e-9), 3
        ),
        # pad fraction of the PADDED layout on this workload — what the
        # packed path stops paying for (the OBSERVABILITY.md math)
        "learn_pad_ratio": round(
            1.0 - (int(plens.sum()) + real_tokens) / (B * S), 4
        ),
        "learn_packed_pad_ratio": round(pk.pad_ratio, 4),
        "learn_packed_rows": pk.rows,
        "learn_pack_len": S,
        "learn_batch_sequences": B,
    }


def _spec_decode_phase(on_accel: bool) -> dict:
    """Speculative-decode A/B (ISSUE 16): the continuous engine at the
    SAME shape/model/params/prompt distribution, speculation off vs on,
    in one artifact.

    Workload: token-recall prompts decoded greedily with a fixed response
    budget, so both engines emit the SAME tokens per round (greedy is
    deterministic and both see identical prompts) and the rate ratio is a
    pure speed ratio.  Greedy decode of the bench policy settles into
    repetitive continuations — exactly the structure the n-gram
    self-drafter exploits — so the reported ``spec_acceptance_rate``
    shows the regime where speculation pays; on incompressible output it
    degrades toward 1 token/pass (the docs/SEQUENCE_RL.md
    acceptance-rate table).

    Measurement design, tuned for a noisy CPU substrate:

    - **interleaved rounds** — each measured round runs through the OFF
      engine then the ON engine back-to-back, so host-load drift hits
      both sides equally instead of whichever phase ran second;
    - **long responses** — every lane occupancy re-pays the drafter's
      cold ramp (the AIMD cap regrows 1 -> 2 -> 4 -> ... -> k through
      the verify ladder's narrow buckets), a fixed per-occupancy cost
      that only amortizes when the steady full-``k`` stretch dominates.
      At the default response budget the spec side clears >1.2x on CPU;
      at short budgets the ramp eats the win — which is itself the
      honest answer the A/B exists to report.

    The headline ``genrl_spec_accepted_tokens_per_sec`` counts accepted
    (real) tokens over whole-round wall clock."""
    import jax
    import numpy as np

    from scalerl_tpu.genrl.continuous import (
        ContinuousConfig,
        ContinuousEngine,
    )
    from scalerl_tpu.genrl.task import TokenRecallTask
    from scalerl_tpu.models.transformer import TransformerPolicy

    R = int(os.environ.get("BENCH_SPEC_RESPONSE", "512"))
    k = int(os.environ.get("BENCH_SPEC_K", "24"))
    if on_accel:
        V, d_model, n_layers, n_heads = 64, 256, 4, 8
        P, lanes, ps = 32, 64, 16
        target_s = 8.0
    else:
        V, d_model, n_layers, n_heads = 8, 32, 1, 4
        P, lanes, ps = 8, 8, 8
        target_s = float(os.environ.get("BENCH_SPEC_TARGET_S", "2.0"))
    task = TokenRecallTask(vocab_size=V, prompt_len=P, response_len=R)
    model = TransformerPolicy(
        num_actions=V, vocab_size=V, d_model=d_model, num_heads=n_heads,
        num_layers=n_layers, max_len=2 * (P + R),
    )
    params = model.init(
        jax.random.PRNGKey(2),
        jax.numpy.zeros((1, 2), jax.numpy.int32),
    )
    base = dict(
        vocab_size=V, max_prompt_len=P, max_new_tokens=R,
        temperature=0.0, eos_token=-1, seed=0,
        lanes=lanes, page_size=ps, steps_per_macro=8,
        prompt_buckets=(P,),
    )

    def make(spec_k):
        return ContinuousEngine(
            model, params, ContinuousConfig(spec_k=spec_k, **base)
        )

    def round_once(engine, prompts, lengths):
        for i in range(lanes):
            engine.submit(prompts[i], int(lengths[i]))
        done = tokens = 0
        while done < lanes:
            cs = engine.step()
            done += len(cs)
            tokens += sum(len(c.response_tokens) for c in cs)
        return tokens

    engines = (make(0), make(k))
    rng = np.random.default_rng(0)
    # warm until the verify ladder stops compiling new buckets for TWO
    # consecutive round pairs: a first pass through an unseen
    # draft-length bucket traces (~1s on CPU), and one stray compile
    # inside a measured round would swamp the signal the interleaving
    # exists to protect.  Rare buckets (a pass whose longest draft is 0
    # or 1 tokens) can surface several rounds in, hence the hysteresis.
    stable = 0
    while stable < 2:
        traces = engines[1]._verify_traces
        warm = task.sample_prompts(lanes, rng)
        for engine in engines:
            round_once(engine, *warm)
        stable = stable + 1 if engines[1]._verify_traces == traces else 0
    times = [0.0, 0.0]
    toks = [0, 0]
    rounds = 0
    while sum(times) < target_s or rounds < 2:
        prompts, lengths = task.sample_prompts(lanes, rng)
        for i, engine in enumerate(engines):
            t0 = time.perf_counter()
            toks[i] += round_once(engine, prompts, lengths)
            times[i] += time.perf_counter() - t0
        rounds += 1
    off_tps = toks[0] / times[0]
    on_tps = toks[1] / times[1]
    eng = engines[1]
    return {
        "genrl_spec_accepted_tokens_per_sec": round(on_tps, 1),
        "spec_off_tokens_per_sec": round(off_tps, 1),
        "spec_speedup": round(on_tps / max(off_tps, 1e-9), 3),
        "spec_acceptance_rate": round(eng.spec_acceptance_rate, 4),
        "spec_k": k,
        "spec_response_budget": R,
        "spec_rollback_pages": eng.spec_rollback_pages_total,
    }


def _run_genrl_measurement() -> None:
    """``--mode genrl``: the token-level sequence-RL plane's headline
    numbers — prefill tokens/s/chip and decode tokens/s/chip through the
    KV-cached generation engine, plus token-PPO learn steps/s.

    Three timed phases over the same model/params, all shape-stable:

    1. **prefill** — the jitted prefill-only program (one full-prompt
       forward filling the KV cache) driven through a 2-deep
       MetricsPipeline, ONE batched metric read per call;
    2. **decode** — whole generation rounds through
       ``GenerationEngine.generate`` (prefill + the fused decode loop in
       one dispatch, one batched read per round — the steady-state guard
       armed after the first round); decode tokens/s counts response
       tokens only, against the full round wall-clock, so the number is
       an honest end-to-end generation rate, not a prefill-subtracted
       estimate;
    3. **learn** — token-PPO steps on a packed batch, pipelined like the
       other learn benches.
    """
    import jax
    import numpy as np

    from scalerl_tpu.agents.token_ppo import TokenPPOAgent
    from scalerl_tpu.config import GenRLArguments
    from scalerl_tpu.genrl.rollout import pack_sequences
    from scalerl_tpu.genrl.task import TokenRecallTask
    from scalerl_tpu.runtime.dispatch import MetricsPipeline
    from scalerl_tpu.trainer.sequence_rl import SequenceRLTrainer

    platform = jax.default_backend()
    device_kind = jax.devices()[0].device_kind
    on_accel = platform in ("tpu", "gpu")

    if on_accel:
        V, d_model, n_layers, n_heads = 1024, 256, 4, 8
        P, R, B = 128, 128, 64
        target_s = 10.0
    else:
        V, d_model, n_layers, n_heads = 32, 32, 1, 4
        P, R, B = 8, 4, 4
        target_s = 1.5

    args = GenRLArguments(
        vocab_size=V, prompt_len=P, max_new_tokens=R,
        d_model=d_model, n_layers=n_layers, n_heads=n_heads,
        genrl_batch=B, genrl_sample_batch=B,
        genrl_buffer_sequences=2 * B,
        telemetry_interval_s=0.0, logger_backend="none",
    )
    task = TokenRecallTask(vocab_size=V, prompt_len=P, response_len=R)
    trainer = SequenceRLTrainer(args, task=task)
    engine, agent = trainer.engine, trainer.agent
    rng = np.random.default_rng(0)
    prompts, lengths = task.sample_prompts(B, rng)

    # phase 1: prefill-only tokens/s (pipelined, one batched read/call)
    pre = engine.prefill_program(P, R)
    aligned = engine._align_prompts(prompts, lengths, P)
    dev_tokens, dev_lengths = jax.device_put((aligned, lengths))
    params, _gen = engine._snapshot_params()
    logits0, value0, _cache = pre(params, dev_tokens, dev_lengths)
    float(value0[0])  # compile + host-fetch sync (warmup barrier)
    pipe = MetricsPipeline(depth=2)
    t0 = time.perf_counter()
    pre_calls = 0
    while time.perf_counter() - t0 < target_s / 2 or pre_calls < 2:
        logits0, value0, _cache = pre(params, dev_tokens, dev_lengths)
        pre_calls += 1
        pipe.push(pre_calls, value0[0])
    pipe.drain()
    pre_elapsed = time.perf_counter() - t0
    prefill_tps = pre_calls * B * P / pre_elapsed

    # phase 2: whole generation rounds (the engine's own one-read round)
    engine.generate(prompts, lengths)  # warm: compile the fused program
    t0 = time.perf_counter()
    rounds = 0
    decode_tokens = 0
    while time.perf_counter() - t0 < target_s or rounds < 2:
        result = engine.generate(prompts, lengths)
        rounds += 1
        decode_tokens += result.decode_tokens
    gen_elapsed = time.perf_counter() - t0
    decode_tps = decode_tokens / gen_elapsed

    # phase 3: token-PPO learn steps/s (pipelined batched metric reads)
    rewards = task.score(
        prompts, lengths, result.response_tokens, result.response_len
    )
    fields, _prio = pack_sequences(result, rewards)
    batch = jax.device_put(fields)
    m = agent.learn_device(batch)
    float(jax.device_get(m["total_loss"]))  # warmup sync
    pipe = MetricsPipeline(depth=2)
    t0 = time.perf_counter()
    steps = 0
    while time.perf_counter() - t0 < target_s / 2 or steps < 2:
        m = agent.learn_device(batch)
        steps += 1
        pipe.push(steps, m)
    pipe.drain()
    learn_elapsed = time.perf_counter() - t0

    result_obj = {
        "metric": "genrl_decode_tokens_per_sec_per_chip",
        "mode": "genrl",
        "value": round(decode_tps, 1),
        "unit": f"decode tokens/sec/chip ({platform})",
        "prefill_tokens_per_sec": round(prefill_tps, 1),
        "decode_tokens_per_sec": round(decode_tps, 1),
        "learn_steps_per_sec": round(steps / learn_elapsed, 2),
        "rounds_per_sec": round(rounds / gen_elapsed, 2),
        "vocab": V,
        "d_model": d_model,
        "num_layers": n_layers,
        "prompt_bucket": P,
        "response_bucket": R,
        "batch": B,
        "iter_mode": engine.iter_mode,
        "device_kind": device_kind,
        "measured_s": round(pre_elapsed + gen_elapsed + learn_elapsed, 1),
    }
    # phase 4 (ISSUE 15): packed-vs-padded learn A/B on a mixed-length
    # workload — the token_ppo_learn_tokens_per_sec_per_chip field rides
    # the same line as the headline value (ONE json line per run)
    result_obj.update(_packed_learn_phase(on_accel))
    # phase 5 (ISSUE 16): speculative-decode A/B on the continuous engine
    # at one shape — spec off vs on in the same artifact
    result_obj.update(_spec_decode_phase(on_accel))
    _emit(result_obj)


def _mesh_axis(mesh_spec: str, axis: str) -> int:
    import re as _re

    m = _re.search(rf"{axis}=(\d+)", mesh_spec or "")
    return int(m.group(1)) if m else 1


def _run_measurement(
    mesh_spec: str | None = None, mode: str | None = None,
) -> None:
    """Run one measurement in this process and print its JSON line.

    ``mesh_spec`` (e.g. ``"dp=4"``): run the fused loop data-parallel over
    a device mesh (the Anakin dp scaling the 8-device dryrun validates) and
    report AGGREGATE env-frames/sec plus per-chip — the north-star-shaped
    number (BASELINE v5e-16 row).  Per-chip batch is held constant, so this
    measures weak scaling.

    ``mode="anakin"``: drive the measurement through
    ``DeviceActorLearnerLoop.run_anakin`` — ONE host dispatch (a single
    jitted scan/unroll over env step -> policy -> V-trace learn) covers a
    whole super-chunk of chunks, with the steady-state transfer guard
    armed and ONE batched metric read per super-chunk.  Reports the same
    fps/chip shape plus MFU from the super-chunk executable's own XLA cost
    analysis.
    """
    import jax
    import jax.numpy as jnp  # noqa: F401

    from scalerl_tpu.agents.impala import ImpalaAgent
    from scalerl_tpu.config import ImpalaArguments
    from scalerl_tpu.envs.jax_envs.base import JaxVecEnv
    from scalerl_tpu.envs.jax_envs.synthetic import SyntheticPixelEnv
    from scalerl_tpu.runtime.device_loop import DeviceActorLearnerLoop
    if mode == "sharded":
        # its own program entirely (dp×mp pjit train step on the
        # transformer policy); prints backend + one JSON line itself
        _run_sharded_measurement(mesh_spec)
        return
    if mode == "serving":
        # the centralized inference plane: requests/sec + latency SLO
        _run_serving_measurement()
        return
    if mode == "traffic":
        # the serving front door: open-loop goodput under SLO through the
        # multi-replica router
        _run_traffic_measurement()
        return
    if mode == "genrl":
        # the token-level sequence-RL plane: prefill/decode tokens/s +
        # token-PPO learn steps/s through the KV-cached engine
        _run_genrl_measurement()
        return
    if mode == "genrl-continuous":
        # the continuous-batching decode plane: paged-KV lane pool under
        # Poisson arrivals, like-for-like vs the fixed-cohort engine
        _run_genrl_continuous_measurement()
        return
    if mode == "disagg":
        # the disaggregated dataflow: generation hosts -> wire -> learner
        _run_disagg_measurement()
        return

    # the backend was pinned by main() (or by the test's environment)
    platform = jax.default_backend()
    device_kind = jax.devices()[0].device_kind

    # batch/unroll sized for one chip; the explicit CPU run shrinks to a
    # toy shape (it checks plumbing and the line's schema, not speed)
    on_accel = platform in ("tpu", "gpu")
    mesh = None
    n_dev = 1
    if mesh_spec:
        from scalerl_tpu.parallel import make_mesh

        mesh = make_mesh(mesh_spec)
        n_dev = mesh.devices.size
        if mesh.shape["dp"] != n_dev:
            raise ValueError(
                f"--mesh {mesh_spec!r}: the fused loop shards env lanes over "
                "dp only; use a pure-dp spec (dp=N)"
            )
    # CPU mesh runs exist to prove the code path, not to measure (virtual
    # devices on shared cores).  BENCH_B overrides the accelerator batch.
    if on_accel:
        B_chip = int(os.environ.get("BENCH_B", "512"))
    else:
        B_chip = 8 if mesh is None else 4
    B = B_chip * (n_dev if mesh is not None else 1)
    T = 20
    iters_per_call = 5 if on_accel else 1
    min_iters = 3 if (on_accel or mesh is None) else 1

    args = ImpalaArguments(
        use_lstm=False,
        hidden_size=512,
        rollout_length=T,
        batch_size=B,
        max_timesteps=0,
        # mixed precision on accelerators: conv/dense torso in bfloat16 feeds
        # the MXU at full rate; params, V-trace, and the optimizer stay f32
        # (standard IMPALA mixed-precision recipe, tested in
        # tests/test_impala.py::test_impala_bfloat16_compute_dtype)
        compute_dtype="bfloat16" if on_accel else "float32",
    )
    env = SyntheticPixelEnv()
    venv = JaxVecEnv(env, num_envs=B)
    agent = ImpalaAgent(args, obs_shape=env.observation_shape, num_actions=env.num_actions)
    learn = agent.make_learn_fn(grad_axis="dp" if mesh is not None else None)
    loop = DeviceActorLearnerLoop(
        model=agent.model,
        venv=venv,
        learn_fn=learn,
        unroll_length=T,
        iters_per_call=iters_per_call,
        mesh=mesh,
    )

    key = jax.random.PRNGKey(0)
    carry = loop.init_carry(key)
    state = agent.state
    frames_per_call = T * B * iters_per_call

    if mode == "anakin":
        _run_anakin_measurement(
            loop, state, carry, key, platform, device_kind,
            frames_per_call, on_accel,
        )
        return

    # AOT-compile the fused program ONCE and run the measurement through the
    # executable: the same compile yields XLA's FLOPs estimate (the MFU
    # numerator) and the jit dispatch path is never hit, so there is no
    # second compile of an identical program.
    flops_per_call = None
    run_fn = loop._train_many
    if mesh is None:
        run_fn = loop._train_many.lower(
            state, carry, jax.random.PRNGKey(1)
        ).compile()
        flops_per_call = _cost_analysis_flops(run_fn)
    # mesh mode: _train_many builds its shard_map program lazily on first
    # call; MFU comes from the single-chip bench, this mode measures scaling

    # warmup: one full call, synchronized by fetching an output scalar
    state, carry, m = run_fn(state, carry, jax.random.PRNGKey(1))
    float(m["total_loss"])

    from scalerl_tpu.runtime.dispatch import MetricsPipeline

    target_s = 20.0 if on_accel else 4.0
    frames = 0
    # pipelined driver: 2 chunks in flight, ONE batched metric read per
    # chunk (lagged a chunk behind the device, so the host never stalls
    # it); drain() is the final host-fetch sync before the clock stops
    pipe = MetricsPipeline(depth=2)
    # --profile-dir / BENCH_PROFILE_DIR: capture a device+host trace of the
    # measured window with one step_marker per fused chunk so the trace
    # viewer lines chunks up against the telemetry spans (a no-op when
    # unset; tracing perturbs the measurement, so profile runs are for
    # understanding the number, not reporting it)
    from scalerl_tpu.utils.profiling import maybe_trace, step_marker

    profile_dir = os.environ.get("BENCH_PROFILE_DIR") or None
    t0 = time.perf_counter()
    i = 0
    with maybe_trace(profile_dir):
        while True:
            key, sub = jax.random.split(key)
            with step_marker(i):
                state, carry, metrics = run_fn(state, carry, sub)
            i += 1
            frames += frames_per_call
            pipe.push(i, metrics)
            if time.perf_counter() - t0 >= target_s and i >= min_iters:
                break
        pipe.drain()
    elapsed = time.perf_counter() - t0

    fps = frames / elapsed
    if mesh is not None:
        # aggregate number, shaped like the BASELINE north star (>=100k
        # aggregate env-frames/sec on a v5e-16)
        result = {
            "metric": "impala_atari_env_frames_per_sec_aggregate",
            "value": round(fps, 1),
            "unit": f"frames/sec aggregate ({platform} x{n_dev})",
            "vs_baseline": round(fps / 100_000, 3),
            "per_chip": round(fps / n_dev, 1),
            "mesh": mesh_spec,
            "device_kind": device_kind,
            "batch": B,
            "unroll": T,
            "measured_s": round(elapsed, 1),
        }
        _emit(result)
        return
    result = {
        "metric": "impala_atari_env_frames_per_sec_per_chip",
        "value": round(fps, 1),
        "unit": f"frames/sec/chip ({platform})",
        "vs_baseline": round(fps / BASELINE_FPS_PER_CHIP, 3),
        "device_kind": device_kind,
        "batch": B,
        "unroll": T,
        "measured_s": round(elapsed, 1),
    }
    if flops_per_call is not None:
        achieved = flops_per_call * i / elapsed
        result["flops_per_frame"] = round(flops_per_call / frames_per_call)
        _stamp_mfu(result, achieved, platform, device_kind)
    _emit(result)


def _run_anakin_measurement(
    loop, state, carry, key, platform, device_kind, frames_per_call, on_accel
) -> None:
    """``--mode anakin``: the whole-run single-dispatch fused path.

    Each measured dispatch is one super-chunk — ``SC`` chunks of (env
    unroll -> policy -> V-trace learn) inside ONE jitted program, with the
    steady-state transfer guard armed and ONE batched metric read covering
    all of them.  MFU comes from the super-chunk executable's own cost
    analysis, exactly like the default mode.
    """
    import jax

    from scalerl_tpu.runtime import dispatch
    from scalerl_tpu.runtime.dispatch import get_metrics

    SC = int(os.environ.get("BENCH_SUPERCHUNK", "10" if on_accel else "4"))
    from functools import partial as _partial

    run_fn = jax.jit(
        _partial(loop._superchunk_impl, num_chunks=SC),
        donate_argnums=(0, 1),
    ).lower(state, carry, jax.random.PRNGKey(1)).compile()
    flops_per_super = _cost_analysis_flops(run_fn)

    # warmup (compile + constants); sync via host fetch like the main mode
    state, carry, m = run_fn(state, carry, jax.random.PRNGKey(1))
    float(get_metrics(m)["total_loss"][0])

    target_s = 20.0 if on_accel else 4.0
    min_iters = 1
    frames = 0
    t0 = time.perf_counter()
    i = 0
    while True:
        key, sub = jax.random.split(key)
        # steady state: one dispatch + one batched read per super-chunk,
        # with implicit host transfers hard-disallowed
        with dispatch.steady_state_guard():
            state, carry, m = run_fn(state, carry, sub)
            host = get_metrics(m)
        i += 1
        frames += frames_per_call * SC
        if time.perf_counter() - t0 >= target_s and i >= min_iters:
            break
    elapsed = time.perf_counter() - t0
    fps = frames / elapsed
    result = {
        "metric": "impala_atari_env_frames_per_sec_per_chip",
        "mode": "anakin",
        "value": round(fps, 1),
        "unit": f"frames/sec/chip ({platform}, anakin x{SC})",
        "vs_baseline": round(fps / BASELINE_FPS_PER_CHIP, 3),
        "device_kind": device_kind,
        "batch": loop.venv.num_envs,
        "unroll": loop.unroll_length,
        "superchunk": SC,
        "dispatches": i,
        "loss_last": round(float(host["total_loss"][-1]), 4),
        "measured_s": round(elapsed, 1),
    }
    if flops_per_super is not None:
        achieved = flops_per_super * i / elapsed
        result["flops_per_frame"] = round(flops_per_super / (frames_per_call * SC))
        _stamp_mfu(result, achieved, platform, device_kind)
    _emit(result)


_MODES = ("anakin", "sharded", "serving", "traffic", "genrl", "disagg")


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--mode", choices=_MODES, default=None)
    parser.add_argument(
        "--continuous", action="store_true",
        help="with --mode genrl: the continuous-batching decode variant",
    )
    parser.add_argument(
        "--learn", action="store_true",
        help="learner-step-only measurement (one device)",
    )
    parser.add_argument("--mesh", default=None, help='e.g. "dp=4"')
    parser.add_argument(
        "--cpu", action="store_true",
        help="explicit toy-shape CPU run; the line says platform: cpu",
    )
    parser.add_argument(
        "--profile-dir", default=None,
        help="capture a device+host trace of the measured window here",
    )
    # this process IS the measurement now; the flag that once selected the
    # in-process path is still accepted so older command lines keep working
    parser.add_argument("--run", action="store_true", help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    if args.learn and args.mesh is not None:
        parser.error(
            "--learn --mesh is not supported: the learn bench measures one "
            "device (run bench.py --mesh for the multi-chip shape)"
        )
    if args.continuous and args.mode != "genrl":
        parser.error("--continuous goes with --mode genrl")
    if args.profile_dir:
        os.environ["BENCH_PROFILE_DIR"] = args.profile_dir

    from scalerl_tpu.utils.platform import setup_platform

    # no chip -> JAX's own "Unable to initialize backend 'tpu'" ends the run
    print("backend:", setup_platform("cpu" if args.cpu else "tpu"), flush=True)
    if args.learn:
        _run_learn_measurement()
        return
    mode = args.mode
    if mode == "genrl" and args.continuous:
        # its own like-for-like history under mode "genrl-continuous",
        # same headline metric
        mode = "genrl-continuous"
    _run_measurement(args.mesh, mode=mode)


if __name__ == "__main__":
    main()
