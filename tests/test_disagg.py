"""Disaggregated sequence-RL dataflow (genrl/disagg.py, ISSUE 12).

Covers the wire snapshot format, exactly-once sequence/lease accounting
across the codec-v2 pipe wire, the drain protocol at sequence granularity,
the shared ParamSnapshotPlane idiom + unified staleness gauge, the
generation-tier autoscaler signals, and — under ``-m chaos`` — the
acceptance e2e: a seeded preemption wave killing half the generation hosts
MID-DECODE with exact unique sequence accounting, bit-exact payloads, and
autoscaler backfill.
"""

import threading
import time

import numpy as np
import pytest

from scalerl_tpu.genrl.disagg import (
    DisaggConfig,
    GenerationTierExecutor,
    LocalGenerationFleet,
    ScriptedEngineFactory,
    SequenceLearner,
    dequantize_wire_tree,
    disagg_signal_source,
    quantize_wire_tree,
    scripted_sequence_payload,
    wire_tree_bytes,
)
from scalerl_tpu.runtime import chaos, telemetry
from scalerl_tpu.runtime.param_server import ParameterServer, ParamSnapshotPlane


def _lease_source(n_leases, start=1):
    counter = {"i": start - 1}
    lock = threading.Lock()

    def source():
        with lock:
            if counter["i"] >= start - 1 + n_leases:
                return None
            counter["i"] += 1
            return {"seed": counter["i"], "length": 4}

    return source


def _weights():
    rng = np.random.default_rng(0)
    return {
        "dense": {
            "kernel": rng.standard_normal((16, 8)).astype(np.float32),
            "bias": rng.standard_normal(8).astype(np.float32),
        },
        "head": {"kernel": rng.standard_normal((8, 4)).astype(np.float32)},
    }


def _collect(learner, n, deadline_s=60.0):
    seqs = []
    deadline = time.monotonic() + deadline_s
    while len(seqs) < n and time.monotonic() < deadline:
        s = learner.get_sequence(timeout=0.2)
        if s is not None:
            seqs.append(s)
    return seqs


# ---------------------------------------------------------------------------
# wire snapshot format


def test_wire_quantize_int8_roundtrip_and_passthrough():
    w = _weights()
    wire = quantize_wire_tree(w, "int8")
    # 2-D leaves compress ~4x; 1-D f32-sensitive leaves pass through exact
    assert wire_tree_bytes(wire) < 0.3 * wire_tree_bytes(
        quantize_wire_tree(w, "none")
    )
    back = dequantize_wire_tree(wire)
    np.testing.assert_array_equal(back["dense"]["bias"], w["dense"]["bias"])
    for path in (("dense", "kernel"), ("head", "kernel")):
        a = back[path[0]][path[1]]
        b = w[path[0]][path[1]]
        assert a.dtype == b.dtype
        scale = np.abs(b).max() / 127.0
        np.testing.assert_allclose(a, b, atol=0.51 * scale)
    # "none" is lossless
    none_back = dequantize_wire_tree(quantize_wire_tree(w, "none"))
    np.testing.assert_array_equal(
        none_back["dense"]["kernel"], w["dense"]["kernel"]
    )
    with pytest.raises(ValueError):
        quantize_wire_tree(w, "fp4")


def test_parameter_server_shares_snapshot_plane_idiom():
    """Satellite: ParameterServer rides the ParamSnapshotPlane mixin —
    monotonic generation ids + device-side copy, the same idiom as the
    InferenceServer and the generation engine."""
    from scalerl_tpu.genrl.continuous import ContinuousEngine
    from scalerl_tpu.serving.server import InferenceServer

    ps = ParameterServer()
    assert isinstance(ps, ParamSnapshotPlane)
    assert issubclass(ContinuousEngine, ParamSnapshotPlane)
    assert issubclass(InferenceServer, ParamSnapshotPlane)
    w = _weights()
    assert ps.push(w) == 1
    assert ps.version == 1
    pulled, version = ps.pull(-1)
    assert version == 1
    np.testing.assert_array_equal(
        pulled["dense"]["kernel"], w["dense"]["kernel"]
    )
    assert ps.pull(1) == (None, 1)
    # the plane's unified staleness definition rides along
    ps.push(w)
    ps.push(w)
    assert ps.staleness_steps(1) == 2.0
    assert ps.staleness_steps(3) == 0.0


def test_unified_staleness_gauge():
    """Satellite: one gauge name/definition — learner steps behind the
    newest generation — reported through telemetry.observe_staleness."""
    assert telemetry.observe_staleness(7.0, plane="disagg") == 7.0
    reg = telemetry.get_registry()
    assert reg.gauge("staleness").value == 7.0
    assert reg.gauge("staleness_plane.disagg").value == 7.0
    telemetry.observe_staleness(-3.0, plane="genrl")  # clamped at 0
    assert reg.gauge("staleness").value == 0.0


# ---------------------------------------------------------------------------
# the dataflow over the pipe wire (thread hosts, scripted engines)


def test_disagg_exact_accounting_and_bit_exact_payloads():
    """Thread fleet of 2 scripted hosts: every lease produces exactly one
    accepted sequence, payloads are byte-identical to the deterministic
    expectation, quantized snapshots adopt, and hosts exit cleanly when
    the prompt source runs dry."""
    n = 40
    cfg = DisaggConfig(
        num_hosts=2, lanes_per_host=3, upload_batch=2,
        heartbeat_interval_s=0.5,
    )
    learner = SequenceLearner(cfg, _lease_source(n))
    learner.start()
    gen = learner.publish(_weights(), learner_step=0)
    assert gen == 1 and learner.snapshot_wire_bytes > 0
    fleet = LocalGenerationFleet(
        learner, cfg,
        ScriptedEngineFactory(lanes=3, response_len=6, tokens_per_step=2),
        use_threads=True,
    )
    fleet.start()
    try:
        seqs = _collect(learner, n)
        assert len(seqs) == n
        assert learner.duplicate_sequences == 0
        assert learner.duplicate_leases == 0
        # exact unique accounting over the lease ids
        assert len({s["lease_id"] for s in seqs}) == n
        # bit-exact payloads: every byte matches the pure function of the
        # lease seed (host-independent by construction)
        for s in seqs:
            expect = scripted_sequence_payload(s["seed"], 6, 32, 1)
            for key in (
                "prompt", "response_tokens", "behavior_logp", "values",
            ):
                np.testing.assert_array_equal(s[key], expect[key])
            assert s["generation"] == 1
        # hosts adopted the published generation via the wire snapshot
        assert all(s["host_id"] in (0, 1) for s in seqs)
    finally:
        learner.stop()
        fleet.join()


def test_duplicate_uploads_and_raced_lease_completions_count_once():
    """The learner-side dedup matrix: a resent seq_batch (same (host,
    epoch, seq_id)) is absorbed, and a lease completing twice (requeue
    raced the original execution) counts once."""
    cfg = DisaggConfig(num_hosts=1, heartbeat_interval_s=0.0)
    learner = SequenceLearner(cfg, _lease_source(4))
    p1 = dict(scripted_sequence_payload(1, 4, 16, 0))
    p1.update(host_id=7, host_epoch=11, seq_id=0, _task_id=100)
    p2 = dict(scripted_sequence_payload(2, 4, 16, 0))
    p2.update(host_id=7, host_epoch=11, seq_id=1, _task_id=101)
    learner._ingest([p1, p2])
    assert learner.total_sequences == 2
    # a retained-upload redelivery: same dedup keys, dropped
    r1 = dict(scripted_sequence_payload(1, 4, 16, 0))
    r1.update(host_id=7, host_epoch=11, seq_id=0, _task_id=100)
    learner._ingest([r1])
    assert learner.total_sequences == 2
    assert learner.duplicate_sequences == 1
    # a racing duplicate COMPLETION from another host (fresh dedup key,
    # same lease): lease-level exactly-once drops it
    race = dict(scripted_sequence_payload(1, 4, 16, 0))
    race.update(host_id=8, host_epoch=12, seq_id=0, _task_id=100)
    learner._ingest([race])
    assert learner.total_sequences == 2
    assert learner.duplicate_leases == 1


def test_lease_group_fanout_exact_sample_accounting():
    """ISSUE 14: a lease issued with samples=n fans out into n sequences
    on the generation host.  Exactly n samples per lease are accepted —
    byte-identical to the per-(seed, sample) deterministic expectation —
    and redelivered or reissue-raced samples dedup per (lease, sample)."""
    n_leases, spp = 12, 3

    def _group_source():
        base = _lease_source(n_leases)

        def source():
            lease = base()
            if lease is not None:
                lease["samples"] = spp
            return lease

        return source

    cfg = DisaggConfig(
        num_hosts=2, lanes_per_host=6, upload_batch=2,
        heartbeat_interval_s=0.5,
    )
    learner = SequenceLearner(cfg, _group_source())
    learner.start()
    learner.publish(_weights(), learner_step=0)
    fleet = LocalGenerationFleet(
        learner, cfg,
        ScriptedEngineFactory(lanes=6, response_len=6, tokens_per_step=2),
        use_threads=True,
    )
    fleet.start()
    try:
        seqs = _collect(learner, n_leases * spp)
        assert len(seqs) == n_leases * spp
        assert learner.duplicate_sequences == 0
        assert learner.duplicate_leases == 0
        # exactly spp distinct samples per lease, every byte scripted
        groups = {}
        for s in seqs:
            groups.setdefault(s["lease_id"], set()).add(s["sample_idx"])
            expect = scripted_sequence_payload(
                s["seed"], 6, 32, 1, sample=s["sample_idx"]
            )
            for key in (
                "prompt", "response_tokens", "behavior_logp", "values",
            ):
                np.testing.assert_array_equal(s[key], expect[key])
        assert len(groups) == n_leases
        assert all(v == set(range(spp)) for v in groups.values())
    finally:
        learner.stop()
        fleet.join()
    # unit: a straggler duplicate of an accepted (lease, sample) drops,
    # and the lease closes only once all samples landed
    learner2 = SequenceLearner(
        DisaggConfig(num_hosts=1, heartbeat_interval_s=0.0),
        _lease_source(1),
    )
    mk = lambda k, sid: dict(  # noqa: E731
        scripted_sequence_payload(1, 4, 16, 0, sample=k),
        host_id=1, host_epoch=5, seq_id=sid, _task_id=50,
        _sample_idx=k, _samples_total=2,
    )
    learner2._ingest([mk(0, 0)])
    assert 50 not in learner2._completed_leases  # half-complete group
    race = mk(0, 7)
    race["host_id"] = 2  # reissue race: fresh upload key, same sample
    learner2._ingest([race])
    assert learner2.duplicate_leases == 1
    learner2._ingest([mk(1, 1)])
    assert 50 in learner2._completed_leases
    assert learner2.total_sequences == 2
    learner2.stop()


def test_lease_requeue_on_host_disconnect():
    """A dead host link requeues its outstanding leases; the next lease
    request serves the requeues first."""
    import multiprocessing as mp

    from scalerl_tpu.fleet.transport import PipeConnection

    cfg = DisaggConfig(num_hosts=1, heartbeat_interval_s=0.0)
    learner = SequenceLearner(cfg, _lease_source(2))
    a, _b = mp.Pipe(duplex=True)
    conn = PipeConnection(a)
    learner.hub.add_connection(conn)
    learner._handle(conn, {"kind": "lease", "n": 2, "have_gen": -1})
    assert len(learner._outstanding) == 2
    learner.hub.disconnect(conn)
    assert learner.requeued_leases == 2
    assert len(learner._outstanding) == 0
    # the requeued leases are served before the (exhausted) source
    lease = learner._next_lease()
    assert lease is not None and "_task_id" in lease
    learner.stop()


def test_drain_protocol_zero_sequence_loss():
    """drain_hosts(1): the drained host stops admitting, finishes or
    returns its live lanes, flushes + awaits acks, and announces
    drain_done — every lease still completes exactly once across the
    remaining fleet."""
    n = 30
    cfg = DisaggConfig(
        num_hosts=2, lanes_per_host=2, upload_batch=1,
        heartbeat_interval_s=0.5,
    )
    learner = SequenceLearner(cfg, _lease_source(n))
    learner.start()
    learner.publish(_weights(), learner_step=0)
    fleet = LocalGenerationFleet(
        learner, cfg,
        ScriptedEngineFactory(
            lanes=2, response_len=8, tokens_per_step=1, step_sleep_s=0.01
        ),
        use_threads=True,
    )
    fleet.start()
    try:
        warm = _collect(learner, 4)
        assert len(warm) == 4
        assert learner.drain_hosts(1) == 1
        seqs = warm + _collect(learner, n - 4)
        assert len(seqs) == n
        assert len({s["lease_id"] for s in seqs}) == n
        deadline = time.monotonic() + 20.0
        while learner.hosts_drained < 1 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert learner.hosts_drained == 1
        assert learner.live_host_count() == 1
    finally:
        learner.stop()
        fleet.join()


def test_disagg_signal_source_and_staleness_rule():
    """The generation-tier signal set feeds the autoscaler: snapshot
    staleness above max_staleness is scale-up pressure."""
    from scalerl_tpu.runtime.autoscaler import (
        Autoscaler,
        AutoscalerConfig,
        FleetSignals,
    )

    cfg = DisaggConfig(num_hosts=1, heartbeat_interval_s=0.0)
    learner = SequenceLearner(cfg, _lease_source(1))
    learner.publish(_weights(), learner_step=10)
    learner.publish(_weights(), learner_step=20)
    lag = learner.observe_consumed(1)
    assert lag == 10.0
    signals = disagg_signal_source(learner)()
    assert signals.snapshot_staleness == 10.0
    assert signals.live_workers == 0
    learner.stop()

    scaler = Autoscaler(
        AutoscalerConfig(
            min_workers=1, max_workers=4, up_hysteresis=1,
            low_occupancy=-1.0, max_staleness=5.0, cooldown_s=0.0,
        )
    )
    d = scaler.evaluate(
        FleetSignals(
            snapshot_staleness=10.0, queue_occupancy=0.5, live_workers=2
        ),
        now=0.0,
    )
    assert d.action == "scale_up"
    # below the threshold the rule is silent
    scaler2 = Autoscaler(
        AutoscalerConfig(
            min_workers=1, max_workers=4, up_hysteresis=1,
            low_occupancy=-1.0, max_staleness=5.0,
        )
    )
    d2 = scaler2.evaluate(
        FleetSignals(
            snapshot_staleness=2.0, queue_occupancy=0.5, live_workers=2
        ),
        now=0.0,
    )
    assert d2.action == "hold"


# ---------------------------------------------------------------------------
# real engines over the wire (the jax path, thread hosts): the trainer's
# default factory, a ContinuousEngine behind a ContinuousEngineShell


def _shell_args(**kw):
    from scalerl_tpu.config import GenRLArguments

    base = dict(
        vocab_size=12, prompt_len=4, max_new_tokens=4, d_model=32,
        n_layers=1, n_heads=2, genrl_batch=4, genrl_sample_batch=4,
        genrl_buffer_sequences=8, disagg_hosts=1, genrl_page_size=2,
        genrl_macro_steps=2, eos_token=1, seed=5,
        telemetry_interval_s=0.0, logger_backend="none",
        disagg_round_timeout_s=120.0,
    )
    base.update(kw)
    return GenRLArguments(**base)


def _host_params(args, scale=1.0):
    """A wire snapshot (host numpy) of the token model's seeded weights."""
    import jax
    import jax.numpy as jnp

    from scalerl_tpu.runtime.param_server import _to_host
    from scalerl_tpu.trainer.sequence_rl import build_genrl_model

    params = build_genrl_model(args).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32)
    )
    return _to_host(jax.tree_util.tree_map(lambda x: x * scale, params))


def _shell(args, lanes, generation=0):
    """What a generation host builds from its first snapshot."""
    from scalerl_tpu.trainer.sequence_rl import _EngineShellFactory

    return _EngineShellFactory(args, lanes)(_host_params(args), generation)


def _prompt_leases(n, samples=1, seed=0):
    rng = np.random.default_rng(seed)
    leases = []
    for i in range(n):
        m = int(rng.integers(1, 5))
        lease = {
            "seed": i + 1, "_task_id": 100 + i, "length": m,
            "prompt": rng.integers(2, 12, size=m).astype(np.int32),
        }
        if samples > 1:
            lease["samples"] = samples
        leases.append(lease)
    return leases


def _run_shell(shell, leases, max_steps=400):
    """The host loop's engine half: submit while there is capacity, step
    while anything is live."""
    queued, out = list(leases), []
    for _ in range(max_steps):
        while queued and shell.capacity() > 0:
            shell.submit(queued.pop(0))
        if shell.live() == 0 and not queued:
            return out
        out.extend(shell.step())
    raise AssertionError(f"{shell.live()} leases still live")


def test_continuous_shell_out_of_order_completions_close_their_lease():
    """Lanes finish at ragged lengths (EOS at temperature 1), so payloads
    come back in another order than their leases went in; each carries the
    task id, prompt and length of the lease that admitted it."""
    shell = _shell(_shell_args(), lanes=2)
    leases = _prompt_leases(8)
    payloads = _run_shell(shell, leases)
    order = [p["_task_id"] for p in payloads]
    assert sorted(order) == [lease["_task_id"] for lease in leases]
    assert order != sorted(order)  # out of order, and still routed right
    by_id = {lease["_task_id"]: lease for lease in leases}
    for p in payloads:
        lease = by_id[p["_task_id"]]
        np.testing.assert_array_equal(p["prompt"], lease["prompt"])
        assert p["prompt_len"] == lease["length"]
        assert 1 <= len(p["response_tokens"]) <= 4
        assert len(p["behavior_logp"]) == len(p["response_tokens"])
        assert "_sample_idx" not in p
    assert shell.live() == 0 and shell.capacity() == 2


def test_continuous_shell_lease_larger_than_free_lanes_queues_and_completes():
    """A fanned-out lease that finds fewer free lanes than samples waits in
    the engine's admission queue (capacity counts its lanes as taken) and
    is admitted whole when the lanes free; every sample arrives once."""
    shell = _shell(_shell_args(), lanes=4)
    first, second = _prompt_leases(2, samples=3)
    shell.submit(first)
    assert shell.capacity() == 1
    shell.submit(second)  # 1 free lane, 3 wanted: queued, not dropped
    assert shell.capacity() == -2 and shell.live() == 2
    shell.step()
    assert shell.engine.live_lanes == 3 and shell.engine.pending == 3
    payloads = _run_shell(shell, [])
    samples = {}
    for p in payloads:
        assert p["_samples_total"] == 3
        samples.setdefault(p["_task_id"], []).append(p["_sample_idx"])
    assert {k: sorted(v) for k, v in samples.items()} == {
        first["_task_id"]: [0, 1, 2], second["_task_id"]: [0, 1, 2],
    }
    assert shell.live() == 0 and shell.capacity() == 4


def test_continuous_shell_push_params_mid_lease_tags_later_sequences():
    """The shell speaks the learner's generation ids: a sequence admitted
    before a push carries the old wire generation even when it completes
    after it, and every sequence admitted later carries the new one."""
    args = _shell_args(eos_token=-1)  # every response runs its 4 tokens
    shell = _shell(args, lanes=2, generation=7)
    before, after = _prompt_leases(2)
    shell.submit(before)
    assert shell.step() == []  # admitted under generation 7, mid-decode
    shell.push_params(_host_params(args, scale=0.5), 9)
    assert shell.generation == 9
    shell.submit(after)
    payloads = {p["_task_id"]: p for p in _run_shell(shell, [])}
    assert payloads[before["_task_id"]]["generation"] == 7
    assert payloads[after["_task_id"]]["generation"] == 9
    snapshot = _host_params(args)
    for _ in range(70):  # the generation map stays bounded
        shell.push_params(snapshot, 10)
    assert len(shell._gen_map) <= 64


def test_continuous_shell_host_killed_mid_decode_exactly_once():
    """Two thread hosts built by the trainer's default factory; one loses
    its link for good while its lanes are decoding.  Its leases requeue to
    the survivor: every lease is accepted exactly once, none lost, none
    surfaced twice."""
    import multiprocessing as mp

    from scalerl_tpu.fleet.transport import PipeConnection
    from scalerl_tpu.genrl.disagg import generation_host_main
    from scalerl_tpu.trainer.sequence_rl import _EngineShellFactory

    n = 14
    args = _shell_args()
    leases = _prompt_leases(n)
    prompts = sorted(tuple(lease["prompt"].tolist()) for lease in leases)
    lock = threading.Lock()

    def source():
        with lock:
            if not leases:
                return None
            lease = leases.pop(0)
            lease.pop("_task_id")  # the learner assigns its own
            return lease

    # the default heartbeat: a host that is compiling its engine answers
    # no ping for seconds and must not be taken for dead
    cfg = DisaggConfig(num_hosts=2, lanes_per_host=2, upload_batch=1)
    learner = SequenceLearner(cfg, source)
    learner.start()
    learner.publish(_host_params(args), learner_step=0)
    links, threads = [], []
    for host_id in range(2):
        parent, child = mp.Pipe(duplex=True)
        links.append(PipeConnection(parent))
        learner.add_host_connection(links[-1])
        threads.append(threading.Thread(
            target=generation_host_main,  # no reconnect seam: a lost link
            args=(PipeConnection(child), cfg,  # ends the host
                  _EngineShellFactory(args, 2), host_id),
            daemon=True,
        ))
        threads[-1].start()
    try:
        warm = _collect(learner, 2, deadline_s=120.0)
        assert len(warm) == 2, "the hosts never warmed up"
        # the victim is whichever host holds leases right now (between an
        # upload and its next lease request a host holds none)
        deadline = time.monotonic() + 30.0
        victim = None
        while victim is None and time.monotonic() < deadline:
            held = [conn for conn, _lease in list(learner._outstanding.values())]
            victim = next((i for i in (0, 1) if links[i] in held), None)
        assert victim is not None, "no host ever held a lease"
        learner.hub.disconnect(links[victim])
        assert learner.requeued_leases >= 1  # it held leases, mid-decode
        seqs = warm + _collect(learner, n - 2, deadline_s=120.0)
        assert len(seqs) == n
        assert len({s["lease_id"] for s in seqs}) == n
        assert sorted(tuple(s["prompt"].tolist()) for s in seqs) == prompts
        assert learner.duplicate_sequences == 0
        threads[victim].join(timeout=20.0)
        assert not threads[victim].is_alive()
    finally:
        learner.stop()
        for t in threads:
            t.join(timeout=10.0)


def test_disagg_trainer_default_factory_one_round():
    """The tier-1 twin of the e2e below at its sizes, one host, one round:
    DisaggSequenceRLTrainer with no engine_factory builds a
    ContinuousEngine behind a ContinuousEngineShell in the host, and the
    round is exactly-once."""
    from scalerl_tpu.genrl.disagg import ContinuousEngineShell
    from scalerl_tpu.trainer.sequence_rl import (
        DisaggSequenceRLTrainer,
        _EngineShellFactory,
    )

    args = _shell_args(eos_token=-1, genrl_page_size=8, genrl_macro_steps=4)
    trainer = DisaggSequenceRLTrainer(args)
    factory = trainer.fleet.engine_factory
    assert isinstance(factory, _EngineShellFactory)
    summary = trainer.train(1)
    assert summary["rounds"] == 1.0
    assert summary["wire_sequences"] >= args.genrl_batch
    assert summary["staleness"] >= 0.0
    assert trainer.learner.duplicate_sequences == 0
    assert trainer.learner.duplicate_leases == 0
    assert np.isfinite(summary["total_loss"])
    shell = factory(_host_params(args), 3)
    assert isinstance(shell, ContinuousEngineShell)
    assert shell.capacity() == args.genrl_batch // args.disagg_hosts


@pytest.mark.slow
def test_disagg_trainer_e2e_real_engines():
    """DisaggSequenceRLTrainer: real ContinuousEngines behind the shells
    stream wire sequences into the real replay + token-PPO learner; the
    unified staleness gauge reports learner steps."""
    from scalerl_tpu.config import GenRLArguments
    from scalerl_tpu.trainer.sequence_rl import DisaggSequenceRLTrainer

    args = GenRLArguments(
        vocab_size=12, prompt_len=4, max_new_tokens=4, d_model=32,
        n_layers=1, n_heads=2, genrl_batch=4, genrl_sample_batch=4,
        genrl_buffer_sequences=8, disagg_hosts=2,
        telemetry_interval_s=0.0, logger_backend="none",
        disagg_round_timeout_s=120.0,
    )
    trainer = DisaggSequenceRLTrainer(args)
    summary = trainer.train(3)
    assert summary["rounds"] == 3.0
    assert summary["wire_sequences"] >= 3 * args.genrl_batch
    assert summary["staleness"] >= 0.0
    assert trainer.learner.duplicate_sequences == 0
    assert np.isfinite(summary["total_loss"])
    assert telemetry.get_registry().gauge("staleness").value >= 0.0


# ---------------------------------------------------------------------------
# the acceptance e2e: preemption wave mid-decode


@pytest.mark.chaos
@pytest.mark.slow
def test_chaos_mass_kill_wave_mid_decode_exact_sequences(monkeypatch):
    """ISSUE 12 acceptance: a seeded ``mass_kill`` wave kills HALF the
    generation hosts mid-decode.  Unique sequence count is exact (no lost,
    no duplicate), payloads are bit-exact, in-flight leases requeue, and
    the autoscaler records >= 1 backfill."""
    monkeypatch.setenv(chaos.ENV_VAR, "777:mass_kill=1.0@1")
    chaos.clear()
    from scalerl_tpu.runtime.autoscaler import Autoscaler, AutoscalerConfig

    n = 80
    cfg = DisaggConfig(
        num_hosts=4, lanes_per_host=2, upload_batch=1,
        heartbeat_interval_s=0.5,
    )
    learner = SequenceLearner(cfg, _lease_source(n))
    learner.start()
    learner.publish(_weights(), learner_step=0)
    # slow scripted decode: one token per step with a sleep, so the wave
    # genuinely lands while lanes are mid-decode
    fleet = LocalGenerationFleet(
        learner, cfg,
        ScriptedEngineFactory(
            lanes=2, response_len=8, tokens_per_step=1, step_sleep_s=0.02
        ),
        mp_context="spawn",
        auto_chaos=False,  # the test lands the wave itself, mid-decode
    )
    fleet.start()
    scaler = Autoscaler(
        AutoscalerConfig(
            min_workers=4, max_workers=8, interval_s=0.25, cooldown_s=1.0,
            up_hysteresis=1, low_occupancy=-1.0,  # floor backfill only
        ),
        executor=GenerationTierExecutor(learner, fleet),
        signal_source=disagg_signal_source(learner),
    ).start()
    try:
        warm = _collect(learner, 8, deadline_s=120.0)
        assert len(warm) == 8, "generation fleet never warmed up"
        # the seeded wave (rate 1.0@1 fires on this draw): half the hosts
        killed = fleet.chaos_poll()
        assert len(killed) == 2, f"wave killed {killed}, wanted half of 4"
        seqs = warm + _collect(learner, n - 8, deadline_s=240.0)
        assert len(seqs) == n, (
            f"only {len(seqs)}/{n} sequences after the wave "
            f"(requeued={learner.requeued_leases}, "
            f"scale_ups={scaler.scale_ups})"
        )
        # exact unique accounting: no lost, no duplicate
        assert len({s["lease_id"] for s in seqs}) == n
        assert {s["seed"] for s in seqs} == set(range(1, n + 1))
        # bit-exact payloads, wherever (and however often) they decoded
        for s in seqs:
            expect = scripted_sequence_payload(s["seed"], 8, 32, 1)
            for key in (
                "prompt", "response_tokens", "behavior_logp", "values",
            ):
                np.testing.assert_array_equal(s[key], expect[key])
        # the learner never surfaced a torn or duplicated chunk
        assert learner.duplicate_sequences + learner.duplicate_leases >= 0
        dup_surfaced = len(seqs) - len({s["lease_id"] for s in seqs})
        assert dup_surfaced == 0
        # the autoscaler backfilled the wave (floor rule, FlightRecorder)
        assert scaler.scale_ups >= 1
        ups = [
            e
            for e in telemetry.get_recorder().events("autoscale_decision")
            if e.get("action") == "scale_up"
        ]
        assert ups, "no scale_up decision on the FlightRecorder"
        assert telemetry.get_recorder().events("mass_kill")
    finally:
        scaler.stop()
        learner.stop()
        fleet.join()
        chaos.clear()
