"""Distributed tracing: sampling, propagation, skew, and the disagg
lifecycle end to end (ISSUE 13).

jax-free on purpose — the tracer, the wire piggyback, the span files, and
``tools/trace_report.py`` all live on the host side, so these tests run in
milliseconds and double as the artifact-schema gate for the trace_report
verdict line a trace-soak caller parses.
"""

import json
import os
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

REPO_ROOT = Path(__file__).resolve().parent.parent
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

from scalerl_tpu.fleet.framing import pack_message, unpack_message
from scalerl_tpu.runtime import telemetry, tracing
from scalerl_tpu.runtime.supervisor import make_ping, make_pong


@pytest.fixture(autouse=True)
def _fresh_planes():
    telemetry.reset()
    tracing.reset()
    yield
    telemetry.reset()
    tracing.reset()


def _armed(monkeypatch, tmp_path=None, rate="1.0"):
    monkeypatch.setenv(tracing.ENV_SAMPLE, rate)
    if tmp_path is not None:
        monkeypatch.setenv(tracing.ENV_DIR, str(tmp_path))
    else:
        monkeypatch.delenv(tracing.ENV_DIR, raising=False)
    tracing.reset()


# ---------------------------------------------------------------------------
# sampling + propagation


def test_sampling_off_is_a_noop(monkeypatch):
    monkeypatch.delenv(tracing.ENV_SAMPLE, raising=False)
    tracing.reset()
    span = tracing.start_span("root")
    assert not span.sampled
    span.end()  # no-op, never raises
    msg = tracing.inject({"kind": "lease"}, span)
    assert tracing.TRACE_KEY not in msg
    assert tracing.get_tracer().finished() == []
    assert not tracing.sampling_enabled()


def test_head_sampling_records_root_and_counters(monkeypatch):
    _armed(monkeypatch)
    span = tracing.start_span("root", kind="test", foo=1)
    assert span.sampled
    span.end(bar=2)
    recs = tracing.get_tracer().finished()
    assert len(recs) == 1
    rec = recs[0]
    assert rec["name"] == "root" and rec["parent"] is None
    assert rec["attrs"] == {"foo": 1, "bar": 2}
    assert rec["host"] == telemetry.host_id()
    reg = telemetry.get_registry()
    assert reg.counter("trace.spans_started").value == 1
    assert reg.counter("trace.spans_finished").value == 1


def test_child_of_remote_context_records_even_when_local_rate_is_zero(
    monkeypatch,
):
    """Head-based sampling: the ROOT decides; a span carrying a remote
    parent context always records — that is what stitches a trace across
    a process whose own rate is 0."""
    monkeypatch.delenv(tracing.ENV_SAMPLE, raising=False)
    tracing.reset()
    wire = {"tid": "a" * 16, "sid": "b" * 16}
    span = tracing.start_span("child", parent=wire)
    assert span.sampled
    span.end()
    (rec,) = tracing.get_tracer().finished()
    assert rec["trace"] == "a" * 16
    assert rec["parent"] == "b" * 16


def test_inject_extract_roundtrip_through_codec_v2(monkeypatch):
    """The context piggybacks on codec-v2 frames exactly like _telem: an
    ordinary dict key, zero new message kinds."""
    _armed(monkeypatch)
    root = tracing.start_span("sequence")
    msg = tracing.inject(
        {"kind": "lease", "prompt": np.arange(4, dtype=np.int32)}, root
    )
    decoded = unpack_message(pack_message(msg))
    ctx = tracing.extract(decoded)
    assert ctx is not None
    assert ctx.trace_id == root.trace_id
    assert ctx.span_id == root.span_id
    # extract never mutates: the key still rides the message afterwards
    assert tracing.TRACE_KEY in decoded
    assert tracing.extract({"kind": "lease"}) is None
    assert tracing.extract({"trace": "garbage"}) is None


def test_finished_ring_is_bounded_and_counts_drops(monkeypatch):
    _armed(monkeypatch)
    tracer = tracing.Tracer(sample_rate=1.0, capacity=8, out_dir="")
    for i in range(20):
        tracer.start_span(f"s{i}").end()
    assert len(tracer.finished()) == 8
    assert tracer.dropped == 12
    # oldest dropped, newest retained
    assert tracer.finished()[-1]["name"] == "s19"


def test_record_span_retroactive_monotonic_stamps(monkeypatch):
    _armed(monkeypatch)
    t0 = time.monotonic() - 1.5
    tracing.record_span("seq.decode", None, t0, t0 + 1.0, kind="disagg")
    (rec,) = tracing.get_tracer().finished()
    assert abs(rec["dur"] - 1.0) < 1e-9
    # wall time derives from the process anchor, not a fresh time.time()
    assert abs(rec["t0"] - tracing.wall_of(t0)) < 1e-9


def test_span_context_manager_activates_for_flight_events(monkeypatch):
    """FlightRecorder linkage: events recorded under an active span carry
    its trace id — fault forensics link both ways."""
    _armed(monkeypatch)
    telemetry.record_event("before")
    with tracing.start_span("episode") as span:
        telemetry.record_event("chaos_injection", fault="bitflip")
    telemetry.record_event("after")
    events = telemetry.get_recorder().events()
    by_kind = {e["kind"]: e for e in events}
    assert by_kind["chaos_injection"]["trace"] == span.trace_id
    assert "trace" not in by_kind["before"]
    assert "trace" not in by_kind["after"]
    # activate() gives the same linkage to a remote context (worker_loop)
    ctx = {"tid": "c" * 16, "sid": "d" * 16}
    with tracing.get_tracer().activate(ctx):
        telemetry.record_event("worker_error")
    assert telemetry.get_recorder().events("worker_error")[0]["trace"] == "c" * 16


# ---------------------------------------------------------------------------
# clock skew off heartbeat pongs


def test_skew_estimator_recovers_synthetic_offset():
    est = tracing.ClockSkewEstimator()
    # peer clock runs 5 s ahead; symmetric 40 ms RTT
    est.observe("h2", 100.0, 105.02, 100.04)
    assert abs(est.offset("h2") - 5.0) < 1e-9
    # a slower, asymmetric sample must NOT displace the min-RTT one
    est.observe("h2", 200.0, 205.9, 201.0)
    assert abs(est.offset("h2") - 5.0) < 1e-9
    # a tighter sample does
    est.observe("h2", 300.0, 305.001, 300.002)
    assert abs(est.offset("h2") - 5.0) < 1e-3
    assert est.samples("h2") == 3
    assert est.offset("unknown") == 0.0


def test_pong_carries_rt_and_host_and_feeds_the_estimator():
    pong = make_pong(make_ping())
    assert pong["kind"] == "pong"
    assert isinstance(pong["rt"], float)
    assert pong["host"] == telemetry.host_id()
    tracing.observe_pong(pong)
    assert telemetry.host_id() in tracing.get_skew().offsets()
    # garbage pongs are ignored, never raise
    tracing.observe_pong({"kind": "pong"})
    tracing.observe_pong(None)


# ---------------------------------------------------------------------------
# span files + trace_report


def test_span_file_sink_meta_and_skew_lines(monkeypatch, tmp_path):
    _armed(monkeypatch, tmp_path)
    root = tracing.start_span("sequence")
    tracing.record_span("seq.decode", root, 1.0, 2.0)
    root.end()
    tracing.get_skew().observe("other-host", 10.0, 10.5, 10.1)
    tracing.export_skew()
    files = [f for f in os.listdir(tmp_path) if f.startswith("spans_")]
    assert len(files) == 1
    lines = [
        json.loads(line) for line in (tmp_path / files[0]).read_text().splitlines()
    ]
    assert lines[0]["kind"] == "meta"
    assert lines[0]["host"] == telemetry.host_id()
    spans = [l for l in lines if "span" in l]
    assert {s["name"] for s in spans} == {"sequence", "seq.decode"}
    (skew,) = [l for l in lines if l.get("kind") == "skew"]
    assert "other-host" in skew["offsets"]


def test_trace_report_applies_skew_and_finds_orphans(tmp_path):
    from tools.trace_report import build_report

    # two hosts; host B's clock is +2 s ahead; learner measured it
    a = tmp_path / "spans_learner_1.jsonl"
    b = tmp_path / "spans_genhost_2.jsonl"
    rows_a = [
        {"kind": "meta", "host": "learner", "pid": 1, "anchor_wall": 0.0},
        {"kind": "skew", "host": "learner", "offsets": {"genhost": 2.0}},
        {"trace": "t1", "span": "r1", "parent": None, "name": "sequence",
         "kind": "disagg", "host": "learner", "t0": 100.0, "dur": 1.0,
         "attrs": {}},
        {"trace": "t1", "span": "l1", "parent": "r1",
         "name": "seq.learn_step", "kind": "disagg", "host": "learner",
         "t0": 101.0, "dur": 0.1, "attrs": {}},
    ]
    rows_b = [
        {"kind": "meta", "host": "genhost", "pid": 2, "anchor_wall": 2.0},
        {"trace": "t1", "span": "d1", "parent": "r1", "name": "seq.decode",
         "kind": "disagg", "host": "genhost", "t0": 102.3, "dur": 0.5,
         "attrs": {}},
        # an orphan: its parent never made it into any file
        {"trace": "t2", "span": "x1", "parent": "missing",
         "name": "seq.decode", "kind": "disagg", "host": "genhost",
         "t0": 103.0, "dur": 0.1, "attrs": {}},
    ]
    a.write_text("\n".join(json.dumps(r) for r in rows_a) + "\n")
    b.write_text("\n".join(json.dumps(r) for r in rows_b) + "\n")
    report = build_report(str(tmp_path))
    assert report["skew_offsets"] == {"genhost": 2.0}
    t1 = report["traces"]["t1"]
    # skew-corrected: genhost's 102.3 became 100.3, inside the root
    (decode,) = [s for s in t1["spans"] if s["name"] == "seq.decode"]
    assert abs(decode["t0"] - 100.3) < 1e-9
    assert t1["orphans"] == []
    v = report["verdict"]
    assert v["sequence_traces"] == 1 and v["complete_sequences"] == 1
    assert v["orphan_spans"] == 1  # the t2 span with the missing parent


def test_edge_attribution_sums_exactly_to_e2e(tmp_path):
    from tools.trace_report import attribute_edges, build_traces

    spans = [
        {"trace": "t", "span": "r", "parent": None, "name": "sequence",
         "kind": "d", "host": "h", "t0": 0.0, "dur": 10.0, "attrs": {}},
        {"trace": "t", "span": "a", "parent": "r", "name": "seq.queue_wait",
         "kind": "d", "host": "h", "t0": 0.0, "dur": 2.0, "attrs": {}},
        # overlaps the queue-wait tail by 1 s: must not double count
        {"trace": "t", "span": "b", "parent": "r", "name": "seq.decode",
         "kind": "d", "host": "h", "t0": 1.0, "dur": 5.0, "attrs": {}},
        # a gap [6, 8) then an upload [8, 10)
        {"trace": "t", "span": "c", "parent": "r", "name": "seq.upload",
         "kind": "d", "host": "h", "t0": 8.0, "dur": 2.0, "attrs": {}},
    ]
    trace = build_traces(spans)["t"]
    edges = attribute_edges(trace)
    assert abs(sum(edges.values()) - trace["e2e"]) < 1e-9
    assert abs(edges["seq.queue_wait"] - 2.0) < 1e-9
    assert abs(edges["seq.decode"] - 4.0) < 1e-9  # clipped, not 5
    assert abs(edges["untracked"] - 2.0) < 1e-9
    assert abs(edges["seq.upload"] - 2.0) < 1e-9


# ---------------------------------------------------------------------------
# the disagg lifecycle end to end (threads fleet, scripted engines) — also
# the in-process artifact-schema test for the trace_report verdict line


VERDICT_SCHEMA = {
    "metric": str,
    "spans": int,
    "traces": int,
    "sequence_traces": int,
    "complete_sequences": int,
    "incomplete": int,
    "orphan_spans": int,
    "tracked_fraction": float,
    "p50_e2e_ms": float,
    "max_e2e_ms": float,
}


@pytest.mark.slow  # ~10 s traced-path e2e; the untraced wire-clean guard + tracer units
# stay tier-1 (ISSUE 19 tier-1 budget buy-back)
def test_disagg_lifecycle_yields_complete_traces(monkeypatch, tmp_path):
    from scalerl_tpu.genrl.disagg import (
        DisaggConfig,
        LocalGenerationFleet,
        ScriptedEngineFactory,
        SequenceLearner,
        record_consumption_trace,
    )
    from tools.trace_report import build_report, write_chrome

    _armed(monkeypatch, tmp_path)
    n = 12
    counter = {"i": 0}
    lock = threading.Lock()

    def source():
        with lock:
            if counter["i"] >= n:
                return None
            counter["i"] += 1
            return {"seed": counter["i"], "length": 4}

    cfg = DisaggConfig(
        num_hosts=2, lanes_per_host=2, upload_batch=1,
        heartbeat_interval_s=0.0,
    )
    learner = SequenceLearner(cfg, source)
    learner.start()
    learner.publish({"w": np.zeros((4, 4), np.float32)}, learner_step=0)
    fleet = LocalGenerationFleet(
        learner, cfg, ScriptedEngineFactory(lanes=2, response_len=4),
        use_threads=True,
    )
    fleet.start()
    seqs = []
    deadline = time.monotonic() + 60
    while len(seqs) < n and time.monotonic() < deadline:
        s = learner.get_sequence(timeout=0.2)
        if s is not None:
            seqs.append(s)
    assert len(seqs) == n
    # the learner-side consumption edges (the trainer's stamps, here the
    # soak's jax-free twin)
    now = time.monotonic()
    assert record_consumption_trace(seqs, now, now, now, now, now, 1) == n
    learner.stop()
    fleet.join()
    tracing.export_skew()

    report = build_report(str(tmp_path))
    v = report["verdict"]
    # every completed sequence -> ONE merged root-to-learn-step trace
    assert v["sequence_traces"] == n
    assert v["complete_sequences"] == n
    assert v["incomplete"] == 0
    assert v["orphan_spans"] == 0
    # per-edge attribution covers the measured end-to-end latency exactly
    for row in report["top_traces"]:
        assert row["edge_sum_ms"] == pytest.approx(row["e2e_ms"], rel=5e-2)
    # each lifecycle carries the full edge chain
    seq_traces = [
        t for t in report["traces"].values()
        if t["root"] is not None and t["root"]["name"] == "sequence"
    ]
    names = {s["name"] for t in seq_traces for s in t["spans"]}
    assert {
        "sequence", "seq.queue_wait", "seq.decode", "seq.upload",
        "seq.seq_add", "seq.learn_step",
    } <= names
    # the snapshot publish -> fetch trace is stitched too
    snap = [
        t for t in report["traces"].values()
        if t["root"] is not None and t["root"]["name"] == "snapshot_publish"
    ]
    assert snap and any(
        s["name"] == "snapshot.fetch" for s in snap[0]["spans"]
    )

    # -- verdict line schema (what a trace-soak caller parses) ----------
    line = json.loads(json.dumps(v))
    for key, typ in VERDICT_SCHEMA.items():
        assert key in line, f"verdict missing {key}"
        assert isinstance(line[key], typ) or (
            typ is float and isinstance(line[key], int)
        ), key
    assert line["metric"] == "trace_report"

    # -- Chrome trace_event JSON is valid and complete ------------------
    chrome_path = write_chrome(report, str(tmp_path / "trace_events.json"))
    with open(chrome_path) as f:
        chrome = json.load(f)
    events = chrome["traceEvents"]
    assert len(events) == v["spans"]
    for e in events[:10]:
        assert e["ph"] == "X"
        assert {"name", "pid", "tid", "ts", "dur"} <= set(e)
        assert e["ts"] >= 0


def test_disagg_untraced_path_stays_wire_clean(monkeypatch):
    """Sampling off: no trace keys on the wire, no span records, and the
    lifecycle still flows — the zero-overhead default."""
    from scalerl_tpu.genrl.disagg import (
        DisaggConfig,
        LocalGenerationFleet,
        ScriptedEngineFactory,
        SequenceLearner,
    )

    monkeypatch.delenv(tracing.ENV_SAMPLE, raising=False)
    monkeypatch.delenv(tracing.ENV_DIR, raising=False)
    tracing.reset()
    n = 4
    counter = {"i": 0}
    lock = threading.Lock()

    def source():
        with lock:
            if counter["i"] >= n:
                return None
            counter["i"] += 1
            return {"seed": counter["i"], "length": 4}

    cfg = DisaggConfig(
        num_hosts=1, lanes_per_host=2, upload_batch=1,
        heartbeat_interval_s=0.0,
    )
    learner = SequenceLearner(cfg, source)
    learner.start()
    learner.publish({"w": np.zeros((4, 4), np.float32)}, learner_step=0)
    fleet = LocalGenerationFleet(
        learner, cfg, ScriptedEngineFactory(lanes=2, response_len=4),
        use_threads=True,
    )
    fleet.start()
    seqs = []
    deadline = time.monotonic() + 60
    while len(seqs) < n and time.monotonic() < deadline:
        s = learner.get_sequence(timeout=0.2)
        if s is not None:
            seqs.append(s)
    learner.stop()
    fleet.join()
    assert len(seqs) == n
    for s in seqs:
        assert tracing.TRACE_KEY not in s
        assert "_t_q" not in s
    assert tracing.get_tracer().finished() == []


# ---------------------------------------------------------------------------
# live spans: one call site, the profiler's annotation and the ring


class _FakeAnnotation:
    made = []

    def __init__(self, name):
        self.name, self.entered, self.exited = name, 0, 0
        _FakeAnnotation.made.append(self)

    def __enter__(self):
        self.entered += 1
        return self

    def __exit__(self, *exc):
        self.exited += 1


@pytest.fixture
def fake_annotator(monkeypatch):
    _FakeAnnotation.made = []
    monkeypatch.setattr(tracing, "_ANNOTATOR", _FakeAnnotation)
    return _FakeAnnotation.made


def test_span_with_sampling_off_is_one_annotation_and_nothing_else(monkeypatch, fake_annotator):
    monkeypatch.delenv(tracing.ENV_SAMPLE, raising=False)
    tracing.reset()
    made_spans = []
    monkeypatch.setattr(
        tracing.Tracer, "start_span", lambda self, *a, **k: made_spans.append(a) or tracing.NOOP_SPAN
    )
    for _ in range(3):
        with tracing.span("genrl.read", kind="genrl", lanes=4) as live:
            assert tracing.get_tracer().current_span() is None
            live.set(completed=1)  # attributes for a span nobody records: dropped
    assert made_spans == []  # no Span, no id
    assert tracing.get_tracer().finished() == []
    snap = telemetry.get_registry().snapshot()
    assert not any(k.startswith("trace.") for k in snap), snap  # no registry counter
    # the factory ran exactly once per span, under the program's prefix
    assert [(a.name, a.entered, a.exited) for a in fake_annotator] == [("scalerl.genrl.read", 1, 1)] * 3


def test_span_without_an_annotator_is_a_plain_no_op(monkeypatch):
    monkeypatch.delenv(tracing.ENV_SAMPLE, raising=False)
    monkeypatch.setattr(tracing, "_ANNOTATOR", None)
    tracing.reset()
    with tracing.span("learn.step"):
        pass
    assert tracing.get_tracer().finished() == []
    assert tracing.get_annotator() is None


def test_sampled_spans_nest_under_the_active_span_and_carry_late_attrs(monkeypatch, fake_annotator):
    _armed(monkeypatch)
    with tracing.span("genrl.macro_step", kind="genrl") as step:
        with tracing.span("genrl.admit", kind="genrl"):
            assert tracing.current_trace_id() is not None
        with tracing.span("genrl.read", kind="genrl"):
            pass
        step.set(completed=2)
    recs = {r["name"]: r for r in tracing.get_tracer().finished()}
    root = recs["genrl.macro_step"]
    assert root["parent"] is None and root["attrs"] == {"completed": 2}
    assert recs["genrl.admit"]["parent"] == recs["genrl.read"]["parent"] == root["span"]
    assert {r["trace"] for r in recs.values()} == {root["trace"]}
    assert tracing.get_tracer().current_span() is None
    assert [a.name for a in fake_annotator] == [
        "scalerl.genrl.macro_step", "scalerl.genrl.admit", "scalerl.genrl.read",
    ]


def test_children_of_an_unsampled_root_stay_unsampled(monkeypatch):
    _armed(monkeypatch, rate="0.5")
    tracer = tracing.get_tracer()
    draws = iter([0.9, 0.1, 0.1])  # the first root loses the draw, the second wins it
    monkeypatch.setattr(tracer._rng, "random", lambda: next(draws))
    for _ in range(2):
        with tracing.span("genrl.round"):
            with tracing.span("round.learn"):
                pass
    names = [r["name"] for r in tracer.finished()]
    assert names == ["round.learn", "genrl.round"]  # one sampled trace, whole
    assert tracer.current_span() is None


def test_a_span_closes_when_its_body_raises(monkeypatch, fake_annotator):
    _armed(monkeypatch)
    with pytest.raises(RuntimeError):
        with tracing.span("learn.step", kind="learn"):
            with tracing.span("learn.dispatch", kind="learn"):
                raise RuntimeError("boom")
    assert [r["name"] for r in tracing.get_tracer().finished()] == ["learn.dispatch", "learn.step"]
    assert tracing.get_tracer().current_span() is None
    assert [(a.entered, a.exited) for a in fake_annotator] == [(1, 1), (1, 1)]
    # and with sampling off the annotation still closes
    monkeypatch.delenv(tracing.ENV_SAMPLE)
    tracing.reset()
    with pytest.raises(RuntimeError):
        with tracing.span("learn.step"):
            raise RuntimeError("boom")
    assert fake_annotator[-1].exited == 1


def test_trace_report_charges_every_new_span_name():
    """``tools/trace_report.py`` classifies every span the hot paths open:
    the two blocking reads are waits, the rest compute."""
    sys.path.insert(0, str(REPO_ROOT / "tools"))
    import trace_report

    waits = {"genrl.read", "dispatch.read"}
    compute = {
        "genrl.macro_step", "genrl.admit", "genrl.dispatch", "genrl.harvest", "genrl.push_params",
        "seq.draft", "seq.verify", "learn.step", "learn.dispatch", "loop.dispatch",
        "round.generate", "round.pack", "round.score", "round.seq_add", "round.sample",
        "round.learn", "round.push",
    }
    assert {trace_report.classify(n) for n in waits} == {"wait"}
    assert {trace_report.classify(n) for n in compute} == {"compute"}


# ---------------------------------------------------------------------------
# always on: a live span's own duration, whatever the profiler and the
# sampler do (ISSUE 34)


@pytest.fixture
def span_clock(monkeypatch):
    """The monotonic clock the live spans read, moved by hand."""
    monkeypatch.delenv(tracing.ENV_SAMPLE, raising=False)
    tracing.reset()
    now = [100.0]
    monkeypatch.setattr(tracing, "_monotonic", lambda: now[0])

    def run(name, took, children=(), **late):
        with tracing.span(name, kind="test", lanes=4) as live:
            for child, child_took in children:
                run(child, child_took)
            now[0] += took
            if late:
                live.set(**late)

    return run


def test_the_tracer_s_clock_is_the_one_the_benchmark_reads():
    """``harness.Context.t_open`` is ``time.perf_counter()``; a span's
    ``t_start`` is ``time.monotonic()``: one clock on Linux, so a reader
    can place a span in the measured window."""
    assert (
        time.get_clock_info("monotonic").implementation
        == time.get_clock_info("perf_counter").implementation
    )
    assert abs(time.monotonic() - time.perf_counter()) < 1e-3


def test_count_and_total_with_the_profiler_stopped_and_sampling_at_zero(span_clock):
    span_clock("genrl.macro_step", 0.015, children=[("genrl.read", 0.010)])
    first = tracing.span_totals()
    assert first["genrl.macro_step"] == {"count": 1.0, "seconds": pytest.approx(0.025)}
    for _ in range(3):
        span_clock("genrl.macro_step", 0.002, children=[("genrl.read", 0.010), ("genrl.read", 0.001)])
    second = tracing.span_totals()
    # two snapshots subtract: the spans that ended between them
    assert second["genrl.macro_step"]["count"] - first["genrl.macro_step"]["count"] == 3
    assert second["genrl.macro_step"]["seconds"] - first["genrl.macro_step"]["seconds"] == pytest.approx(0.039)
    assert second["genrl.read"] == {"count": 7.0, "seconds": pytest.approx(0.043)}
    # they are the registry's own counters: an operator's snapshot has them
    tree = telemetry.get_registry().snapshot()["span"]["genrl"]
    assert tree["read"] == {"count": 7.0, "seconds": pytest.approx(0.043)}
    assert tracing.get_tracer().finished() == [] and tracing.slow_spans() == []
    telemetry.reset()  # a fresh registry starts every name anew
    assert tracing.span_totals() == {}
    span_clock("genrl.read", 0.5)
    assert tracing.span_totals() == {"genrl.read": {"count": 1.0, "seconds": pytest.approx(0.5)}}


@pytest.mark.parametrize("took,slow", [(0.27, False), (0.37, True), (2.6, True)])
def test_a_span_is_slow_past_four_times_its_level_and_a_quarter_second(span_clock, took, slow):
    span_clock("loop.chunk", 30.0)  # the first occurrences compile: not judged,
    span_clock("loop.chunk", 12.0)  # and kept out of the level
    for _ in range(6):
        span_clock("loop.chunk", 0.09)
    assert tracing.slow_spans() == []
    span_clock("loop.chunk", took)  # 3 x the level is no stall; 4.1 x is
    events = tracing.slow_spans()
    assert len(events) == (1 if slow else 0)
    if slow:
        assert events[0]["level_s"] == pytest.approx(0.09) and events[0]["dur_s"] == pytest.approx(took)
        # one stall does not hide the next: it raised the level by a twentieth
        assert tracing._STATS["loop.chunk"].level == pytest.approx(0.09 * 1.05)
        span_clock("loop.chunk", 1.06 * took)
        assert len(tracing.slow_spans()) == 2


def test_a_fast_span_is_never_slow_however_far_above_its_level(span_clock):
    for _ in range(8):
        span_clock("genrl.admit", 0.0002)
    span_clock("genrl.admit", 0.2)  # a thousand times its level, under the quarter second
    assert tracing.slow_spans() == []


def test_a_slow_span_says_what_held_it(span_clock):
    for _ in range(5):
        span_clock("genrl.macro_step", 0.002, children=[("genrl.admit", 0.001), ("genrl.read", 0.012)])
    with tracing.span("round.generate", kind="genrl"):
        span_clock(
            "genrl.macro_step", 0.1, in_flight=2,
            children=[("genrl.admit", 0.001), ("genrl.read", 1.0), ("genrl.read", 0.899)],
        )
    events = tracing.slow_spans()  # in the order they ended: both reads, then the step
    assert [e["name"] for e in events] == ["genrl.read", "genrl.read", "genrl.macro_step"]
    by_name = {e["name"]: e for e in events}
    step = by_name["genrl.macro_step"]
    assert step["above"] == ["round.generate"] and by_name["genrl.read"]["above"] == [
        "round.generate", "genrl.macro_step",
    ]
    assert step["dur_s"] == pytest.approx(2.0) and step["level_s"] == pytest.approx(0.015)
    assert step["children"] == {"genrl.admit": pytest.approx(0.0005), "genrl.read": pytest.approx(0.9495)}
    assert step["attrs"] == {"lanes": 4, "in_flight": 2}
    assert step["t_end"] - step["t_start"] == pytest.approx(2.0)
    assert step["cpu_s"] is None  # neither a root nor a blocking read: not stamped
    assert by_name["genrl.read"]["cpu_s"] is not None
    assert step["kind"] == tracing.SLOW_EVENT and step in telemetry.get_recorder().events()
    # the query by when a span began
    assert [e["name"] for e in tracing.slow_spans(since=step["t_start"] + 1e-6)] == ["genrl.read"] * 2
    assert [e["name"] for e in tracing.slow_spans(until=step["t_start"])] == ["genrl.macro_step"]
    assert tracing.slow_spans(since=step["t_end"] + 1.0) == []


def _slow_root(body):
    """A root span made slow by ``body`` after five quick ones; its event."""
    import gc

    for _ in range(5):
        with tracing.span("learn.step", kind="learn"):
            with tracing.span("dispatch.read", kind="dispatch"):
                time.sleep(0.002)
    gc.collect()
    with tracing.span("learn.step", kind="learn"):
        with tracing.span("dispatch.read", kind="dispatch"):
            body()
    events = {e["name"]: e for e in tracing.slow_spans()}
    assert set(events) == {"learn.step", "dispatch.read"}
    return events


def test_a_span_blocked_in_a_wait_burns_no_cpu(monkeypatch):
    monkeypatch.delenv(tracing.ENV_SAMPLE, raising=False)
    tracing.reset()
    events = _slow_root(lambda: time.sleep(0.3))
    for event in events.values():
        assert event["dur_s"] >= 0.3 and event["cpu_s"] < 0.05, event
    assert events["learn.step"]["children"]["dispatch.read"] > 0.95
    assert events["learn.step"]["gc_collections"] == 0


def test_a_span_held_by_the_host_burns_its_duration_in_cpu_and_counts_collections(monkeypatch):
    import gc

    monkeypatch.delenv(tracing.ENV_SAMPLE, raising=False)
    tracing.reset()

    def busy():
        gc.collect()
        until = time.perf_counter() + 0.3
        while time.perf_counter() < until:
            sum(range(100))

    events = _slow_root(busy)
    for event in events.values():
        assert event["cpu_s"] > 0.6 * event["dur_s"] > 0.18, event
        assert event["gc_collections"] >= 1 and 0.0 < event["gc_pause_s"] < event["dur_s"]
