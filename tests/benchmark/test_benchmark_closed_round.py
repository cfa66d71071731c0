"""The closed round's cell (``gpt2m_closed_round``): its per-layer run
rehearsed on the CPU, healthy and with a stall the test makes; and each of
its readers on hand-made readings and on the recorded fixtures."""

import os
import re
import shutil
import sys
import types

import pytest

from bench_helpers import BENCH, ROOT, result_line, run_cell

sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import program_trace as pt  # noqa: E402

CELL = "gpt2m_closed_round"
SHARES = ["round_generate_share", "round_learn_share", "round_push_share", "round_host_share"]


def _metric(name):
    return harness.load_module("metrics", name)


def _shares_logged(stdout):
    (line,) = [ln for ln in stdout.splitlines() if "round shares (%): " in ln]
    return {k: float(v) for k, v in re.findall(r"(generate|learn|push|host|sum) ([0-9.]+)", line)}


# appended to the copy's driver: the seeded task's reward sleeps once, in
# the round of that number, which makes ``round.score`` a slow span
_STALL = """

_plain_score, _scored = _SeededTask.score, []


def _stalling_score(self, *args):
    _scored.append(None)
    if len(_scored) == {round}:
        time.sleep({seconds})
    return _plain_score(self, *args)


_SeededTask.score = _stalling_score
"""


def _rehearse_in_a_copy(where, **stall):
    """The cell's traced rehearsal from a copy of the benchmark: its trace
    goes under the copy, not where another test's run of the cell writes."""
    shutil.copytree(BENCH, where / "benchmark")
    shutil.copy(ROOT / "BENCHMARK.json", where / "BENCHMARK.json")
    if stall:
        with open(where / "benchmark" / "traffic" / "closed_round.py", "a") as driver:
            driver.write(_STALL.format(**stall))
    proc = run_cell(
        CELL, 1, "--rehearse", cwd=where, seconds="1.5",
        env={"PYTHONPATH": str(ROOT) + os.pathsep + os.environ.get("PYTHONPATH", "")},
    )
    return proc, result_line(proc)


@pytest.fixture(scope="module")
def healthy(tmp_path_factory):
    return _rehearse_in_a_copy(tmp_path_factory.mktemp("healthy"))


def test_the_rehearsed_round_is_correct_and_its_shares_sum_to_100(healthy):
    proc, line = healthy
    assert line["correct"] is True and line["failed"] == 0, line
    assert line["metrics"]["compiles_in_window"] == {"value": 0.0, "unit": "count"}
    shares = _shares_logged(proc.stdout)
    assert shares.pop("sum") == pytest.approx(100.0, abs=1.0)
    assert set(shares) == {"generate", "learn", "push", "host"}
    assert all(0.0 <= v <= 100.0 for v in shares.values()) and shares["generate"] > shares["host"]
    # a share is a chip reading: logged in a rehearsal, never reported
    assert not set(SHARES) & set(line["rehearsed"])
    assert {"round_ms_mean", "round_slow_span_s", "round_staleness_steps"} <= set(line["rehearsed"])
    notes = line["notes"]
    assert notes["generation"] >= notes["rounds"] > 3 and all(notes["snapshot_equal"].values())
    assert 1.0 <= line["metrics"]["round_staleness_steps"]["value"] <= notes["staleness_max"] <= 8.0
    assert "slow spans that began in the window: 0, 0.000 s" in proc.stdout


def test_a_stall_the_test_makes_is_counted_once_and_named(tmp_path):
    proc, line = _rehearse_in_a_copy(tmp_path, round=45, seconds=0.4)
    assert line["correct"] is True and "round_slow_span_s" in line["rehearsed"]
    count, union = re.search(
        r"slow spans that began in the window: (\d+), ([0-9.]+) s in their union", proc.stdout
    ).groups()
    # the slow score inside the slow round counts once
    assert int(count) == 2 and 0.39 < float(union) < 0.6
    events = [ln for ln in proc.stdout.splitlines() if "  slow span: " in ln]
    assert any("'name': 'round.score'" in ln and "'above': ['genrl.round']" in ln for ln in events)
    (root,) = [ln for ln in events if "'name': 'genrl.round'" in ln]
    assert "'round.score': 0.9" in root  # the child that held the time, by its share
    assert "slow span: {" in proc.stderr + proc.stdout  # the program logged it when it ended


# ---------------------------------------------------------------------------
# the readers, on readings made by hand


def _reading(counters, rehearse=False, **more):
    logged = []
    ctx = types.SimpleNamespace(
        rehearse=rehearse, trace_path=None, log=lambda *a: logged.append(" ".join(map(str, a)))
    )
    return {"ctx": ctx, "result": {"counters": counters}, "trace": None, "logged": logged, **more}


_TOTALS = {
    "genrl.round": {"count": 50.0, "seconds": 40.0},
    "round.generate": {"count": 50.0, "seconds": 32.0},
    "round.pack": {"count": 100.0, "seconds": 0.3},
    "round.score": {"count": 50.0, "seconds": 0.1},
    "round.seq_add": {"count": 50.0, "seconds": 0.2},
    "round.sample": {"count": 50.0, "seconds": 0.4},
    "round.learn": {"count": 50.0, "seconds": 4.0},
    "round.push": {"count": 50.0, "seconds": 2.8},
    "genrl.macro_step": {"count": 3000.0, "seconds": 31.0},  # below the phases: in no share
}


def test_the_round_s_shares_come_from_the_span_totals_and_sum_to_100():
    r = _reading({"span_totals": _TOTALS})
    assert _metric("round_ms_mean").read(r) == pytest.approx(800.0)
    got = {name: _metric(name).read(r) for name in SHARES}
    assert got == pytest.approx({
        "round_generate_share": 80.0, "round_learn_share": 11.0, "round_push_share": 7.0,
        # score, pack and insert are 1.5%; the 0.2 s no child covers is the root's own
        "round_host_share": 2.0,
    })
    assert sum(got.values()) == pytest.approx(100.0)
    assert sum("round shares (%)" in line for line in r["logged"]) == 1  # logged once a run
    # a rehearsal logs them and reports none
    rehearsed = _reading({"span_totals": _TOTALS}, rehearse=True)
    assert all(_metric(name).read(rehearsed) is None for name in SHARES)
    assert _metric("round_ms_mean").read(rehearsed) == pytest.approx(800.0)


def test_the_counter_readers_give_nothing_on_a_program_without_totals():
    """The parent of the PR that brought the totals runs the cell too: its
    driver's counters hold an empty table and no slow-span seconds."""
    r = _reading({"span_totals": {}, "staleness_mean": 1.75})
    for name in SHARES + ["round_ms_mean", "round_slow_span_s"]:
        assert _metric(name).read(r) is None, name
    assert _metric("round_staleness_steps").read(r) == 1.75
    assert _metric("round_slow_span_s").read(_reading({"slow_span_s": 0.0})) == 0.0
    assert _metric("round_slow_span_s").read(_reading({"slow_span_s": 2.6})) == 2.6


def _program(spans, idle, devices=1):
    return pt.Program((0.0, 4e9), pt.tree(spans), idle, {}, devices)


def test_handoff_idle_is_the_idle_outside_generation_over_the_traced_rounds():
    ms = 1e6
    spans = [
        ("scalerl.genrl.round", 0, 1000 * ms), ("scalerl.round.generate", 0, 800 * ms),
        ("scalerl.genrl.macro_step", 10 * ms, 20 * ms), ("scalerl.genrl.read", 12 * ms, 19 * ms),
        ("scalerl.round.learn", 810 * ms, 900 * ms), ("scalerl.learn.step", 811 * ms, 899 * ms),
        ("scalerl.dispatch.read", 820 * ms, 899 * ms), ("scalerl.round.push", 900 * ms, 960 * ms),
        ("scalerl.genrl.round", 1100 * ms, 2100 * ms),
    ]
    idle = {
        "scalerl.genrl.round": 0.004, "scalerl.round.generate": 0.050, "scalerl.genrl.read": 0.030,
        "scalerl.round.learn": 0.001, "scalerl.learn.step": 0.006, "scalerl.dispatch.read": 0.002,
        "scalerl.round.push": 0.047, "(no program span open)": 0.100,
    }
    r = _reading({}, trace={"idle_share": 0.0625}, program=_program(spans, idle))
    # 4 + 1 + 6 + 2 + 47 ms of 2 s of rounds
    assert _metric("round_handoff_idle_share").read(r) == pytest.approx(100.0 * 0.060 / 2.0)
    assert _metric("rollout_device_idle_share").read(r) == pytest.approx(6.25)
    assert _metric("learn_read_wait_ms_p50.round").read(r) == pytest.approx(79.0)
    assert _metric("learn_host_ms_p50.round").read(r) == pytest.approx(9.0)
    # no device in the trace (a rehearsal), or no round in it: nothing to read
    assert _metric("round_handoff_idle_share").read(
        _reading({}, trace={"idle_share": 0.0}, program=_program(spans, idle, devices=0))
    ) is None
    assert _metric("round_handoff_idle_share").read(
        _reading({}, trace={"idle_share": 0.0}, program=_program(spans[2:4], idle))
    ) is None


@pytest.mark.parametrize("fixture", ["program_spans.xplane.pb", "small.xplane.pb"])
def test_the_trace_readers_on_the_recorded_fixtures(fixture):
    """Neither recorded trace holds a round: the readers built on the
    program's spans return nothing and none raises; the device's idle
    share is read from both."""
    import trace_reduce

    path = str(BENCH / "fixtures" / fixture)
    ctx = types.SimpleNamespace(rehearse=False, trace_path=path, log=lambda *a: None)
    r = {"ctx": ctx, "result": {"end_to_end": {}}, "trace": trace_reduce.reduce_trace(path)}
    for name in ("round_handoff_idle_share", "learn_host_ms_p50.round", "learn_read_wait_ms_p50.round"):
        assert _metric(name).read(r) is None, name
    assert 0.0 < _metric("rollout_device_idle_share").read(r) < 100.0
