"""The two bounds of the exposed collective share (``benchmark/link_ops.py``)
on a slice of a real trace of the four-chip learn step compiled with
asynchronous collectives (``benchmark/fixtures/async_fusion_rows.json``:
one plain all-reduce, one ``async-collective-start``, four asynchronous
collective fusions, one ``async-collective-done``, 121 other operations),
against sums taken from the slice itself; and on the recorded traces of
programs that have no such instruction, where both bounds are what
``collective_exposed_share`` reads."""

import json
import re
import sys
import types
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[2] / "benchmark"
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import link_ops  # noqa: E402
import program_trace  # noqa: E402
import trace_reduce as tr  # noqa: E402

ROWS = json.loads((BENCH / "fixtures" / "async_fusion_rows.json").read_text())["rows"]


def _event(hlo, start, duration):
    """As ``trace_reduce.load`` makes an event of an instruction."""
    head, opcode, result = tr.short_name(hlo)
    head = re.sub(r"\d+", "N", head)
    return tr.Event(
        f"{head} {opcode} {result}".strip(), start, start + duration, tr._is_mosaic(hlo),
        bool(tr._COLLECTIVE.match(opcode) or tr._COLLECTIVE.match(head)),
    )


EVENTS = [_event(*row) for row in ROWS]
HLO = [row[0] for row in ROWS]
LO, HI = EVENTS[0].start_ns, EVENTS[-1].end_ns
# ``trace_reduce`` takes an event with another inside it for a container and
# counts it nowhere (here one fusion with a zero-length custom call at its
# start: PERF.md section 7 (a)); the sums below leave those out as it does
_CONTAINERS = {(ev.start_ns, ev.end_ns) for ev, _own, parent in tr.nesting(EVENTS) if parent}


def _sum(keep):
    return sum(
        ev.end_ns - ev.start_ns
        for ev, hlo in zip(EVENTS, HLO)
        if (ev.start_ns, ev.end_ns) not in _CONTAINERS and keep(ev, hlo)
    )


def test_the_instructions_of_an_asynchronous_reduction_are_told_apart():
    kinds = [link_ops.kind(hlo) for hlo, _s, _d in ROWS]
    assert kinds.count(link_ops.WAIT) == 2 and kinds.count(link_ops.FUSED) == 4
    waits = [hlo.split(" ")[0] for hlo, _s, _d in ROWS if link_ops.kind(hlo) == link_ops.WAIT]
    assert waits == ["%async-collective-start", "%async-collective-done"]
    # none of the six is a collective to the accepted reader; the plain
    # all-reduce, the permutes and the all-to-alls of the slice are
    assert [ev.collective for ev, k in zip(EVENTS, kinds) if k] == [False] * 6
    assert EVENTS[0].collective and EVENTS[0].name.startswith("%all-reduce.N all-reduce")
    assert {ev.name.split(" ")[1] for ev in EVENTS if ev.collective} >= {"all-reduce"}
    assert link_ops.kind("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop, calls=%fused_computation.5") is None
    assert link_ops.kind("%all-reduce.7 = f32[8]{0} all-reduce(f32[8]{0} %x), channel_id=1") is None


@pytest.mark.parametrize(
    "kinds",
    [(), (link_ops.WAIT,), (link_ops.WAIT, link_ops.FUSED)],
    ids=["accepted", "with_waits", "with_fused"],
)
def test_each_bound_counts_its_operations_and_nothing_else(kinds):
    """The slice's operations run one after another, so what is exposed is
    the counted operations' own time, and busy time is everyone's."""
    exposed, busy = link_ops.exposed_ns(EVENTS, HLO, LO, HI, kinds)
    assert busy == pytest.approx(_sum(lambda ev, hlo: True), rel=1e-9)
    assert exposed == pytest.approx(
        _sum(lambda ev, hlo: ev.collective or link_ops.kind(hlo) in kinds), rel=1e-9
    )


def test_the_bounds_bracket_and_the_gaps_are_the_waits_and_the_fusions():
    accepted, low, high = (
        link_ops.exposed_ns(EVENTS, HLO, LO, HI, kinds)[0]
        for kinds in ((), (link_ops.WAIT,), (link_ops.WAIT, link_ops.FUSED))
    )
    assert 0 < accepted < low < high
    # start 2,917 ns and done 59,337 ns; three of the four fusions (76,378,
    # 82,522 and 63,627 ns: the fourth is the container)
    assert low - accepted == pytest.approx(62_254.0, rel=1e-6)
    assert high - low == pytest.approx(222_527.0, rel=1e-6)


@pytest.mark.parametrize("fixture", ["small.xplane.pb", "program_spans.xplane.pb"])
def test_a_program_without_such_instructions_reads_the_accepted_share(fixture, capsys):
    path = str(BENCH / "fixtures" / fixture)
    found = link_ops.shares(path)
    reduced = tr.reduce_trace(path)
    assert found["accepted"] == pytest.approx(
        100.0 * reduced["collective_exposed_s"] / reduced["busy_s"], abs=1e-9
    )
    assert found["with_waits"] == found["with_fused"] == found["accepted"]
    # the way to the readers while no cell's list names them
    assert program_trace.main([path, "collective_wait_share", "collective_link_share"]) == 0
    said = capsys.readouterr().out
    for name in ("collective_wait_share", "collective_link_share"):
        assert float(said.split(f"{name} = ")[1].split()[0]) == pytest.approx(found["accepted"])


@pytest.mark.parametrize("name", ["collective_wait_share", "collective_link_share"])
def test_a_run_that_was_not_traced_leaves_the_metric_out(name):
    metric = harness.load_module("metrics", name)
    assert (metric.NAME, metric.UNIT, metric.LAYER, metric.MOVES) == (
        name, "%", "sharding", "learn_tokens_per_s",
    )
    reading = {"ctx": types.SimpleNamespace(trace_path=None, log=print), "trace": None}
    assert metric.read(reading) is None
