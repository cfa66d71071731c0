"""``program_trace.py``: the program's own spans and kernel names read out
of a profiler trace, checked on a second small trace recorded on the chip
(``benchmark/fixtures/program_spans.xplane.pb``, from
``benchmark/tools/record_program_fixture.py``) against numbers worked out
by hand from that trace's own text dump."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[2] / "benchmark"
sys.path.insert(0, str(BENCH))

import program_trace as pt  # noqa: E402

FIXTURE = BENCH / "fixtures" / "program_spans.xplane.pb"


def test_spans_nest_by_containment_and_keep_their_own_time():
    spans = [
        ("step", 0, 100), ("admit", 5, 15), ("read", 40, 90), ("inner", 50, 60),
        ("step", 110, 150), ("read", 120, 140), ("stray", 145, 160),
    ]
    tree = pt.tree(spans)
    assert [(sp.name, sp.parent) for sp in tree] == [
        ("step", -1), ("admit", 0), ("read", 0), ("inner", 2),
        ("step", -1), ("read", 4), ("stray", -1),  # overlaps its neighbour: a sibling
    ]
    assert [sp.self_ns for sp in tree] == [40, 10, 40, 10, 20, 20, 15]
    program = pt.Program((0, 200), tree, {}, {}, 0)
    assert program.durations_ms("read") == pytest.approx([50e-6, 20e-6])
    assert program.durations_ms("inner", inside="step") == pytest.approx([10e-6])
    assert program.durations_ms("stray", inside="step") == []
    assert program.less_ms("step", "read") == pytest.approx([50e-6, 20e-6])
    assert program.less_ms("step", "inner") == pytest.approx([90e-6, 40e-6])
    assert program.less_ms("nothing", "read") == []


def test_a_kernel_is_known_by_the_head_of_its_operation():
    assert pt.kernel_of("%segment_flash_bwd_dkv.N custom-call f32[2,16,1024,64]") == "segment_flash_bwd_dkv"
    assert pt.kernel_of("%paged_decode custom-call f32[16,1,16,64]") == "paged_decode"
    assert pt.kernel_of("%toy_fwd.N.N custom-call f32[8]") == "toy_fwd"
    # what the trace showed before the kernels had names is a kernel too,
    # named after its scope: the readers ask for kernels by name
    assert pt.kernel_of("%block_N.N custom-call f32[2,16,1024,64]") == "block_N"


def test_reduction_of_the_recorded_trace_matches_the_hand_worked_numbers():
    """``fixtures/program_spans.dump.txt`` is the trace as text.  By hand:

    ``bench.window`` lasts 13,724,730 ns and holds three ``scalerl.toy.step``
    (3,657,000 / 3,457,610 / 3,313,260 ns), each with one
    ``scalerl.toy.dispatch`` (479,630 / 321,920 / 388,260) and one
    ``scalerl.toy.read`` (61,380 / 82,770 / 104,990) inside it.  A step's
    own time is its duration less both children: 3,115,990 / 3,052,920 /
    2,820,010; a step less its read: 3,595,620 / 3,374,840 / 3,208,270.

    The device ran ``jit_toy_step`` three times: a ``toy_fwd`` (1,907 /
    2,102 / 1,937 ns), two ``toy_bwd`` (173 + 1,826, 172 + 1,787, 172 +
    1,787) and a copy-start, a copy-done and a matmul fusion, busy 95,624 +
    95,598 + 95,684 = 286,906 ns in all.  Its clock is shifted by 1,251,332
    ns (the third module against its ``DoEnqueueProgram``), after which
    every module runs after its step's dispatch span has closed and before
    its read span opens.  So the dispatch spans (1,189,810 ns together)
    and the read spans (249,140) are idle throughout, the steps' own time
    (8,988,920) is idle but for the device's 286,906, and the rest of the
    window, 13,724,730 - 10,427,870 = 3,296,860 ns, has no span open.
    """
    assert FIXTURE.stat().st_size < 1 << 20
    p = pt.reduce(str(FIXTURE))
    ns = 1e-9
    assert p.devices == 1
    assert p.window_ns[1] - p.window_ns[0] == 13_724_730
    rows = {name: rest for name, *rest in p.table()}
    assert list(rows) == ["scalerl.toy.step", "scalerl.toy.dispatch", "scalerl.toy.read"]
    # count, median ms, median self ms, idle seconds charged
    assert rows["scalerl.toy.step"] == pytest.approx([3, 3.457610, 3.052920, 8_702_014 * ns])
    assert rows["scalerl.toy.dispatch"] == pytest.approx([3, 0.388260, 0.388260, 1_189_810 * ns])
    assert rows["scalerl.toy.read"] == pytest.approx([3, 0.082770, 0.082770, 249_140 * ns])
    assert p.idle_by_span["(no program span open)"] == pytest.approx(3_296_860 * ns)
    assert sum(p.idle_by_span.values()) == pytest.approx((13_724_730 - 286_906) * ns)
    assert p.less_ms("scalerl.toy.step", "scalerl.toy.read") == pytest.approx(
        [3.595620, 3.374840, 3.208270]
    )
    assert p.durations_ms("scalerl.toy.read", inside="scalerl.toy.step") == pytest.approx(
        [0.061380, 0.082770, 0.104990]
    )
    assert p.kernel_s == pytest.approx({"toy_fwd": 5_946 * ns, "toy_bwd": 5_917 * ns})
    text = "\n".join(pt.report(p))
    assert "scalerl.toy.read: 3 spans, median 0.083 ms" in text and "toy_bwd" in text


def test_readers_return_nothing_where_there_is_nothing_to_read(tmp_path):
    """The parent of the PR that named the kernels and opened the spans
    has neither: on its traces every reader gives ``None`` and none raises.
    ``small.xplane.pb`` is such a trace (no ``scalerl.*`` annotation, one
    Mosaic call named after its jit scope)."""
    import trace_reduce

    small = str(BENCH / "fixtures" / "small.xplane.pb")
    logged = []
    ctx = type("Ctx", (), {"trace_path": small, "log": lambda self, *a: logged.append(a)})()
    reading = {"ctx": ctx, "result": {"end_to_end": {}}, "trace": trace_reduce.reduce_trace(small)}
    for name in (
        "engine_host_ms_p50", "engine_read_wait_ms_p50", "learn_host_ms_p50",
        "learn_read_wait_ms_p50", "segment_flash_fwd_time_share", "segment_flash_bwd_time_share",
    ):
        import harness

        assert harness.load_module("metrics", name).read(reading) is None, name
    assert reading["program"].spans == [] and set(reading["program"].kernel_s) == {"mosaic_add_one"}
    untraced = {"ctx": type("Ctx", (), {"trace_path": None})(), "result": {}, "trace": None}
    assert harness.load_module("metrics", "learn_host_ms_p50").read(untraced) is None


def test_readers_on_the_recorded_trace():
    """The same readers over a trace that has spans and named kernels: a
    median with the count of spans it came from in the log, and a share of
    the busy time."""
    import trace_reduce

    logged = []
    ctx = type("Ctx", (), {
        "trace_path": str(FIXTURE), "log": lambda self, *a: logged.append(" ".join(map(str, a))),
    })()
    reading = {
        "ctx": ctx, "result": {"end_to_end": {"rate": 1.0}},
        "trace": trace_reduce.reduce_trace(str(FIXTURE)),
    }
    value = pt.p50_ms(reading, "toy_host_ms_p50", lambda p: p.less_ms("scalerl.toy.step", "scalerl.toy.read"))
    assert value == pytest.approx(3.374840)
    assert any("toy_host_ms_p50: 3.375 ms, the median of 3 spans" in line for line in logged)
    assert any("device idle charged" in line for line in logged)  # the table, logged once
    share = pt.kernel_share(reading, "toy_bwd_time_share", ("toy_bwd", "absent"))
    assert share == pytest.approx(100 * 5_917 / 286_906)
    assert pt.kernel_share(reading, "absent_time_share", ("absent",)) is None
    assert sum("program spans inside" in line for line in logged) == 1


# The names a ``benchmark`` PR appends to each cell's ``per_layer`` list (and
# to ``BENCHMARK.json``) to have the harness read these metrics: the PR that
# brought the readers may not edit a file the benchmark already had.
_APPENDS = {
    "gpt2m_group_rollout": ["engine_host_ms_p50", "engine_read_wait_ms_p50"],
    "gpt2m_packed_learn": [
        "learn_host_ms_p50", "learn_read_wait_ms_p50",
        "segment_flash_fwd_time_share", "segment_flash_bwd_time_share",
    ],
    "gpt2l_learn_dp2mp2": [
        "learn_host_ms_p50", "learn_read_wait_ms_p50",
        "segment_flash_fwd_time_share", "segment_flash_bwd_time_share",
    ],
}


@pytest.mark.parametrize("cell", sorted(_APPENDS))
def test_a_cell_reads_the_program_spans_once_its_list_names_the_metrics(cell, tmp_path, capsys):
    """In a copy of the benchmark whose cell lists the new names, a CPU
    rehearsal reads every ``_ms_p50`` one from the host plane of its own
    trace (the program's spans exist and nest there) and leaves the kernel
    shares out (no device plane); the command line reads the same trace."""
    import json
    import os
    import shutil

    from bench_helpers import ROOT, result_line, run_cell

    shutil.copytree(BENCH, tmp_path / "benchmark")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    path = tmp_path / "benchmark" / "workloads" / f"{cell}.json"
    workload = json.loads(path.read_text())
    workload["per_layer"] += _APPENDS[cell]
    path.write_text(json.dumps(workload))
    proc = run_cell(
        cell, 1, "--rehearse", cwd=tmp_path,
        env={"PYTHONPATH": str(ROOT) + os.pathsep + os.environ.get("PYTHONPATH", "")},
    )
    line = result_line(proc)
    assert line["correct"] is True and line["failed"] == 0, line
    spans = [m for m in _APPENDS[cell] if m.endswith("_ms_p50")]
    assert set(spans) <= set(line["rehearsed"])
    assert not (set(_APPENDS[cell]) - set(spans)) & set(line["rehearsed"])
    assert f"{spans[0]}: " in proc.stdout and "spans in the traced window" in proc.stdout
    (trace,) = (tmp_path / "chiprun_out" / "rehearsal" / cell).rglob("*.xplane.pb")
    assert pt.main([str(trace), *spans]) == 0
    said = capsys.readouterr().out
    for name in spans:
        assert float(said.split(f"{name} = ")[1].split()[0]) > 0
