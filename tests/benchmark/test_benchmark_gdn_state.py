"""``qwen3next_group_rollout``'s limit on the matrix state's handoff, at
the rehearsal's size on the CPU (it passes the program and refuses a
reference with a planted fault), its pick identity over every layer, and
the three ``gdn_*`` readers on readings made by hand."""

import sys
from types import SimpleNamespace

import pytest

from bench_helpers import BENCH, result_line, run_cell, workload_file

CELL = "qwen3next_group_rollout"


def test_the_gdn_cell_refuses_a_state_taken_at_the_buckets_end():
    """The rehearsal (``L L L F L``): the tokens decoded right after the
    delta rule's state was handed over (two whole groups: a leader from
    its prefill's state, three members from the fork's rows) read within
    their limit, and the reference with the planted fault (the rule run on
    through the prompt's pads) reads a hundred times over it; every layer
    routes, so held + absent picks are 3 a token a layer over 5 layers."""
    notes = result_line(run_cell(CELL, 0, "--rehearse"))["notes"]
    limits = workload_file(CELL)["rehearse_params"]
    assert notes["state_handoff_ok"] is True and notes["state_rows_checked"] >= 7
    assert min(notes["state_pads"]) > 0  # a prompt that fills its bucket plants nothing
    for kind in ("logp", "value"):
        limit = limits[f"state_{kind}_median_atol"]
        assert notes[f"state_{kind}_median_err"] <= limit
        assert notes[f"pad_fault_state_{kind}_median_err"] > 100 * limit
    assert notes["picks_ok"] is True and notes["zero_picks"] == 0
    assert (notes["held_picks"] + notes["absent_picks"]) % (3 * 5) == 0
    # 4 delta-rule layers x (4 x 8 x 8 state + 3 x 64 window) float32 a lane
    assert notes["state_ok"] is True and notes["state_bytes_per_lane"] == 4 * 4 * (256 + 192)


def _metric(name):
    sys.path.insert(0, str(BENCH))
    try:
        import harness

        return harness.load_module("metrics", name)
    finally:
        sys.path.remove(str(BENCH))


def _reading(kernel_s, counters):
    sys.path.insert(0, str(BENCH))
    try:
        import program_trace
    finally:
        sys.path.remove(str(BENCH))
    logged = []
    return {
        "ctx": SimpleNamespace(trace_path=None, log=lambda *a: logged.append(a)),
        "program": program_trace.Program((0.0, 2e9), [], {}, kernel_s, 1),
        "trace": {"busy_s": 2.0}, "peaks": {"hbm_bytes_per_s": 819e9},
        "result": {"counters": counters},
    }


def test_the_gdn_readers_divide_what_they_say():
    """The kernel found by its name: 0.8 s of ``gdn_decode_update`` in 2 s
    busy is 40%; 327.6 GB of state at 819 GB/s is 0.4 s, half of the
    kernel's time; 1,146.6 GB in all is 1.4 s of 2 s busy."""
    sys.path.insert(0, str(BENCH))
    try:
        counters = {"traced_gdn_state_bytes": 327.6e9, "traced_gdn_hybrid_bytes": 1146.6e9}
        r = _reading({"gdn_decode_update": 0.8, "paged_decode": 0.1}, counters)
        assert _metric("gdn_decode_time_share").read(r) == pytest.approx(40.0)
        assert _metric("gdn_decode_roofline").read(r) == pytest.approx(50.0)
        assert _metric("gdn_hybrid_decode_roofline").read(r) == pytest.approx(70.0)
    finally:
        sys.path.remove(str(BENCH))


def test_the_gdn_readers_give_nothing_where_there_is_nothing_to_read():
    """A program whose trace names no such kernel (the parent of the PR that
    brought it, a model of another family), a run that was not traced, a
    traced run with nothing counted: ``None``, never a raise."""
    sys.path.insert(0, str(BENCH))
    try:
        counters = {"traced_gdn_state_bytes": 1e9, "traced_gdn_hybrid_bytes": 2e9}
        names = ("gdn_decode_time_share", "gdn_decode_roofline", "gdn_hybrid_decode_roofline")
        untraced = _reading({}, counters)
        untraced.update(program=None, trace=None)
        for name in names:
            assert _metric(name).read(_reading({"paged_decode": 0.1}, counters)) is None
            assert _metric(name).read(untraced) is None
        empty = _reading({"gdn_decode_update": 0.5}, {})
        assert _metric("gdn_decode_roofline").read(empty) is None
        assert _metric("gdn_hybrid_decode_roofline").read(empty) is None
    finally:
        sys.path.remove(str(BENCH))
