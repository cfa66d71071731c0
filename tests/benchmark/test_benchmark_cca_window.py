"""``zaya_group_rollout``'s limit on the window's hand-off, at the
rehearsal's size on the CPU (it passes the program and refuses a reference
with a planted fault), its pick identity over every layer, ``zaya_work``'s
counts against the issue's arithmetic, and the two new readers: one on a
reading made by hand, one on the trace recorded on the chip
(``benchmark/fixtures/small.xplane.pb``)."""

import sys
from types import SimpleNamespace

import pytest

from bench_helpers import BENCH, result_line, run_cell, workload_file

CELL = "zaya_group_rollout"


def test_the_cca_cell_refuses_a_window_taken_at_the_buckets_end():
    """The rehearsal (three layers): the two tokens decoded right after
    the window was handed over (two whole groups: a leader from its
    prefill's window, three members from the fork's rows) read within
    their limit, and the reference with the planted fault (the window
    taken at the prompt bucket's end: two pad tokens' rows) reads a
    hundred times over it; every layer routes and every expert is held, so
    held picks are 1 a token a layer over 3 layers and none is absent."""
    notes = result_line(run_cell(CELL, 0, "--rehearse"))["notes"]
    limits = workload_file(CELL)["rehearse_params"]
    assert notes["state_handoff_ok"] is True and notes["state_rows_checked"] >= 4
    assert min(notes["state_pads"]) > 0  # a prompt that fills its bucket plants nothing
    for kind in ("logp", "value"):
        limit = limits[f"state_{kind}_median_atol"]
        assert notes[f"state_{kind}_median_err"] <= limit
        assert notes[f"pad_fault_state_{kind}_median_err"] > 100 * limit
    assert notes["picks_ok"] is True and notes["zero_picks"] == 0 and notes["absent_picks"] == 0
    assert notes["held_picks"] > 0 and notes["held_picks"] % 3 == 0
    # 3 layers x 2 rows x ((4 + 2) x 8 latents + 8 shifted values) float32 a lane
    assert notes["state_ok"] is True and notes["state_bytes_per_lane"] == 3 * 2 * 56 * 4


def _bench(name):
    sys.path.insert(0, str(BENCH))
    try:
        import harness

        if name.endswith("_work"):
            return __import__(name)
        return harness.load_module("metrics", name)
    finally:
        sys.path.remove(str(BENCH))


def test_the_byte_counts_are_the_issues_arithmetic():
    """``zaya_work.py`` on the configuration file: the attention's 5.24 M
    projections and 0.33 M convolutions, the router's 0.66 M, a layer's
    207.58 M, 40,960 B of K and V a cached token, 225,280 B of window a
    lane; a substep's 8.05 GB of banks and 2.40 GB of everything else."""
    work = _bench("zaya_work")
    sys.path.insert(0, str(BENCH))
    try:
        import harness

        cfg = harness.load_json("configs", "zaya1-8b")
    finally:
        sys.path.remove(str(BENCH))
    assert work.attention_params(cfg) == 5_242_880 + 332_802
    assert work.router_params(cfg) == 660_752
    assert work.layer_params(cfg) == 207_583_506
    assert work.kv_bytes_per_token(cfg, 4) == 40_960
    assert work.state_bytes_per_lane(cfg) == 225_280
    assert work.window_rows(cfg) == 2 and work.window_channels(cfg) == 1280 + 128
    assert work.decode_expert_bytes(cfg, 1, 2) == 20 * 16 * 3 * 2048 * 2048 * 2
    assert work.decode_dense_bytes(cfg, 2, 4) == pytest.approx(2.3988e9, rel=1e-4)
    assert work.wide_head_bytes_per_substep(cfg, 48, 4) == 2048 * 262272 * 4 + 2 * 48 * 262272 * 4


def _reading(counters, trace_path=None, busy_s=2.0):
    logged = []
    return {
        "ctx": SimpleNamespace(trace_path=trace_path, log=lambda *a: logged.append(a)),
        "trace": {"busy_s": busy_s}, "peaks": {"hbm_bytes_per_s": 819e9},
        "result": {"counters": counters},
    }


def test_the_whole_steps_reader_divides_what_it_says():
    """1,146.6 GB at 819 GB/s is 1.4 s of 2 s busy; a run that counted
    nothing, or was not traced, gives nothing and does not raise."""
    roofline = _bench("cca_moe_decode_roofline")
    assert roofline.read(_reading({"traced_cca_moe_bytes": 1146.6e9})) == pytest.approx(70.0)
    assert roofline.read(_reading({})) is None
    untraced = _reading({"traced_cca_moe_bytes": 1e9})
    untraced["trace"] = None
    assert roofline.read(untraced) is None


def test_the_wide_heads_reader_finds_operations_by_their_shape():
    """On the recorded trace: the two ``custom-call``s whose result and
    operand are ``f32[512,512]`` ran 3,858 + 3,511 ns, so with that shape
    named and 1 ms busy the share is 0.7369%; the ``[2048,2048]`` fusions,
    copies and ``copy-done``s are ten events whose intervals union to
    their sum.  A shape no operation holds, a driver that names none and a
    run that was not traced give nothing."""
    sys.path.insert(0, str(BENCH))
    try:
        share = _bench("wide_head_time_share")
        small = str(BENCH / "fixtures" / "small.xplane.pb")
        r = _reading({"wide_head_shape": [512, 512]}, small, busy_s=1e-3)
        assert share.read(r) == pytest.approx(100 * (3858 + 3511) * 1e-9 / 1e-3, rel=1e-6)
        big = _reading({"wide_head_shape": [2048, 2048]}, small, busy_s=1e-3)
        assert share.read(big) == pytest.approx(100 * 2 * (13 + 23073 + 89708 + 89996 + 92202) * 1e-9 / 1e-3, rel=1e-2)
        assert share.read(_reading({"wide_head_shape": [48, 262272]}, small)) is None
        assert share.read(_reading({}, small)) is None
        untraced = _reading({"wide_head_shape": [512, 512]})
        untraced["trace"] = None
        assert share.read(untraced) is None
        # control flow only carries the array: its event spans the work inside
        assert share._carries_only("%while.3 = (s32[], f32[48,262272]{1,0}) while((s32[], f32[48,262272]{1,0}) %tuple.1), body=%b")
        assert not share._carries_only("%fusion.9 = f32[48,262272]{1,0} fusion(f32[48,2048]{1,0} %x), kind=kOutput")
    finally:
        sys.path.remove(str(BENCH))
