"""``xing4_group_rollout``'s own checks at the rehearsal's size on the CPU
(the hyper-connections' two controls beside the sound readings, the pick
identity over the routed layers, no lane state, prefix hits served),
``xing4_work``'s counts against the issue's arithmetic, and the three new
readers: two on readings made by hand, one on the trace recorded on the
chip (``benchmark/fixtures/small.xplane.pb``)."""

import re
import sys
from types import SimpleNamespace

import pytest

from bench_helpers import BENCH, result_line, run_cell, workload_file

CELL = "xing4_group_rollout"


def test_the_mhc_cell_records_its_controls_and_holds_its_counters():
    """The rehearsal (three layers, one dense): a reference with 2 Sinkhorn
    iterations in place of 20 reads far over the median limits (so the
    check refuses it), one whose maps come from a bfloat16 flattened norm
    is recorded beside it; every expert is held, so held picks are 2 a
    token a routed layer over 2 routed layers and none is absent; the
    stream is no lane state and the groups' members shared their prompts'
    pages."""
    notes = result_line(run_cell(CELL, 0, "--rehearse"))["notes"]
    limits = workload_file(CELL)["rehearse_params"]
    assert notes["mhc_control_refused"] is True
    for kind in ("logp", "value"):
        limit = limits[f"{kind}_median_atol"]
        assert notes[f"{kind}_median_err"] <= limit
        assert notes[f"sinkhorn2_reference_{kind}_median_err"] > 10 * limit
        assert notes[f"float8_reference_{kind}_median_err"] > 100 * limit
        assert notes[f"bfloat16_maps_reference_{kind}_median_err"] > 0
    assert notes["picks_ok"] is True and notes["zero_picks"] == 0 and notes["absent_picks"] == 0
    assert notes["held_picks"] > 0 and notes["held_picks"] % (2 * 2) == 0
    assert notes["stateless_ok"] is True and notes["state_bytes_per_lane"] == 0
    assert notes["prefix_skipped_recurrent"] == 0
    assert notes["prefix_ok"] is True and notes["prefix_tokens_saved"] > 0


def _bench(name):
    sys.path.insert(0, str(BENCH))
    try:
        import harness

        if name.endswith("_work"):
            return __import__(name)
        return harness.load_module("metrics", name)
    finally:
        sys.path.remove(str(BENCH))


def _config():
    sys.path.insert(0, str(BENCH))
    try:
        import harness

        return harness.load_json("configs", "xing4.0-29b-a4b")
    finally:
        sys.path.remove(str(BENCH))


def test_the_byte_counts_are_the_issues_arithmetic():
    """``xing4_work.py`` on the configuration file: the attention's 28.41 M,
    an expert's 11.01 M, a hyper-connection's 0.358 M, a routed layer's
    745.0 M and the dense layer's 128.2 M, 13,824 B of latent rows a cached
    token over the 6 pools, a substep's 7.05 GB of banks and 2.55 GB of
    everything else, 0.69 MB of stream a decoded token."""
    work, cfg = _bench("xing4_work"), _config()
    assert work.mla_params(cfg) == 2_752_512 + 4_718_592 + 2_064_384 + 4_194_304 + 14_680_064
    assert work.expert_params(cfg) == 11_010_048 and work.dense_ffn_params(cfg) == 99_090_432
    assert work.hyper_params(cfg) == 14336 * 24 + 24 + 3 + 14336
    assert work.layer_params(cfg, routed=True) == pytest.approx(745.0e6, rel=2e-4)
    assert work.layer_params(cfg, routed=False) == pytest.approx(128.2e6, rel=2e-4)
    assert (work.sublayers(cfg), work.routed_layers(cfg)) == (12, 5)
    assert work.latent_bytes_per_token(cfg, 4) == 6 * 576 * 4
    assert work.decode_expert_bytes(cfg, 1, 2) == 5 * 64 * 3 * 3584 * 1024 * 2
    assert work.decode_dense_bytes(cfg, 2, 4) == pytest.approx(2.5477e9, rel=1e-4)
    assert work.stream_shape(cfg, 96) == [96, 1, 4, 3584]
    assert work.stream_bytes_per_token(cfg, 2) == 12 * 2 * 4 * 3584 * 2
    assert work.mhc_bytes(cfg, 96, 1, 2) == 96 * 688_128 + work.hyper_weight_bytes(cfg)
    assert work.hyper_weight_bytes(cfg) == 4 * 12 * 358_427


def _reading(counters, trace_path=None, busy_s=2.0):
    logged = []
    return {
        "ctx": SimpleNamespace(trace_path=trace_path, log=lambda *a: logged.append(a)),
        "trace": {"busy_s": busy_s}, "peaks": {"hbm_bytes_per_s": 819e9},
        "result": {"counters": counters},
    }


def test_the_whole_steps_reader_divides_what_it_says():
    """982.8 GB at 819 GB/s is 1.2 s of 2 s busy; a run that counted
    nothing, or was not traced, gives nothing and does not raise."""
    roofline = _bench("mhc_moe_decode_roofline")
    assert roofline.read(_reading({"traced_mhc_moe_bytes": 982.8e9})) == pytest.approx(60.0)
    assert roofline.read(_reading({})) is None
    untraced = _reading({"traced_mhc_moe_bytes": 1e9})
    untraced["trace"] = None
    assert roofline.read(untraced) is None


def test_the_mhc_readers_name_the_streams_and_the_maps_shapes():
    """From ``[96, 1, 4, 3584]``: the stream in any element type, the
    projection's 24 columns, the matrix's 16 entries (with the token axis
    and without), the read weights and write gates, the matrix as the
    write takes it; a
    prefill's stream, the sublayer's one-row input and the head do not
    match; control flow only carries."""
    share = _bench("mhc_time_share")
    marks = share.shapes([96, 1, 4, 3584])
    for held in (
        "bf16[96,1,4,3584]{3,0,2,1:T(8,128)(2,1)}", "f32[96,1,4,3584]{3,2,0,1}", "f32[96,1,24]{0,2,1}",
        "f32[96,1,16]{0,2,1:T(8,128)}", "f32[96,16]{0,1}", "f32[96,1,4]{0,2,1}", "f32[96,1,4,4]{0,3,2,1}",
    ):
        assert marks.search(f"%fusion.7 = {held} fusion(f32[96]{{0}} %x), kind=kLoop"), held
    for other in ("bf16[1,256,4,3584]", "bf16[96,1,3584]", "f32[96,131072]", "f32[96,64,640]", "f32[96]", "f32[96,1]"):
        assert not marks.search(f"%fusion.7 = {other} fusion(f32[96]{{0}} %x), kind=kLoop"), other
    assert share._carries_only("%while.3 = (s32[], bf16[96,1,4,3584]{3,0,2,1}) while((s32[], bf16[96,1,4,3584]{3,0,2,1}) %t), body=%b")
    assert not share._carries_only("%fusion.9 = bf16[96,1,4,3584]{3,0,2,1} fusion(f32[96,1,4,4]{0,3,2,1} %m), kind=kLoop")
    # a neighbour's product with the write as its epilogue holds a weight
    # larger than the stream: its time is the weight's, and is not counted
    whole = 96 * 4 * 3584
    down = "%fusion.3 = (bf16[96,1,1,3584]{3,0,2,1}, bf16[96,1,1,3584]{3,0,2,1}) fusion(bf16[96,1,4,3584]{3,0,2,1} %x, bf16[64,1024,3584]{2,1,0} %w, f32[96,1,4,4]{0,3,2,1} %m), kind=kOutput"
    assert marks.search(down) and share._holds_more_than(down, whole)
    alone = "%fusion.4 = f32[96,1,24]{0,2,1} fusion(f32[14336,24]{1,0} %phi, bf16[96,1,4,3584]{3,0,2,1} %x), kind=kOutput"
    assert marks.search(alone) and not share._holds_more_than(alone, whole)


def test_the_mhc_readers_find_operations_in_a_recorded_trace(monkeypatch):
    """On the recorded trace, with the marks pointed at a shape it holds:
    the two ``custom-call``s on ``f32[512,512]`` ran 3,858 + 3,511 ns, so
    with 1 ms busy the share is 0.7369%, and 6.03 KB of hyper-connection
    bytes over that time are 0.1% of 819 GB/s.  A driver that names no
    stream, a shape no operation holds and a run that was not traced give
    nothing."""
    sys.path.insert(0, str(BENCH))
    try:
        share, roofline = _bench("mhc_time_share"), _bench("mhc_stream_roofline")
        import harness

        monkeypatch.setattr(harness, "load_module", lambda kind, name: share)
        small = str(BENCH / "fixtures" / "small.xplane.pb")
        nothing = _reading({"mhc_stream_shape": [96, 1, 4, 3584], "traced_mhc_bytes": 1e6}, small)
        assert share.read(nothing) is None and roofline.read(nothing) is None
        monkeypatch.setattr(share, "shapes", lambda stream: re.compile(r"\[512,512\]"))
        seconds = (3858 + 3511) * 1e-9
        r = _reading({"mhc_stream_shape": [96, 1, 4, 3584], "traced_mhc_bytes": 819e9 * seconds / 1000}, small, busy_s=1e-3)
        assert share.read(r) == pytest.approx(100 * seconds / 1e-3, rel=1e-6)
        assert roofline.read(r) == pytest.approx(0.1, rel=1e-6)
        assert share.read(_reading({}, small)) is None and roofline.read(_reading({}, small)) is None
        untraced = _reading({"mhc_stream_shape": [96, 1, 4, 3584], "traced_mhc_bytes": 1e6})
        untraced["trace"] = None
        assert share.read(untraced) is None
    finally:
        sys.path.remove(str(BENCH))
