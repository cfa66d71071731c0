"""``op_scopes.py``: device time by program scope, read out of a trace's
event METADATA.  Checked on the small trace recorded on the chip
(``benchmark/fixtures/program_spans.xplane.pb``) against numbers worked out
by hand from its text dump, on hand-made ``XSpace`` files written with the
few lines of wire format below, and against TensorFlow's generated classes
where they can be imported."""

import sys
import types
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[2] / "benchmark"
sys.path.insert(0, str(BENCH))

import op_scopes as ops  # noqa: E402
import trace_reduce  # noqa: E402

FIXTURE = BENCH / "fixtures" / "program_spans.xplane.pb"


# ---------------------------------------------------------------------------
# a writer of the wire format, for the hand-made files


def _varint(n):
    n &= (1 << 64) - 1
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _int(field, value):
    return _varint(field << 3) + _varint(value)


def _msg(field, payload):
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


STAT_IDS = {"tf_op": 1, "hlo_category": 2, "program_id": 3, "flops": 4, "bytes_accessed": 5, "source": 6}


def _stat(name, value):
    body = _int(1, STAT_IDS[name])
    if isinstance(value, str):
        body += _msg(5, value)
    elif name == "program_id":
        body += _int(3, value)  # uint64_value
    else:
        body += _int(4, value)  # int64_value
    return _msg(5, body)  # XEventMetadata.stats


def _event_meta(meta_id, name, **stats):
    body = _int(1, meta_id) + _msg(2, name) + b"".join(_stat(k, v) for k, v in stats.items())
    return _msg(4, _int(1, meta_id) + _msg(2, body))  # XPlane.event_metadata entry


def _line(name, events):
    """``events``: (metadata id, offset ps, duration ps)."""
    body = _int(1, 1) + _msg(2, name)
    for meta_id, offset, duration in events:
        body += _msg(4, _int(1, meta_id) + _int(2, offset) + _int(3, duration))
    return _msg(3, body)


def _space(path, metas, modules, ops_events, tf_op=True):
    plane = _int(1, 2) + _msg(2, "/device:TPU:0")
    plane += _line("XLA Modules", modules) + _line("XLA Ops", ops_events)
    plane += b"".join(metas)
    for name, stat_id in STAT_IDS.items():
        if name != "tf_op" or tf_op:
            plane += _msg(5, _int(1, stat_id) + _msg(2, _int(1, stat_id) + _msg(2, name)))
    path.write_bytes(_msg(1, plane))
    return str(path)


def _two_programs(tmp_path, tf_op=True):
    """Two programs whose hot operation has the SAME instruction text."""
    text = "%fusion.1 = f32[8,128]{1,0} fusion(f32[8,128]{1,0} %p), kind=kOutput, calls=%fused_computation.1"
    names = dict(
        decode="jit(decode)/while/body/closed_call/TransformerPolicy/block_3/qkv/dot_general:",
        prefill="jit(prefill)/TransformerPolicy/block_11/mlp_in/dot_general:",
    )
    stats = lambda which, pid: dict(  # noqa: E731
        hlo_category="convolution fusion", program_id=pid, flops=1000, bytes_accessed=4096,
        source="/somewhere/scalerl_tpu/models/transformer.py:1216",
        **({"tf_op": names[which]} if tf_op else {}),
    )
    metas = [
        _event_meta(1, text, **stats("decode", 77)),
        _event_meta(2, text, **stats("prefill", 88)),
        _event_meta(3, "%copy-start = f32[8] copy-start(f32[8] %p)", hlo_category="copy-start", program_id=77),
        _event_meta(10, "jit_decode(77)"),
        _event_meta(11, "jit_prefill(88)"),
    ]
    modules = [(10, 0, 9_000_000), (11, 10_000_000, 5_000_000)]
    ops_events = [
        (1, 1_000_000, 2_000_000), (3, 3_000_000, 500_000), (1, 4_000_000, 3_000_000),
        (2, 11_000_000, 4_000_000),
    ]
    return _space(tmp_path / "two.xplane.pb", metas, modules, ops_events, tf_op=tf_op)


# ---------------------------------------------------------------------------
# the recorded trace


def test_the_recorded_trace_by_scope_matches_the_hand_worked_numbers():
    """``fixtures/program_spans.dump.txt`` by hand: ``jit_toy_step`` ran
    three times; its matmul ``%fusion`` took 91,702 + 91,520 + 91,772 =
    274,994 ns, ``toy_fwd`` 1,907 + 2,102 + 1,937 = 5,946, the two
    ``toy_bwd`` calls 173 + 1,826 + 172 + 1,787 + 172 + 1,787 = 5,917,
    ``copy-start`` 13 + 14 + 13 = 40 and ``copy-done`` 3 x 3 = 9: 286,906 ns
    in all, which is ``trace_reduce``'s busy time.  The matmul's op_name is
    ``jit(toy_step)/block_7/dot_general``: a bare block, so nobody named it."""
    table = ops.read(str(FIXTURE))
    assert table.devices == 1
    rows = {r.scope: r for r in table.rows}
    assert {r.program for r in table.rows} == {"jit_toy_step"}
    ns = 1e-9
    matmul = rows["(unnamed) toy_step"]
    assert matmul.seconds == pytest.approx(274_994 * ns) and matmul.calls == 3
    assert matmul.flops == 3 * 17_184_063_488 and matmul.bytes_accessed == 3 * 50_331_648
    assert matmul.category == "convolution fusion"
    assert matmul.source == "benchmark/tools/record_program_fixture.py:55"
    assert matmul.tails[0][0].startswith("block_7/dot_general @ ")
    fwd, bwd = rows["block_N/toy_fwd/pallas_call"], rows["block_N/toy_bwd/pallas_call"]
    assert (fwd.seconds, fwd.calls) == (pytest.approx(5_946 * ns), 3)
    assert (bwd.seconds, bwd.calls) == (pytest.approx(5_917 * ns), 6)
    assert fwd.category == bwd.category == "custom-call"
    assert rows["(compiler) copy-start"].seconds == pytest.approx(40 * ns)
    assert rows["(compiler) copy-done"].seconds == pytest.approx(9 * ns)
    assert rows["(compiler) copy-start"].bytes_accessed == 3 * 50_331_652
    assert len(rows) == 5
    # the rows tile the busy time trace_reduce reports, to the nanosecond
    busy = trace_reduce.reduce_trace(str(FIXTURE))["busy_s"]
    assert table.busy_s == pytest.approx(busy) and busy == pytest.approx(286_906 * ns)
    assert table.own_s == pytest.approx(busy)
    assert table.share(lambda r: True) == pytest.approx(100.0)
    assert table.share(lambda r: ops.class_of(r.scope) == "unnamed") == pytest.approx(100 * 274_994 / 286_906)
    assert "own time 0.0003 s of 0.0003 s busy" in ops.report(table)[0]


def test_the_rows_fold_to_a_depth():
    table = ops.read(str(FIXTURE))
    folded = {r.scope: r for r in table.fold(1)}
    assert set(folded) == {"(unnamed) toy_step", "block_N", "(compiler) copy-start", "(compiler) copy-done"}
    assert folded["block_N"].calls == 9
    assert folded["block_N"].seconds == pytest.approx((5_946 + 5_917) * 1e-9)


def test_the_hand_decoder_agrees_with_the_generated_classes():
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except Exception as e:  # noqa: BLE001 - whatever stops the import
        pytest.skip(f"no generated XSpace classes here: {e}")
    space = xplane_pb2.XSpace()
    space.ParseFromString(FIXTURE.read_bytes())
    (theirs,) = [p for p in space.planes if p.name == "/device:TPU:0"]
    (ours,) = ops.decode(str(FIXTURE))
    stat_names = {k: v.name for k, v in theirs.stat_metadata.items()}
    assert set(ours.metas) == set(theirs.event_metadata)
    for key, meta in theirs.event_metadata.items():
        stats = {stat_names[s.metadata_id]: s for s in meta.stats}
        mine = ours.metas[key]
        assert mine.name == meta.name
        tf_op = stats["tf_op"].str_value if "tf_op" in stats else None
        assert mine.op_name == (None if tf_op is None else tf_op.rsplit(":", 1)[0])
        if "flops" in stats:
            assert mine.flops == stats["flops"].int64_value
            assert mine.bytes_accessed == stats["bytes_accessed"].int64_value
            assert mine.program_id == stats["program_id"].uint64_value
            assert mine.category == stats["hlo_category"].str_value
    (line,) = [l for l in theirs.lines if l.name == "XLA Ops"]
    assert [(m, s, d) for m, s, d in ours.ops] == [
        (e.metadata_id, float((line.timestamp_ns * 1000 + e.offset_ps) // 1000), float(e.duration_ps // 1000))
        for e in line.events
    ]
    assert ours.modules == {6671129503126069069: "jit_toy_step"}


# ---------------------------------------------------------------------------
# hand-made traces


def test_the_join_is_by_metadata_id_not_by_instruction_text(tmp_path):
    path = _two_programs(tmp_path)
    table = ops.read(path)
    rows = {(r.program, r.scope): r for r in table.rows}
    assert set(rows) == {
        ("jit_decode", "block_N/qkv/dot_general"),
        ("jit_prefill", "block_N/mlp_in/dot_general"),
        ("jit_decode", "(compiler) copy-start"),
    }
    qkv = rows[("jit_decode", "block_N/qkv/dot_general")]
    assert (qkv.seconds, qkv.calls, qkv.flops) == (pytest.approx(5_000e-9), 2, 2000)
    assert qkv.source == "scalerl_tpu/models/transformer.py:1216"
    mlp = rows[("jit_prefill", "block_N/mlp_in/dot_general")]
    assert (mlp.seconds, mlp.calls) == (pytest.approx(4_000e-9), 1)
    assert ops.class_of(qkv.scope) == "attention" and ops.class_of(mlp.scope) == "ffn"
    # the shares the metric files ask for
    assert table.share(ops.is_decode) == pytest.approx(100 * 5_500 / 9_500)
    assert table.share(lambda r: not ops.is_decode(r)) == pytest.approx(100 * 4_000 / 9_500)
    # trace_reduce, which knows an operation by its text, sees one name
    named = dict(trace_reduce.reduce_trace(path)["device_ops"])
    assert named["%fusion.N fusion f32[8,128]"] == pytest.approx(9_000e-9)


@pytest.mark.parametrize("metric", [
    "rollout_prefill_time_share", "rollout_attention_time_share", "rollout_ffn_time_share",
    "rollout_head_sampler_time_share", "rollout_unnamed_time_share", "learn_forward_time_share",
    "learn_backward_time_share", "learn_update_time_share", "fused_act_time_share",
    "fused_env_time_share", "fused_learn_time_share",
])
def test_a_metric_file_reads_a_share_and_nothing_where_there_is_none(tmp_path, metric):
    import harness

    module = harness.load_module("metrics", metric)
    assert (module.NAME, module.UNIT) == (metric, "%")
    logged = []

    def reading(path, traced=True):
        ctx = types.SimpleNamespace(trace_path=path, log=lambda *a: logged.append(a))
        return {"ctx": ctx, "result": {}, "trace": trace_reduce.reduce_trace(path) if traced and path else None}

    value = module.read(reading(_two_programs(tmp_path)))
    expected = {
        "rollout_prefill_time_share": 100 * 4_000 / 9_500,
        "rollout_attention_time_share": 100 * 5_000 / 9_500,
    }.get(metric, 0.0)
    assert value == pytest.approx(expected) and logged
    # a trace whose events carry no tf_op (the parent of this reader's
    # runtime), a run that was not traced, a rehearsal without a device
    # plane, a file that is no XSpace: nothing, and nothing raised
    assert module.read(reading(_two_programs(tmp_path, tf_op=False))) is None
    assert module.read(reading(None)) is None
    assert module.read(reading(_two_programs(tmp_path), traced=False)) is None
    broken = tmp_path / "broken.xplane.pb"
    broken.write_bytes(b"\x0a\xff\xff\xff\xff\x0f not a trace")
    r = reading(None)
    r["ctx"].trace_path, r["trace"] = str(broken), {"busy_s": 1.0}
    assert module.read(r) is None


@pytest.mark.parametrize("op_name, category, scope, kind", [
    ("jit(decode)/while/body/closed_call/TransformerPolicy/block_7/qkv/dot_general", "convolution fusion",
     "block_N/qkv/dot_general", "attention"),
    ("jit(decode)/while/body/closed_call/TransformerPolicy/block_0/attend/jit(_paged_decode)/paged_decode/pallas_call",
     "custom-call", "block_N/attend/paged_decode/pallas_call", "attention"),
    ("jit(decode)/while/body/closed_call/TransformerPolicy/block_21/kv_write/scatter", "loop fusion",
     "block_N/kv_write/scatter", "attention"),
    ("jit(decode)/while/body/closed_call/TransformerPolicy/block_2/LayerNorm_1/div", "loop fusion",
     "block_N/LayerNorm_1/div", "ffn"),
    ("jit(decode)/while/body/closed_call/TransformerPolicy/block_2/mixer/ssm_decode_update/mul", "loop fusion",
     "block_N/mixer/ssm_decode_update/mul", "attention"),
    ("jit(decode)/while/body/closed_call/TransformerPolicy/block_2/attn_hc.read/attn_hc.maps/mhc_maps/exp", "loop fusion",
     "block_N/attn_hc.read/attn_hc.maps/mhc_maps/exp", "attention"),
    ("jit(decode)/while/body/closed_call/TransformerPolicy/block_2/ffn_norm_1/mul", "loop fusion",
     "block_N/ffn_norm_1/mul", "ffn"),
    ("jit(decode)/while/body/closed_call/TransformerPolicy/block_2/router/zaya_router/score/dot_general", "convolution fusion",
     "block_N/router/zaya_router/score/dot_general", "ffn"),
    ("jit(decode)/while/body/closed_call/sample/jit(_gumbel)/jit(_uniform)/xor", "loop fusion", "sample/xor", "head_sampler"),
    ("jit(decode)/while/body/closed_call/sample/jit(take_along_axis)", "loop fusion", "sample", "head_sampler"),
    ("jit(decode)/while/body/closed_call/jit(_where)/select_n", "loop fusion", "(unnamed) decode", "unnamed"),
    ("jit(decode)/while/body/closed_call/TransformerPolicy/policy_head/dot_general", "convolution fusion",
     "policy_head/dot_general", "head_sampler"),
    ("jit(decode)/while/body/closed_call/TransformerPolicy/token_embed/jit(_take)/gather", "loop fusion",
     "token_embed/gather", "embed"),
    ("jit(decode)/while/body/closed_call/TransformerPolicy/block_0/bhd,bshd->bhs/dot_general", "convolution fusion",
     "(unnamed) decode", "unnamed"),
    ("jit(decode)/while/body/closed_call/jit(log_softmax)/reduce_max", "loop fusion", "(unnamed) decode", "unnamed"),
    ("jit(decode)/while/body/closed_call/TransformerPolicy/add", "loop fusion", "(unnamed) decode", "unnamed"),
    ("jit(decode)/while/body/dynamic_update_slice", "data formatting", "(unnamed) decode", "unnamed"),
    ("jit(decode)/while/body/closed_call/jit(_threefry_split)/ContinuousEngine._build_decode.<locals>.substep/xor",
     "loop fusion", "(unnamed) decode", "unnamed"),
    (None, "copy-start", "(compiler) copy-start", "compiler"),
    ("", "data formatting", "(compiler) data formatting", "compiler"),
    ("jit(learn)/loss/jvp(TransformerPolicy)/block_3/mlp_in/dot_general", "convolution fusion",
     "fwd/loss/block_N/mlp_in/dot_general", "ffn"),
    ("jit(learn)/loss/transpose(jvp(TransformerPolicy))/block_3/mlp_in/dot_general", "convolution fusion",
     "bwd/loss/block_N/mlp_in/dot_general", "ffn"),
    ("jit(learn)/loss/jvp()/mul", "loop fusion", "fwd/loss/mul", "loss"),
    ("jit(learn)/loss/transpose(loss)/jvp(TransformerPolicy)/block_5/attend/segment_flash_bwd_dkv/pallas_call", "custom-call",
     "bwd/loss/block_N/attend/segment_flash_bwd_dkv/pallas_call", "attention"),
    ("jit(learn)/loss/transpose(jvp())/add_any", "loop fusion", "bwd/loss/add_any", "loss"),
    ("jit(learn)/loss/jvp(jit(clip))/max", "loop fusion", "fwd/loss/max", "loss"),
    ("jit(learn)/update/mul", "loop fusion", "update/mul", "update"),
    ("jit(learn)/sqrt", "loop fusion", "(unnamed) learn", "unnamed"),
    ("jit(learn)/guard/jit(_where)/select_n", "loop fusion", "guard/select_n", "update"),
    ("jit(_train_many_impl)/while/body/closed_call/learn/transpose(jvp(AtariNet))/Conv_0/conv_general_dilated",
     "convolution fusion", "bwd/learn/Conv_0/conv_general_dilated", "learn"),
    ("jit(_train_many_impl)/while/body/closed_call/while/body/closed_call/act/AtariNet/policy_head/dot_general",
     "convolution fusion", "act/policy_head/dot_general", "head_sampler"),
    ("jit(_train_many_impl)/while/body/closed_call/while/body/closed_call/env_step/jit(_where)/select_n",
     "loop fusion", "env_step/select_n", "env"),
])
def test_a_scope_is_the_op_name_less_jaxs_own_wrappers(op_name, category, scope, kind):
    program = (op_name or "jit(decode)").split("/", 1)[0][4:-1]
    got, tail = ops.scope_of(op_name, category, program)
    assert got == scope and ops.class_of(got) == kind
    if kind == "unnamed":  # what is left says what the unnamed code does
        assert tail and not tail.startswith("jit(")
    else:
        assert tail == ""


def test_under_looks_at_the_scopes_own_names():
    assert ops.under("act/policy_head/dot_general", "act")
    assert ops.under("bwd/learn/Conv_0/conv_general_dilated", "learn")
    assert not ops.under("act/policy_head/dot_general", "learn")
    assert not ops.under("(unnamed) learn", "learn")
    assert ops.direction_of("bwd/learn/Conv_0") == "bwd" and ops.direction_of("learn/bwd") == ""


def test_the_command_line_prints_the_table(capsys):
    assert ops.main([str(FIXTURE)]) == 0
    out = capsys.readouterr().out
    assert "jit_toy_step | (unnamed) toy_step" in out and "block_7/dot_general @" in out
    assert "jit_toy_step | block_N/toy_fwd/pallas_call" in out
    assert ops.main([str(FIXTURE), "1"]) == 0
    assert "jit_toy_step | block_N " in capsys.readouterr().out
    # and program_trace's command line loads the new metric files by name
    import program_trace

    assert program_trace.main([str(FIXTURE), "rollout_unnamed_time_share", "learn_update_time_share"]) == 0
    out = capsys.readouterr().out
    assert "rollout_unnamed_time_share = 95.84" in out and "learn_update_time_share = 0.0 %" in out


def test_no_tensorflow_in_the_run_path():
    """The reader is a few lines of wire format: nothing of TensorFlow or
    protobuf is imported to read a trace."""
    import subprocess

    code = (
        "import sys; sys.path.insert(0, %r); import op_scopes; op_scopes.read(%r); "
        "bad = [m for m in sys.modules if m.split('.')[0] in ('tensorflow', 'tsl', 'google.protobuf')]; "
        "assert not bad, bad" % (str(BENCH), str(FIXTURE))
    )
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
