"""The reduction from a profiler trace to numbers, checked on one small
trace recorded on the chip (``benchmark/fixtures/small.xplane.pb``, from
``benchmark/tools/record_fixture.py``), against numbers worked out by hand
from that trace's own text dump; and ``work.py`` against the program's
real parameter counts; and the plain optimiser step against the program's
own optimiser."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[2] / "benchmark"
sys.path.insert(0, str(BENCH))

import trace_reduce as tr  # noqa: E402
import work  # noqa: E402

FIXTURE = BENCH / "fixtures" / "small.xplane.pb"


def test_interval_arithmetic():
    merged = tr.union([(0, 10), (5, 12), (20, 30), (30, 31), (40, 41), (3, 3)])
    assert merged.tolist() == [[0, 12], [20, 31], [40, 41]]
    assert tr.total(merged) == 24
    assert tr.complement(merged, -5, 50).tolist() == [[-5, 0], [12, 20], [31, 40], [41, 50]]
    assert tr.clip(merged, 10, 25).tolist() == [[10, 12], [20, 25]]
    assert tr.subtract(tr.union([(0, 100)]), merged).tolist() == [[12, 20], [31, 40], [41, 100]]
    assert tr.intersect(merged, tr.union([(11, 21), (40.5, 60)])).tolist() == [
        [11, 12], [20, 21], [40.5, 41],
    ]


def test_idle_time_goes_to_the_innermost_open_span():
    spans = [("a", 0, 50), ("b", 10, 20), ("c", 15, 18), ("d", 60, 70)]
    assert tr.owner_timeline(spans) == [
        (0, 10, "a"), (10, 15, "b"), (15, 18, "c"), (18, 20, "b"), (20, 50, "a"), (60, 70, "d"),
    ]
    charged = tr.charge_gaps(np.array([[5.0, 25.0], [55.0, 65.0]]), spans)
    assert charged == pytest.approx(
        {"a": 10e-9, "b": 7e-9, "c": 3e-9, "d": 5e-9, "(no bench span open)": 5e-9}
    )


def test_hlo_instruction_names_are_shortened():
    assert tr.short_name(
        "%copy-start = (f32[2048,2048]{1,0:T(8,128)S(1)}, f32[2048,2048]{1,0:T(8,128)}, "
        "u32[]{:S(2)}) copy-start(f32[2048,2048]{1,0:T(8,128)} %x.1), cross_program_prefetch_index=0"
    ) == ("%copy-start", "copy-start", "f32[2048,2048]")
    mosaic = (
        '%mosaic_add_one.1 = f32[512,512]{1,0:T(8,128)} custom-call(f32[512,512]{1,0:T(8,128)} '
        '%x.1), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[512,512]{1,0}}'
    )
    assert tr.short_name(mosaic) == ("%mosaic_add_one.1", "custom-call", "f32[512,512]")
    assert tr._is_mosaic(mosaic) and not tr._is_mosaic("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x)")
    assert tr.short_name("not an instruction") == ("not an instruction", "", "")


def test_gpt2_parameter_count_is_the_programs_own():
    """``work.gpt2_params`` against the model the program builds."""
    import jax
    import jax.numpy as jnp
    from scalerl_tpu.models.transformer import TransformerPolicy

    cfg = {"n_embd": 32, "n_layer": 2, "n_head": 4, "n_positions": 24, "vocab_size": 50, "n_inner": None}
    model = TransformerPolicy(
        num_actions=cfg["vocab_size"], vocab_size=cfg["vocab_size"], d_model=cfg["n_embd"],
        num_heads=cfg["n_head"], num_layers=cfg["n_layer"], max_len=cfg["n_positions"],
    )
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32))
    count = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert work.gpt2_params(cfg) == count
    for name, millions in (("gpt2-medium", 406.19), ("gpt2-large", 838.13)):
        published = json.loads((BENCH / "configs" / f"{name}.json").read_text())
        assert work.gpt2_params(published) / 1e6 == pytest.approx(millions, abs=0.05)


def test_work_from_shapes():
    medium = json.loads((BENCH / "configs" / "gpt2-medium.json").read_text())
    assert work.gpt2_matmul_params(medium) == 24 * 12 * 1024 * 1024 + 1024 * 50257
    assert work.gpt2_kv_bytes_per_token(medium, 4) == 196608  # 196 KB in float32
    assert work.mean_attended_keys([4]) == 2.5 and work.mean_attended_keys([]) == 0.0
    assert work.decode_kv_tokens_read(10, 3) == 10 + 1 + 10 + 2 + 10 + 3
    atari = json.loads((BENCH / "configs" / "impala-atarinet.json").read_text())
    conv = 2 * (21 * 21 * 32 * 8 * 8 * 4 + 11 * 11 * 64 * 4 * 4 * 32 + 11 * 11 * 64 * 3 * 3 * 64)
    dense = 2 * (11 * 11 * 64 * 512 + 519 * 7)
    assert work.atarinet_forward_flops_per_frame(atari) == conv + dense
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
    assert work.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_reduction_of_the_recorded_trace_matches_the_hand_worked_numbers():
    """``fixtures/small.dump.txt`` is the trace as text.  By hand from it:

    The device ran two ``jit_matmuls`` (copy-start 13 ns, copy-done, three
    fusions) and two Mosaic ``x + 1`` calls.  Their durations add to
    13+23073+89708+89996+92202 = 294,992 ns, 3,858 ns,
    13+22962+89710+89997+92239 = 294,921 ns and 3,511 ns, and no two of the
    twelve operations overlap, so the device was busy 597,282 ns.

    The four modules start 1,286,589 / 1,263,997 / 1,274,042 / 1,297,585 ns
    *before* the host's ``DoEnqueueProgram`` of the same ``run_id`` began,
    so the device's clock is shifted by the largest, 1,297,585 ns.

    ``bench.window`` lasts 11,030,289 ns.  After the shift the first
    matmuls fall inside ``bench.alpha`` (1,254,529 ns long: 959,537 idle),
    nothing runs during ``bench.sleep`` (4,624,269 ns, all idle), the first
    Mosaic call and the second matmuls fall inside ``bench.beta``
    (1,874,910 - 3,858 - 294,921 = 1,576,131 idle), the second Mosaic call
    inside ``bench.gamma`` (636,940 - 3,511 = 633,429 idle), and the rest of
    the window, 11,030,289 - 8,390,648 = 2,639,641 ns, has no span open.
    """
    assert FIXTURE.stat().st_size < 1 << 20
    r = tr.reduce_trace(str(FIXTURE))
    ns = 1e-9
    assert r["devices"] == 1
    assert r["clock_shift_s"] == pytest.approx(1_297_585 * ns)
    assert r["window_s"] == pytest.approx(11_030_289 * ns)
    assert r["busy_s"] == pytest.approx(597_282 * ns)
    assert r["idle_share"] == pytest.approx(1 - 597_282 / 11_030_289)
    assert r["mosaic_s"] == pytest.approx(7_369 * ns)
    assert r["collective_s"] == 0.0 and r["collective_exposed_s"] == 0.0
    assert [name for name, _s in r["device_ops"]] == [
        "%fusion.N fusion bf16[2048,2048]",
        "%fusion fusion f32[2048,2048]",
        "%copy-done copy-done f32[2048,2048]",
        "%mosaic_add_one.N custom-call f32[512,512]",
        "%copy-start copy-start f32[2048,2048]",
    ]
    assert [s for _n, s in r["device_ops"]] == pytest.approx(
        [359_411 * ns, 184_441 * ns, 46_035 * ns, 7_369 * ns, 26 * ns]
    )
    assert dict(r["idle_gaps"]) == pytest.approx({
        "bench.sleep": 4_624_269 * ns,
        "(no bench span open)": 2_639_641 * ns,
        "bench.beta": 1_576_131 * ns,
        "bench.alpha": 959_537 * ns,
        "bench.gamma": 633_429 * ns,
    })
    assert [n for n, _s in r["idle_gaps"]][:2] == ["bench.sleep", "(no bench span open)"]


def test_a_loops_time_is_its_bodys():
    ev = lambda name, a, b: tr.Event(name, a, b, False, False)  # noqa: E731
    events = [ev("while", 0, 100), ev("a", 10, 30), ev("cond", 40, 90), ev("b", 50, 70), ev("a", 100, 110)]
    assert tr.self_times(events, 0, 200) == pytest.approx(
        {"while": 30e-9, "a": 30e-9, "cond": 30e-9, "b": 20e-9}
    )
    assert tr.self_times(events, 20, 60) == pytest.approx(
        {"while": 10e-9, "a": 10e-9, "cond": 10e-9, "b": 10e-9}
    )


def test_a_loop_neither_hides_a_collective_nor_fills_its_own_gaps():
    """By hand.  A ``while`` runs from 0 to 100 around a fusion (10-30), an
    all-reduce (30-60), a second fusion that overlaps its end (50-70) and
    nothing from 70 on; a second all-reduce (110-130) runs alone; the
    window is 0-150.  The operations cover 10-70 and 110-130: busy 80, not
    the 120 that the loop's own interval would make it.  The collectives
    last 30 + 20 = 50; other operations run beside them only from 50 to 60,
    so 30-50 and 110-130 are exposed: 40, not the 20 left if the loop
    counted as an operation beside the first."""
    ev = lambda name, a, b, coll=False: tr.Event(name, a, b, False, coll)  # noqa: E731
    events = [
        ev("while", 0, 100), ev("fusion.a", 10, 30), ev("all-reduce", 30, 60, True),
        ev("fusion.b", 50, 70), ev("all-reduce", 110, 130, True),
    ]
    t = tr.device_times(events, 0, 150)
    assert (t["busy"], t["collective"], t["collective_exposed"], t["mosaic"]) == (80, 50, 40, 0)
    assert t["merged"].tolist() == [[10, 70], [110, 130]]
    assert t["containers"].tolist() == [[0, 100]]
    # clipped to a window that opens inside the loop and the collective
    t = tr.device_times(events, 40, 120)
    assert (t["busy"], t["collective"], t["collective_exposed"]) == (40, 30, 20)
    # the loop's own 40 (0-10, 70-100) stay under its name in the breakdown
    assert tr.self_times(events, 0, 150)["while"] == pytest.approx(40e-9)
    # an operation that only overlaps another is no container
    overlap = [ev("a", 0, 10), ev("b", 5, 15)]
    assert tr.device_times(overlap, 0, 20)["busy"] == 15
    assert tr.device_times(overlap, 0, 20)["containers"].tolist() == []


def _plain_token_ppo():
    import importlib.util

    spec = importlib.util.spec_from_file_location("plain", BENCH / "reference" / "token_ppo.py")
    plain = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(plain)
    return plain


def test_the_plain_first_update_is_the_programs_optimisers_first_step():
    """``reference/token_ppo.first_update`` against ``TokenPPOAgent``'s own
    optimiser (global-norm clip, then Adam) on a gradient above the clip
    norm and on one below it."""
    from types import SimpleNamespace

    import jax.numpy as jnp
    from scalerl_tpu.agents.token_ppo import TokenPPOAgent

    plain = _plain_token_ppo()
    hyper = {"learning_rate": 3e-3, "max_grad_norm": 1.0}
    tx = TokenPPOAgent._make_optimizer(SimpleNamespace(**hyper))
    rng = np.random.default_rng(0)
    for size in (3.0, 1e-3):
        grads = {"a": jnp.asarray(size * rng.normal(size=(5, 7)), jnp.float32),
                 "b": jnp.asarray(size * rng.normal(size=11), jnp.float32)}
        updates, _ = tx.update(grads, tx.init(grads), grads)
        norm = float(np.sqrt(sum(np.sum(np.square(g)) for g in grads.values())))
        for k in grads:
            want = plain.first_update(grads[k], min(1.0, 1.0 / norm), hyper)
            assert np.allclose(updates[k], want, rtol=1e-5, atol=1e-9)


def test_following_a_step_tells_a_right_update_from_a_wrong_one():
    """``follow`` on a two-matrix bigram model: the reference's own update
    reads 1 and 1; twice the step reads 2 and 2; an update that leaves one
    of the two matrices where it was reads short in both."""
    import jax
    import jax.numpy as jnp

    plain = _plain_token_ppo()
    rng = np.random.default_rng(1)
    V, T = 9, 12
    before = {"logit": jnp.asarray(rng.normal(size=(V, V)), jnp.float32),
              "value": jnp.asarray(rng.normal(size=(V,)), jnp.float32)}
    forward = lambda w, t: (w["logit"][t], w["value"][t])  # noqa: E731
    seq = {"tokens": jnp.asarray(rng.integers(0, V, T), jnp.int32),
           "mask": jnp.asarray(np.arange(T) >= 4, jnp.float32),
           "behavior_logp": jnp.asarray(-np.log(V) + 0.1 * rng.normal(size=T), jnp.float32),
           "value": jnp.asarray(0.1 * rng.normal(size=T), jnp.float32),
           "reward": jnp.asarray(rng.random(T), jnp.float32)}
    hyper = {"clip_range": 0.2, "value_cost": 0.5, "entropy_cost": 0.01, "kl_cost": 0.0,
             "adv_norm": True, "learning_rate": 3e-3, "max_grad_norm": 1.0}
    grads = jax.grad(lambda w: plain.loss(w, w, seq, forward, hyper)[0])(before)
    norm = float(np.sqrt(sum(np.sum(np.square(g)) for g in grads.values())))
    step = {k: plain.first_update(g, min(1.0, 1.0 / norm), hyper) for k, g in grads.items()}

    def read(after):
        out = plain.follow(before, after, seq, forward, hyper)
        assert out["grad_norm"] == pytest.approx(norm, rel=1e-5)
        return out["update_gain"], out["update_norm_ratio"]

    assert read({k: before[k] + step[k] for k in before}) == pytest.approx((1.0, 1.0), rel=1e-5)
    assert read({k: before[k] + 2 * step[k] for k in before}) == pytest.approx((2.0, 2.0), rel=1e-5)
    gain, length = read({"logit": before["logit"] + step["logit"], "value": before["value"]})
    assert gain < 0.99 and length < 0.99
