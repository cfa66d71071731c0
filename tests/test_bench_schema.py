"""Schema of the lines ``bench.py`` prints, checked at toy shapes on the CPU.

Each test runs one ``_run_*_measurement`` function in-process (or, for the
slow sharded case, ``bench.py --cpu`` in a subprocess) and checks the JSON
line's fields — including the device stamp: a CPU run says ``"platform":
"cpu"`` and carries no FLOP/s or MFU field, which are device metrics.
"""

import json
import sys

import pytest
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))


def _assert_cpu_stamp(result):
    assert result["platform"] == "cpu"
    assert result["device_kind"] and result["device_count"] >= 1
    assert "mfu" not in result and "achieved_tflops_per_s" not in result


@pytest.mark.slow  # ~22 s in-process bench; test_genrl_bench_artifact_schema keeps the
# schema/gate machinery tier-1-covered (ISSUE 19 tier-1 budget buy-back)
def test_sharded_bench_artifact_schema():
    """bench --mode sharded artifacts carry the like-for-like comparison
    keys the gate needs: mode, mesh, params_total, params_per_chip."""
    import re
    import subprocess
    import sys as _sys

    env = dict(
        __import__("os").environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
    )
    out = subprocess.run(
        [_sys.executable, str(REPO / "bench.py"), "--cpu",
         "--mode", "sharded"],
        env=env, capture_output=True, text=True, timeout=500, cwd=str(REPO),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    line = [
        l for l in out.stdout.splitlines()
        if l.strip().startswith("{") and l.strip().endswith("}")
    ][-1]
    result = json.loads(line)
    assert result["metric"] == "sharded_train_step_frames_per_sec"
    assert result["mode"] == "sharded"
    assert re.fullmatch(r"dp=\d+(,mp=\d+)?", result["mesh"])
    assert result["params_total"] > result["params_per_chip"] > 0
    assert result["value"] > 0


def test_serving_bench_artifact_schema(capsys, monkeypatch):
    """bench --mode serving artifacts carry the SLO fields the docs table
    promises (p50/p95/p99, occupancy) and the like-for-like gate keys
    (metric + mode) so serving history only gates serving runs.  Runs
    in-process at a shrunken window (the genrl schema-test shape) — a
    subprocess would pay a whole fresh jax import for the same assert."""
    import importlib.util

    monkeypatch.setenv("BENCH_SERVING_TARGET_S", "1.0")
    spec = importlib.util.spec_from_file_location(
        "bench_serving_mod", REPO / "bench.py"
    )
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    bench._run_serving_measurement()
    lines = [
        l for l in capsys.readouterr().out.splitlines()
        if l.strip().startswith("{") and l.strip().endswith("}")
    ]
    result = json.loads(lines[-1])
    assert result["metric"] == "serving_requests_per_sec"
    assert result["mode"] == "serving"
    assert result["value"] > 0
    assert result["lane_steps_per_sec"] >= result["value"]
    assert result["p99_ms"] >= result["p95_ms"] >= result["p50_ms"] > 0
    assert 0.0 < result["batch_occupancy"] <= 1.0
    assert result["flushes"] > 0
    _assert_cpu_stamp(result)


def test_traffic_bench_artifact_schema(capsys, monkeypatch):
    """bench --mode traffic artifacts carry the goodput-under-SLO verdict
    line the gate reads: metric + mode for like-for-like history, the SLO
    quantiles, and the router's exact-accounting verdict
    (accounting_balanced — the chaos e2e's equation, re-checked on every
    bench round).  In-process at a shrunken window, like the serving twin."""
    import importlib.util

    monkeypatch.setenv("BENCH_TRAFFIC_TARGET_S", "1.0")
    monkeypatch.setenv("BENCH_TRAFFIC_REPLICAS", "2")
    monkeypatch.setenv("BENCH_TRAFFIC_CLIENTS", "2")
    monkeypatch.setenv("BENCH_TRAFFIC_RPS", "30")
    spec = importlib.util.spec_from_file_location(
        "bench_traffic_mod", REPO / "bench.py"
    )
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    bench._run_traffic_measurement()
    lines = [
        l for l in capsys.readouterr().out.splitlines()
        if l.strip().startswith("{") and l.strip().endswith("}")
    ]
    result = json.loads(lines[-1])
    assert result["metric"] == "traffic_goodput_rps"
    assert result["mode"] == "traffic"
    assert result["value"] > 0
    assert result["offered_rps"] >= result["value"]
    assert result["answered"] >= result["good"] > 0
    assert result["p99_ms"] >= result["p95_ms"] >= result["p50_ms"] > 0
    assert result["slo_ms"] > 0
    assert result["accounting_balanced"] is True
    assert result["n_replicas"] == 2
    _assert_cpu_stamp(result)


def test_genrl_bench_artifact_schema(capsys, monkeypatch):
    """bench --mode genrl artifacts carry the three headline numbers
    (prefill/decode tokens/s + learn steps/s) and the like-for-like gate
    keys (metric + mode) so genrl history only gates genrl runs.  Runs the
    measurement in-process (CPU shapes are tiny) — no subprocess jax
    import on the tier-1 clock."""
    import importlib.util

    monkeypatch.setenv("BENCH_LEARN_TARGET_S", "0.2")
    # shrink the speculative A/B (ISSUE 16) to schema-test scale: short
    # responses + tiny draft window keep the verify-ladder compiles small
    monkeypatch.setenv("BENCH_SPEC_TARGET_S", "0.2")
    monkeypatch.setenv("BENCH_SPEC_RESPONSE", "8")
    monkeypatch.setenv("BENCH_SPEC_K", "1")
    spec = importlib.util.spec_from_file_location(
        "bench_genrl_mod", REPO / "bench.py"
    )
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    bench._run_genrl_measurement()
    lines = [
        l for l in capsys.readouterr().out.splitlines()
        if l.strip().startswith("{") and l.strip().endswith("}")
    ]
    result = json.loads(lines[-1])
    assert result["metric"] == "genrl_decode_tokens_per_sec_per_chip"
    assert result["mode"] == "genrl"
    assert result["value"] > 0
    assert result["value"] == result["decode_tokens_per_sec"]
    assert result["prefill_tokens_per_sec"] > 0
    assert result["learn_steps_per_sec"] > 0
    assert result["prompt_bucket"] > 0 and result["response_bucket"] > 0
    assert result["iter_mode"] in ("scan", "unroll")
    # packed-learner A/B fields (ISSUE 15): the gated packed rate, its
    # padded twin, and the pad economics that explain the gap
    assert result["token_ppo_learn_tokens_per_sec_per_chip"] > 0
    assert result["padded_learn_tokens_per_sec"] > 0
    assert result["learn_speedup_vs_padded"] > 0
    assert 0.0 < result["learn_pad_ratio"] < 1.0
    assert 0.0 <= result["learn_packed_pad_ratio"] < result["learn_pad_ratio"]
    assert 0 < result["learn_packed_rows"] <= result["learn_batch_sequences"]
    assert result["learn_pack_len"] > 0
    # speculative-decode A/B fields (ISSUE 16): the gated spec-on rate,
    # its spec-off twin at the same shape, and the acceptance economics
    # behind the ratio (>1x only at production response budgets — the
    # schema-test budget is ramp-dominated by design)
    assert result["genrl_spec_accepted_tokens_per_sec"] > 0
    assert result["spec_off_tokens_per_sec"] > 0
    assert result["spec_speedup"] > 0
    assert 0.0 <= result["spec_acceptance_rate"] <= 1.0
    assert result["spec_k"] == 1
    assert result["spec_response_budget"] == 8
    assert result["spec_rollback_pages"] >= 0
    _assert_cpu_stamp(result)


@pytest.mark.slow  # ~28 s in-process bench; schema machinery tier-1-covered by
# test_genrl_bench_artifact_schema (ISSUE 19 tier-1 budget buy-back)
def test_genrl_continuous_bench_artifact_schema(capsys, monkeypatch):
    """bench --mode genrl --continuous artifacts carry the like-for-like
    acceptance comparison (cohort rate + speedup in the SAME artifact) and
    the continuous-plane observables (lane occupancy, admission latency,
    page geometry), under their own gate mode ("genrl-continuous") so
    continuous history never gates fixed-cohort runs.  Runs in-process at
    a shrunken window/lane count — the full CPU shape is ``bench.py --cpu --mode genrl
    --continuous``."""
    import importlib.util

    monkeypatch.setenv("BENCH_GENRL_TARGET_S", "0.3")
    monkeypatch.setenv("BENCH_GENRL_LANES", "8")
    monkeypatch.setenv("BENCH_GENRL_RESPONSE", "16")
    monkeypatch.setenv("BENCH_LEARN_TARGET_S", "0.2")
    spec = importlib.util.spec_from_file_location(
        "bench_genrl_cont_mod", REPO / "bench.py"
    )
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    bench._run_genrl_continuous_measurement()
    lines = [
        l for l in capsys.readouterr().out.splitlines()
        if l.strip().startswith("{") and l.strip().endswith("}")
    ]
    result = json.loads(lines[-1])
    assert result["metric"] == "genrl_decode_tokens_per_sec_per_chip"
    assert result["mode"] == "genrl-continuous"
    assert result["value"] > 0
    assert result["value"] == result["decode_tokens_per_sec"]
    assert result["cohort_decode_tokens_per_sec"] > 0
    assert result["speedup_vs_cohort"] >= 0
    assert 0.0 <= result["lane_occupancy_mean"] <= 1.0
    assert result["admission_latency_p50_ms"] >= 0
    assert result["admission_latency_p95_ms"] >= (
        result["admission_latency_p50_ms"]
    )
    # the real tail quantile rides the artifact (ISSUE 13 satellite)
    assert result["admission_latency_p99_ms"] >= (
        result["admission_latency_p95_ms"]
    )
    assert result["lanes"] > 0 and result["page_size"] > 0
    assert result["pages_capacity"] > 0
    assert result["completed_sequences"] >= 2
    assert result["iter_mode"] in ("scan", "unroll")
    # shared-prefix reuse observables (ISSUE 14) ride every artifact; the
    # ungrouped workload carries NO group key (its own gate history)
    assert 0.0 <= result["prefill_tokens_saved_ratio"] <= 1.0
    assert 0.0 <= result["prefix_hit_rate"] <= 1.0
    assert result["steps_in_flight"] >= 1
    assert "group" not in result
    # packed-learner fields (ISSUE 15) ride the continuous artifact too
    assert result["token_ppo_learn_tokens_per_sec_per_chip"] > 0
    assert 0.0 < result["learn_pad_ratio"] < 1.0


@pytest.mark.slow  # ~14 s in-process bench; same buy-back as the continuous schema test
def test_genrl_continuous_group_bench_artifact_schema(capsys, monkeypatch):
    """The BENCH_GENRL_GROUP shape (ISSUE 14): every arrival fans into
    n=4 lanes via submit_group, the artifact carries group=n for the
    like-for-like gate, and the prefill-savings ratio clears the
    full-page acceptance bar ((n-1)/n of full-page prefix tokens)."""
    import importlib.util

    monkeypatch.setenv("BENCH_GENRL_TARGET_S", "0.3")
    monkeypatch.setenv("BENCH_GENRL_LANES", "8")
    monkeypatch.setenv("BENCH_GENRL_RESPONSE", "8")
    monkeypatch.setenv("BENCH_GENRL_GROUP", "4")
    # the learn A/B fields are asserted by the ungrouped schema tests;
    # this one exercises the GROUP decode shape only
    monkeypatch.setenv("BENCH_SKIP_LEARN_AB", "1")
    spec = importlib.util.spec_from_file_location(
        "bench_genrl_group_mod", REPO / "bench.py"
    )
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    bench._run_genrl_continuous_measurement()
    lines = [
        l for l in capsys.readouterr().out.splitlines()
        if l.strip().startswith("{") and l.strip().endswith("}")
    ]
    result = json.loads(lines[-1])
    assert result["mode"] == "genrl-continuous"
    assert result["group"] == 4
    assert result["value"] > 0
    # group fan-out alone guarantees (n-1)/n of full-page prefix tokens
    # are shared CoW; cross-round cache hits only add to it
    assert result["prefill_tokens_saved_ratio"] >= 0.75
    assert result["prefix_hit_rate"] >= 0.0


@pytest.mark.slow  # ~17 s in-process bench; schema/gate machinery tier-1-covered by
# test_genrl_bench_artifact_schema (ISSUE 19 tier-1 budget buy-back)
def test_disagg_bench_artifact_schema(capsys, monkeypatch):
    """bench --mode disagg artifacts carry the disaggregated-dataflow
    headline (end-to-end sequences/s through the wire) plus the
    snapshot-push numbers (publish->adoption latency, int8 wire bytes),
    under their own gate mode so disagg history only gates disagg runs.
    Runs in-process with a shrunken window — the full CPU shape is
    ``bench.py --cpu --mode disagg``."""
    import importlib.util

    monkeypatch.setenv("BENCH_DISAGG_TARGET_S", "1.0")
    spec = importlib.util.spec_from_file_location(
        "bench_disagg_mod", REPO / "bench.py"
    )
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    bench._run_disagg_measurement()
    lines = [
        l for l in capsys.readouterr().out.splitlines()
        if l.strip().startswith("{") and l.strip().endswith("}")
    ]
    result = json.loads(lines[-1])
    assert result["metric"] == "disagg_sequences_per_sec"
    assert result["mode"] == "disagg"
    assert result["value"] > 0
    assert result["value"] == result["sequences_per_sec"]
    assert result["hosts"] == 2 and result["lanes_per_host"] > 0
    assert result["snapshot_wire_bytes"] > 0
    assert result["snapshot_quantize_ms"] >= 0
    if result["snapshot_pushes"]:
        assert result["snapshot_push_latency_ms_p50"] > 0
        # real percentiles over every sample, ordered p50 <= p95 <= p99
        # <= max — the max no longer stands in for a tail quantile
        assert result["snapshot_push_latency_ms_p95"] >= (
            result["snapshot_push_latency_ms_p50"]
        )
        assert result["snapshot_push_latency_ms_p99"] >= (
            result["snapshot_push_latency_ms_p95"]
        )
        assert result["snapshot_push_latency_ms_max"] >= (
            result["snapshot_push_latency_ms_p99"]
        )
    assert result["accepted_sequences"] >= 2
    _assert_cpu_stamp(result)
