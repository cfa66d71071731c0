"""The program's live spans (``runtime/tracing.span``) on the three hot
paths: what the ring holds, with sampling at 1, after a few engine cycles,
one learn step and one training round; and that the profiler half of a
span is installed by importing the modules that open them."""

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax

from scalerl_tpu.config import GenRLArguments
from scalerl_tpu.data.sequence_replay import seq_sample
from scalerl_tpu.runtime import telemetry, tracing
from scalerl_tpu.trainer.sequence_rl import SequenceRLTrainer

ROOT = Path(__file__).resolve().parent.parent
PHASES = [  # host packing before and after the score: two ``round.pack`` a round
    "round.generate", "round.pack", "round.score", "round.pack", "round.seq_add",
    "round.sample", "round.learn", "round.push",
]


@pytest.fixture(scope="module")
def recorded():
    """One tiny trainer under ``SCALERL_TRACE_SAMPLE=1``: the records of
    one round, then of three engine cycles, then of one learn step."""
    old = os.environ.get(tracing.ENV_SAMPLE)
    os.environ[tracing.ENV_SAMPLE] = "1.0"
    os.environ.pop(tracing.ENV_DIR, None)
    telemetry.reset()
    tracing.reset()
    try:
        trainer = SequenceRLTrainer(GenRLArguments(
            seed=3, vocab_size=8, prompt_len=4, max_new_tokens=4, d_model=32,
            n_layers=1, n_heads=2, genrl_batch=8, genrl_sample_batch=8,
            genrl_buffer_sequences=16, telemetry_interval_s=0.0,
            logger_backend="none", samples_per_prompt=4,
            genrl_engine="continuous", genrl_lanes=8, genrl_page_size=2,
            genrl_macro_steps=2,
        ))
        tracer = tracing.get_tracer()
        before = tracing.span_totals()
        trainer.train_round()
        out = {"round": tracer.finished(), "totals": (before, tracing.span_totals())}
        tracer.clear()
        prompts, lengths = trainer.task.sample_prompts(2, np.random.default_rng(0))
        for i in range(2):
            trainer.engine.submit_group(prompts[i], 4, lengths[i])
        for _ in range(3):
            trainer.engine.step()
        out["engine"] = tracer.finished()
        tracer.clear()
        batch, _core, _idx, weights = seq_sample(
            trainer.replay, jax.random.PRNGKey(0), 8, method=trainer._seq_method
        )
        trainer.agent.learn({**batch, "is_weight": weights})
        out["learn"] = tracer.finished()
        assert tracer.current_span() is None  # every span that opened closed
    finally:
        if old is None:
            os.environ.pop(tracing.ENV_SAMPLE, None)
        else:
            os.environ[tracing.ENV_SAMPLE] = old
        telemetry.reset()
        tracing.reset()
    return out


def _children(records, parent):
    return [r for r in records if r["parent"] == parent["span"]]


def _named(records, name):
    return [r for r in records if r["name"] == name]


def test_engine_cycles_record_their_phases(recorded):
    steps = _named(recorded["engine"], "genrl.macro_step")
    assert len(steps) == 3 and all(s["parent"] is None for s in steps)
    assert len({s["trace"] for s in steps}) == 3  # each cycle is a root
    for step in steps:
        names = [c["name"] for c in _children(recorded["engine"], step)]
        assert names.count("genrl.admit") == 1 and names.count("genrl.dispatch") == 1
        assert set(names) <= {"genrl.admit", "genrl.dispatch", "genrl.read", "genrl.harvest"}
        assert names.count("genrl.read") == names.count("genrl.harvest")
        assert set(step["attrs"]) == {"completed", "live_lanes", "occupancy", "in_flight"}
    assert _named(recorded["engine"], "genrl.read")  # a cycle in steady state reads once
    assert {r["name"] for r in recorded["engine"]} == {
        "genrl.macro_step", "genrl.admit", "genrl.dispatch", "genrl.read", "genrl.harvest",
    }


def test_a_learn_step_records_its_dispatch_and_its_one_read(recorded):
    records = recorded["learn"]
    (step,) = _named(records, "learn.step")
    assert step["parent"] is None
    assert [c["name"] for c in _children(records, step)] == ["learn.dispatch", "dispatch.read"]
    assert len(records) == 3
    dispatch, read = _children(records, step)
    assert dispatch["t0"] + dispatch["dur"] <= read["t0"] + 1e-6


def test_a_round_records_its_phases_that_do_not_overlap_and_cover_the_push(recorded):
    records = recorded["round"]
    (root,) = _named(records, "genrl.round")
    assert root["parent"] is None and root["attrs"]["step"] == 1
    assert root["attrs"]["staleness"] == 1.0  # the bookkeeping is inside the root
    phases = _children(records, root)
    assert [p["name"] for p in phases] == PHASES  # in the order they ran
    end = root["t0"]
    for phase in phases:
        assert phase["t0"] >= end - 1e-6, phase["name"]  # starts after the last one ended
        end = phase["t0"] + phase["dur"]
    assert end <= root["t0"] + root["dur"] + 1e-6  # the push is inside the root
    assert {r["trace"] for r in records} == {root["trace"]}  # one trace: nothing made a root of its own
    # the engine's cycles hang under the generate phase, the learner's step
    # under the learn phase, the snapshot placement under the push
    by_name = {p["name"]: p for p in phases}
    generate, push = by_name["round.generate"]["attrs"], by_name["round.push"]["attrs"]
    assert generate["groups_submitted"] == 2 and generate["macro_steps"] >= 2
    assert push["generation"] == 1 and push["leaves"] > 0 and push["bytes"] > 4 * push["leaves"]
    (placed,) = _named(records, "genrl.push_params")
    assert placed["attrs"] == push
    parents = lambda name: {r["parent"] for r in _named(records, name)}  # noqa: E731
    assert parents("genrl.macro_step") == {by_name["round.generate"]["span"]}
    assert parents("learn.step") == {by_name["round.learn"]["span"]}
    assert parents("genrl.push_params") == {by_name["round.push"]["span"]}
    assert by_name["round.generate"]["attrs"]["decode_tokens"] > 0


def test_the_round_s_children_cover_it_and_the_totals_say_so(recorded):
    """The phases tile the round: the root's self time (its duration less
    its children) is the bookkeeping at its end.  The same from the
    always-on totals, which need neither the sampler nor the profiler."""
    records = recorded["round"]
    (root,) = _named(records, "genrl.round")
    covered = sum(p["dur"] for p in _children(records, root))
    assert 0.0 <= root["dur"] - covered < 0.01 * root["dur"] + 2e-3
    before, after = recorded["totals"]
    took = lambda name: after[name]["seconds"] - before.get(name, {"seconds": 0.0})["seconds"]  # noqa: E731
    count = lambda name: after[name]["count"] - before.get(name, {"count": 0.0})["count"]  # noqa: E731
    assert count("genrl.round") == 1 and count("round.pack") == 2 and count("round.push") == 1
    assert took("genrl.round") == pytest.approx(root["dur"], abs=1e-3)
    phases = sum(took(name) for name in set(PHASES))
    assert 0.0 <= took("genrl.round") - phases < 0.01 * took("genrl.round") + 2e-3
    assert count("genrl.macro_step") == _named(records, "round.generate")[0]["attrs"]["macro_steps"]


def test_a_dispatch_carries_the_generation_of_the_snapshot_it_ran_with(recorded):
    """The round pushed generation 1 at its end: its own macro-steps ran on
    generation 0, the engine cycles after it on 1."""
    assert {d["attrs"]["generation"] for d in _named(recorded["round"], "genrl.dispatch")} == {0}
    assert {d["attrs"]["generation"] for d in _named(recorded["engine"], "genrl.dispatch")} == {1}


@pytest.fixture(scope="module")
def import_checks():
    """Each hot-path module imported alone, in a process of its own (both
    started at once): did the import install the profiler annotator?"""
    code = (
        "import importlib, sys; importlib.import_module(sys.argv[1]); "
        "from scalerl_tpu.runtime import tracing; import jax; "
        "assert tracing.get_annotator() is jax.profiler.TraceAnnotation, tracing.get_annotator(); "
        "print('loaded device_loop:', 'scalerl_tpu.runtime.device_loop' in sys.modules); "
        "print('loaded orbax:', any(m == 'orbax' or m.startswith(('orbax.', 'google.cloud.')) for m in sys.modules))"
    )
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT)}
    procs = {
        module: subprocess.Popen(
            [sys.executable, "-c", code, module], env=env, cwd=ROOT,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        for module in ("scalerl_tpu.genrl.continuous", "scalerl_tpu.agents.token_ppo")
    }
    return {module: (proc.communicate(timeout=120)[0], proc.returncode) for module, proc in procs.items()}


@pytest.mark.parametrize("module", ["scalerl_tpu.genrl.continuous", "scalerl_tpu.agents.token_ppo"])
def test_importing_a_hot_path_module_installs_the_annotator(import_checks, module):
    output, returncode = import_checks[module]
    assert returncode == 0, output[-2000:]


@pytest.mark.parametrize("module", ["scalerl_tpu.genrl.continuous", "scalerl_tpu.agents.token_ppo"])
def test_importing_a_hot_path_module_leaves_orbax_out(import_checks, module):
    """No cell writes a checkpoint, and orbax's import (through
    google.cloud.logging, two walks of every installed distribution) cost
    the learn cells 9 to 29 s of set-up on the chip's host (PERF.md, PR 28):
    `utils/checkpoint.py` imports it where a checkpoint is written."""
    output, returncode = import_checks[module]
    assert returncode == 0, output[-2000:]
    assert "loaded orbax: False" in output, output[-2000:]
