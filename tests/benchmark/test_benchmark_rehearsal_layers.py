"""Every cell's per-layer run (``--trace 1``) rehearsed on the CPU, and
"a new cell is files only": a configuration, a workload and a metric
written beside the others are found by name and run, with no edit to a
file that is there."""

import json
import os
import shutil

import pytest

from bench_helpers import BENCH, CELLS, ROOT, result_line, run_cell, workload_file

# what only a device trace can give: absent from a CPU rehearsal
_TRACE_ONLY = ("_share", "_roofline", "_mfu")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_its_per_layer_run(cell):
    line = result_line(run_cell(cell, 1, "--rehearse"))
    assert line["correct"] is True and line["failed"] == 0, line
    expected = [m for m in workload_file(cell)["per_layer"] if not m.endswith(_TRACE_ONLY)]
    assert sorted(line["rehearsed"]) == sorted(expected)
    # every shape the window uses was warmed up in set-up
    assert line["metrics"]["compiles_in_window"] == {"value": 0.0, "unit": "count"}
    assert "breakdown" not in line  # no device trace on a CPU


def test_a_new_cell_is_files_only(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmark")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    before = {
        p.relative_to(tmp_path): p.read_bytes()
        for p in tmp_path.rglob("*") if p.is_file()
    }
    bench = tmp_path / "benchmark"
    config = json.loads((bench / "configs" / "gpt2-medium.json").read_text())
    config.update(name="gpt2-new", n_layer=2, n_embd=32, n_head=2, n_positions=32,
                  vocab_size=48, eos_token_id=47, bos_token_id=47)
    (bench / "configs" / "gpt2-new.json").write_text(json.dumps(config))
    cell = workload_file("gpt2m_packed_learn")
    cell.update(name="new_cell", config="gpt2-new", traffic="new_mix",
                per_layer=["compiles_in_window", "new_steps"])
    cell["params"].update(cell.pop("rehearse_params"))
    cell.pop("rehearse_config")
    cell["params"].update(completions=9, rows_per_step=1)
    (bench / "workloads" / "new_cell.json").write_text(json.dumps(cell))
    (bench / "metrics" / "new_steps.py").write_text(
        'NAME, UNIT, LAYER, MOVES = "new_steps", "steps", "learner", "learn_tokens_per_s"\n\n\n'
        'def read(r):\n    return r["result"]["counters"]["steps_in_window"]\n'
    )
    proc = run_cell(
        "new_cell", 1, "--rehearse", cwd=tmp_path,
        env={"PYTHONPATH": str(ROOT) + os.pathsep + os.environ.get("PYTHONPATH", "")},
    )
    line = result_line(proc)
    assert line["correct"] is True
    assert line["rehearsed"] == ["compiles_in_window", "new_steps"]
    assert line["metrics"]["new_steps"]["value"] == line["attempted"] > 0
    for rel, content in before.items():  # nothing that was there changed
        assert (tmp_path / rel).read_bytes() == content, rel
