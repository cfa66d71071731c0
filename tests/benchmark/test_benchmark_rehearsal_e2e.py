"""Every cell end to end at tiny sizes on the CPU (``--rehearse``), as the
end-to-end run (``--trace 0``); the contract of the last line; and what
the command does off the chip without ``--rehearse``."""

import importlib.util
import json
import os
import re
import subprocess
import sys

import pytest

from bench_helpers import BENCH, CELLS, CONTRACT, ROOT, result_line, run_cell, workload_file

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_end_to_end(cell):
    line = result_line(run_cell(cell, 0, "--rehearse"))
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True, line
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"  # never mistaken for a chip run
    # the cell measured each of its end-to-end metrics, and a CPU run prints
    # no time, rate, share or memory under a device metric's name
    assert sorted(line["rehearsed"]) == sorted(workload_file(cell)["end_to_end"])
    assert all(m["unit"] in ("count", "tokens", "frames", "steps") for m in line["metrics"].values())
    assert "breakdown" not in line and "memory_peak_bytes" not in line["device"]


def test_off_the_chip_the_command_fails_and_prints_no_result():
    proc = run_cell(CELLS[0], 0)
    assert proc.returncode != 0
    last = (proc.stdout.strip().splitlines() or [""])[-1]
    assert not last.startswith("{"), last


def test_a_kernel_selector_in_the_environment_is_refused():
    proc = subprocess.run(
        [sys.executable] + CONTRACT["command"][1:] + ["--workload", CELLS[0], "--rehearse"],
        cwd=ROOT, env={**os.environ, "SCALERL_PAGED_ATTN": "xla"},
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0 and "SCALERL_PAGED_ATTN" in proc.stderr


def test_contract_names_units_and_files():
    """``BENCHMARK.json`` against the files it names and the limits of the
    contract that a test can check without a run."""
    assert set(CONTRACT) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    assert 1 <= CONTRACT["run_seconds"] <= 51
    configs = {c["name"]: c for c in CONTRACT["configs"]}
    for c in configs.values():
        assert NAME.match(c["name"]) and set(c) == {"name", "source", "file", "reduced", "why"}
        on_disk = json.loads((ROOT / c["file"]).read_text())
        assert on_disk["reduced"] == c["reduced"] and on_disk["source"] == c["source"]
        assert any(c["file"].startswith(p + "/") for p in CONTRACT["paths"])
    e2e = {m["name"]: m for m in CONTRACT["end_to_end"]}
    layer = {m["name"]: m for m in CONTRACT["per_layer"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in list(e2e.values()) + list(layer.values()):
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in e2e.values():
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.1
    four_chip = 0
    for w in CONTRACT["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and len(w["why"]) <= 200
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["config"] in configs
        on_disk = workload_file(w["name"])
        assert {k: on_disk[k] for k in w} == w
        four_chip += w["chips"] == 4
        # the metrics the cell's file lists are the ones the contract gives it
        for name, unit in on_disk["end_to_end"].items():
            assert e2e[name]["unit"] == unit
            assert w["name"] in e2e[name].get("workloads", [w["name"]])
        for name in on_disk["per_layer"]:
            assert w["name"] in layer[name].get("workloads", [w["name"]])
            assert layer[name]["moves"] in on_disk["end_to_end"]
        assert {n for n, m in e2e.items() if w["name"] in m.get("workloads", [w["name"]])} == set(
            on_disk["end_to_end"]
        )
        assert {n for n, m in layer.items() if w["name"] in m.get("workloads", [w["name"]])} == set(
            on_disk["per_layer"]
        )
    # a quarter of the cells, rounded down, may ask for four chips; one always may
    assert four_chip <= max(1, len(CONTRACT["workloads"]) // 4)
    assert len({(w["config"], w["traffic"]) for w in CONTRACT["workloads"]}) == len(CONTRACT["workloads"])
    # a per-layer metric is one reader file that agrees with its entry
    for name, m in layer.items():
        spec = importlib.util.spec_from_file_location(name, BENCH / "metrics" / f"{name}.py")
        module = importlib.util.module_from_spec(spec)
        sys.path.insert(0, str(BENCH))
        try:
            spec.loader.exec_module(module)
        finally:
            sys.path.remove(str(BENCH))
        assert (module.NAME, module.UNIT, module.LAYER, module.MOVES) == (
            name, m["unit"], m["layer"], m["moves"],
        )
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
