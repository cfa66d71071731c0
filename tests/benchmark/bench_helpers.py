"""Shared by the benchmark's tests: run the one command as the driver does
(a process of its own), and read its last line."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmark"
CONTRACT = json.loads((ROOT / "BENCHMARK.json").read_text())
# every cell that has a file, whether BENCHMARK.json lists it yet or not
CELLS = sorted(p.stem for p in (BENCH / "workloads").glob("*.json"))


def run_cell(cell, trace, *extra, cwd=ROOT, env=None, seconds="1", timeout=600):
    """``<command> --workload ... `` from ``cwd``; returns the finished
    process."""
    full_env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})}
    # one compute thread a process: the suite runs several workers, and
    # other tests beside these are sensitive to a loaded machine
    full_env["XLA_FLAGS"] = (
        full_env.get("XLA_FLAGS", "")
        + " --xla_cpu_multi_thread_eigen=false"
    ).strip()
    for name in list(full_env):
        if name.startswith("SCALERL_"):
            del full_env[name]
    command = [sys.executable] + CONTRACT["command"][1:]
    return subprocess.run(
        command + ["--workload", cell, "--seed", "3", "--seconds", seconds,
                   "--trace", str(trace), *extra],
        cwd=cwd, env=full_env, capture_output=True, text=True, timeout=timeout,
    )


def result_line(proc):
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def workload_file(cell):
    return json.loads((BENCH / "workloads" / f"{cell}.json").read_text())
