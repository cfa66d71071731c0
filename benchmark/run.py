"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process.  It runs the cell named ``--workload`` on the accelerator JAX
finds (a TPU, or the run fails and prints no result), and prints free-form
log lines followed by one JSON object on the last line of standard output.
With ``--trace 0`` the object's ``metrics`` are the cell's end-to-end
metrics, measured with the profiler off; with ``--trace 1`` they are its
per-layer metrics, and the profiler traces the window's last seconds.

Everything that belongs to one cell, configuration, traffic driver or
per-layer metric is a file found by name (``workloads/``, ``configs/``,
``traffic/``, ``metrics/``, ``reference/``); this file names none of them.

``--rehearse`` is for tests: the cell's tiny sizes on the CPU.  Its result
line says ``"platform": "cpu"`` and carries no time, rate, share or
utilisation, only counts.
"""

from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse
import json
import os
import re
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT))

import harness  # noqa: E402

# every user-set selector between duplicate kernel paths (copied from
# chip_smoke.py): the benchmark measures what ``auto`` picks on this
# backend, so none of them may be set
_SELECTORS = (
    "SCALERL_PAGED_ATTN", "SCALERL_SEGMENT_ATTN", "SCALERL_PER_METHOD",
    "SCALERL_PER_UPDATE", "SCALERL_ITER_MODE", "SCALERL_NONFINITE_GUARD",
    "SCALERL_NO_TRANSFER_GUARD", "SCALERL_TRACE_SAMPLE",
)
# units a CPU rehearsal may print: things the program counts
_COUNT_UNITS = ("count", "tokens", "frames", "steps")


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    return ap.parse_args(argv)


def _pin_platform(rehearse: bool, chips: int):
    """Pin the backend before JAX initialises.  On the chip: TPU, with the
    host CPU backend beside it (a driver may stage a model there that does
    not fit one chip unsharded); JAX fails loudly when an explicitly listed
    platform cannot initialise."""
    if rehearse:  # a mesh needs exactly as many (virtual) devices as chips
        rest = re.sub(
            r"--xla_force_host_platform_device_count=\d+", "", os.environ.get("XLA_FLAGS", "")
        )
        os.environ["XLA_FLAGS"] = f"{rest} --xla_force_host_platform_device_count={chips}".strip()
    import jax

    jax.config.update("jax_platforms", "cpu" if rehearse else "tpu,cpu")
    # the program's own set-up call: places the persistent compilation
    # cache at <checkout>/.jax_cache unless JAX_COMPILATION_CACHE_DIR is set
    from scalerl_tpu.utils.platform import setup_platform

    backend = setup_platform("auto")
    want = "cpu" if rehearse else "tpu"
    if backend != want:
        raise SystemExit(f"the benchmark needs a {want} backend, JAX gave {backend!r}")
    if not rehearse:
        # small programs are rebuilt by every run unless they are cached too
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chip(s), JAX found {len(devices)}")
    return devices


def _device_entry(devices, log) -> dict:
    """The device as JAX reports it.  ``memory_peak_bytes`` is the fullest
    chip's peak of live buffers plus its peak of bytes reserved for the
    loaded programs' temporaries (``peak_bytes_in_use`` and
    ``peak_bytes_reserved`` of ``memory_stats()``): the first alone leaves
    out what a program needs while it runs, which is most of the memory in
    the fused loop and the decode program (PERF.md, PR 22)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        log(f"memory_stats of device {d.id}: {stats}")
        peak = max(
            peak, int(stats.get("peak_bytes_in_use", 0)) + int(stats.get("peak_bytes_reserved", 0))
        )
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }


def main(argv=None) -> int:
    args = _parse(argv)
    set_selectors = [k for k in _SELECTORS if os.environ.get(k)]
    if set_selectors:
        raise SystemExit(f"unset {set_selectors}: the benchmark runs what auto picks")

    workload = harness.load_json("workloads", args.workload)
    config = harness.load_json("configs", workload["config"])
    params = dict(workload["params"])
    if args.rehearse:
        params.update(workload.get("rehearse_params", {}))
        config = {**config, **workload.get("rehearse_config", {})}
    chips = int(workload["chips"])
    devices = _pin_platform(args.rehearse, chips)

    import trace_reduce
    import work

    # a rehearsal's trace goes beside, not over, the chip's
    trace_dir = ROOT / "chiprun_out" / ("rehearsal" if args.rehearse else "traces") / args.workload
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = harness.Context(
        workload=workload, config=config, params=params, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), rehearse=args.rehearse,
        process_start=_PROCESS_START, trace_dir=trace_dir,
        reference=harness.load_module("reference", config["reference"]),
        clock=harness.CompileClock(),
    )
    ctx.log(
        f"cell {args.workload} seed {args.seed} seconds {args.seconds} "
        f"trace {args.trace} on {len(devices)} x {devices[0].device_kind}"
    )
    driver = harness.load_module("traffic", workload["driver"])
    state = driver.build(ctx)  # the system, its weights from the seed, warm-up
    result = driver.run(ctx, state)  # opens and closes the window
    device = _device_entry(devices, ctx.log)
    correct, notes = driver.check(ctx, state, result)  # outside the window
    ctx.log("correct:", correct, notes)

    reading = {
        "ctx": ctx,
        "result": result,
        "device": device,
        "peaks": None if args.rehearse else work.peaks(devices[0].device_kind),
        "trace": None,
    }
    out = {
        "correct": bool(correct),
        "attempted": int(result["attempted"]),
        "failed": int(result["failed"]),
    }
    values = {}
    if args.trace:
        if ctx.trace_path is not None:
            reading["trace"] = trace_reduce.reduce_trace(ctx.trace_path)
        for name in workload["per_layer"]:
            metric = harness.load_module("metrics", name)
            value = metric.read(reading)
            if value is not None:  # a reader with nothing to read returns nothing
                values[name] = {"value": float(value), "unit": metric.UNIT}
        trace = reading["trace"]
        if trace is not None and not args.rehearse:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            out["breakdown"] = {
                "device_ops": trace["device_ops"],
                "idle_gaps": trace["idle_gaps"],
            }
    else:
        units = workload["end_to_end"]
        measured = dict(result["end_to_end"])
        measured["setup_s"] = ctx.setup_s
        measured["peak_hbm_gb"] = device["memory_peak_bytes"] / 1e9
        for name, unit in units.items():
            values[name] = {"value": float(measured[name]), "unit": unit}
    if args.rehearse:
        out["rehearsed"] = sorted(values)
        values = {k: v for k, v in values.items() if v["unit"] in _COUNT_UNITS}
        device.pop("memory_peak_bytes")
    out["metrics"] = values
    out["device"] = device
    out["notes"] = notes
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
