"""Compile the cells' whole-step programs for a described ``v5e:2x2``.

    JAX_PLATFORMS=cpu python benchmark/aot_compile.py [fused] [decode] [learn_1chip] [learn_4chip]

No chip is needed and nothing runs: the installed TPU compiler compiles
for devices that are described, not attached.  This is where lanes and
rows per step are sized (``memory_analysis()`` per device) and where a
kernel the compiler would refuse shows before any chip time is spent.
A compile that passes is not a chip run.

The program asks ``jax.default_backend()`` to choose its kernels and
``jax.devices()`` to build its mesh, and places its own state with
``jax.device_put``.  This script steers all three from its own side, for
its own process only, and adds no option to the program: the backend
reads ``tpu``, the devices are the described ones, and ``device_put`` to a
described device returns the shape with that sharding.  The sizes are read
from the cells' own files (``workloads/*.json``, ``configs/*.json``).
"""

from __future__ import annotations

import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

import harness  # noqa: E402

HBM_BYTES = 16e9


def _steer(devices=None):
    """Make the program see a TPU backend and, for a mesh, the described
    devices."""
    jax.default_backend = lambda: "tpu"
    if devices is None:
        return
    real_put = jax.device_put

    def described_put(x, device=None, **kw):
        def one(leaf, sh):
            if isinstance(sh, jax.Device):
                sh = SingleDeviceSharding(sh)
            if sh is None or not any(d in devices for d in sh.device_set):
                return real_put(leaf, sh, **kw)
            return jax.ShapeDtypeStruct(np.shape(leaf), jnp.result_type(leaf), sharding=sh)

        if device is None or isinstance(device, (jax.Device, jax.sharding.Sharding)):
            return jax.tree_util.tree_map(lambda leaf: one(leaf, device), x)
        return jax.tree_util.tree_map(one, x, device)

    real_devices = jax.devices
    # asked for a backend by name (the host's CPU), the program gets it
    jax.devices = lambda *a, **k: real_devices(*a, **k) if a or k else list(devices)
    jax.device_put = described_put


def _shapes_on(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.result_type(x), sharding=sharding),
        tree,
    )


def _report(name, lowered, t0):
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    per_device = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    )
    collectives = {
        op: text.count(f" {op}(") + text.count(f" {op}-start(")
        for op in ("all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute")
    }
    print(
        f"{name}: compiled in {time.perf_counter() - t0:.1f}s; per device "
        f"{per_device / 1e9:.2f} GB (arguments {mem.argument_size_in_bytes / 1e9:.2f}, "
        f"outputs {mem.output_size_in_bytes / 1e9:.2f}, temporaries "
        f"{mem.temp_size_in_bytes / 1e9:.2f}, aliased {mem.alias_size_in_bytes / 1e9:.2f}); "
        f"tpu_custom_call x{text.count('tpu_custom_call')}; collectives "
        f"{ {k: v for k, v in collectives.items() if v} }",
        flush=True,
    )
    if per_device > HBM_BYTES:
        print(f"  -> DOES NOT FIT a 16 GB device", flush=True)
    return per_device


def _ctx(cell):
    workload = harness.load_json("workloads", cell)
    config = harness.load_json("configs", workload["config"])
    return harness.Context(
        workload=workload, config=config, params=dict(workload["params"]), seed=0,
        seconds=0.0, trace=False, rehearse=False, process_start=time.perf_counter(),
        trace_dir=BENCH, reference=harness.load_module("reference", config["reference"]),
    )


def decode(topo, cell="gpt2m_group_rollout"):
    """The engine's decode macro-step and its widest prefill, one chip.
    The engine is built for real in host memory (it asks ``jax.devices()``
    only for where to keep its state); its jitted programs are then
    lowered on that state's shapes, described on the chip."""
    _steer()
    one_chip = SingleDeviceSharding(topo.devices[0])
    ctx = _ctx(cell)
    st = harness.load_module("traffic", ctx.workload["driver"]).build_engine(ctx)
    eng = st.engine
    state = _shapes_on(
        (eng._pools, eng._logits_st, eng._value_st, eng._cl, eng._done, eng._resp), one_chip
    )
    params = _shapes_on(st.params, one_chip)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)  # noqa: E731
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    t0 = time.perf_counter()
    _report(
        f"{cell} decode macro-step, {eng.config.lanes} lanes",
        eng._decode_fn.lower(params, *state, i32(*eng._table.shape), key), t0,
    )
    P = max(eng.config.resolved_prompt_buckets())
    t0 = time.perf_counter()
    _report(
        f"{cell} prefill P={P} A=1",
        eng._prefill_fn(("local", P, 1)).lower(
            params, *state, i32(1, P), i32(1), i32(1), i32(1, P), i32(1, P)
        ),
        t0,
    )


def _learn(topo, cell, n_devices, rows_options):
    _steer(topo.devices[:n_devices])
    from scalerl_tpu.genrl.rollout import packed_field_shapes

    ctx = _ctx(cell)
    st = harness.load_module("traffic", ctx.workload["driver"]).build_learner(ctx)
    first = None
    if st.agent.mesh is None:
        first = SingleDeviceSharding(topo.devices[0])
        st.agent.state = _shapes_on(st.agent.state, first)
    S = int(ctx.params["pack_len"])
    for rows in rows_options:
        batch = {
            name: jax.ShapeDtypeStruct((rows,) + shape, dtype, sharding=first)
            for name, (shape, dtype) in packed_field_shapes(S).items()
        }
        batch["is_weight"] = jax.ShapeDtypeStruct((rows,), jnp.float32, sharding=first)
        t0 = time.perf_counter()
        _report(f"{cell} learn step, {rows} rows of {S}", st.agent.lower_learn(batch), t0)


def fused(topo, cell="impala_fused"):
    """The fused loop's one-dispatch program at several lane counts."""
    _steer()
    one_chip = SingleDeviceSharding(topo.devices[0])
    for num_envs in (512, 2048, 4096):
        ctx = _ctx(cell)
        ctx.params["num_envs"] = num_envs
        driver = harness.load_module("traffic", ctx.workload["driver"])
        st = driver.build(ctx)
        # train_chunk is the loop's public one-dispatch entry; under this
        # outer jit its donation is lost, so the temporaries read high
        shapes = _shapes_on((st.agent.state, st.carry, st.key), one_chip)
        t0 = time.perf_counter()
        _report(
            f"{cell} one dispatch, {num_envs} envs x {ctx.params['iters_per_dispatch']} iterations",
            jax.jit(st.trainer.loop.train_chunk).lower(*shapes), t0,
        )


def learn_1chip(topo):
    _learn(topo, "gpt2m_packed_learn", 1, (1, 2, 4))


def learn_4chip(topo):
    _learn(topo, "gpt2l_learn_dp2mp2", 4, (4, 8))


def main(argv):
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    steps = {"fused": fused, "decode": decode, "learn_1chip": learn_1chip, "learn_4chip": learn_4chip}
    for name in argv or steps:
        steps[name](topo)


if __name__ == "__main__":
    main(sys.argv[1:])
