"""The kernels of the benchmark's cells, compiled at the cells' own shapes
for a described ``v5e:2x2``: no chip is needed, nothing runs.  A tiling or
memory limit the chip's compiler would refuse fails here, before any chip
time is spent.  The shapes are read from the cells' files, so a cell that
changes its sizes changes what is compiled.

The topology is described inside a module-scoped fixture, never at import
(only one process at a time may load the TPU's library), and every such
test lives in this one file.  The whole-step compiles (decode program,
learn steps, with ``memory_analysis()``) take minutes and are the script
``benchmark/aot_compile.py``; its output is quoted in ``PERF.md``.
"""

import json
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

BENCH = Path(__file__).resolve().parents[2] / "benchmark"


def _cell(name):
    workload = json.loads((BENCH / "workloads" / f"{name}.json").read_text())
    config = json.loads((BENCH / "configs" / f"{workload['config']}.json").read_text())
    return workload["params"], config


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever stops the description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip: keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).trace(*shapes).lower(lowering_platforms=("tpu",)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_paged_decode_kernel_at_the_rollout_cells_pool(one_chip):
    from scalerl_tpu.ops.pallas_paged_attention import paged_decode_attention

    p, cfg = _cell("gpt2m_group_rollout")
    heads, dim = cfg["n_head"], cfg["n_embd"] // cfg["n_head"]
    page = 8  # --genrl-page-size in the cell's argv
    assert p["argv"][p["argv"].index("--genrl-page-size") + 1] == str(page)
    per_lane = -(-(p["prompt_len"][1] + p["max_new_tokens"]) // page)
    pages = p["lanes"] * per_lane + 1
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    _compile(
        lambda q, k, v, table, lengths: paged_decode_attention(
            q, k, v, table, lengths, interpret=False
        ),
        sds((p["lanes"], 1, heads, dim), jnp.float32),
        sds((pages, page, heads, dim), jnp.float32),
        sds((pages, page, heads, dim), jnp.float32),
        sds((p["lanes"], per_lane), jnp.int32),
        sds((p["lanes"],), jnp.int32),
    )


@pytest.mark.parametrize(
    "cell,mp", [("gpt2m_packed_learn", 1), ("gpt2l_learn_dp2mp2", 2)],
    ids=["gpt2-medium-16-heads", "gpt2-large-mp-shard-10-heads"],
)
def test_segment_flash_forward_and_backward_on_packed_rows(one_chip, cell, mp):
    from scalerl_tpu.ops.pallas_attention import segment_flash_attention

    p, cfg = _cell(cell)
    heads, dim = cfg["n_head"] // mp, cfg["n_embd"] // cfg["n_head"]
    dp = 2 if mp == 2 else 1
    rows = p["rows_per_step"] // dp  # what one shard of the step holds
    qkv = jax.ShapeDtypeStruct((rows, p["pack_len"], heads, dim), jnp.float32, sharding=one_chip)
    seg = jax.ShapeDtypeStruct((rows, p["pack_len"]), jnp.int32, sharding=one_chip)

    def loss(q, k, v, seg):
        return jnp.sum(segment_flash_attention(q, k, v, seg, interpret=False))

    _compile(jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv, seg)


def test_per_sample_kernel_at_the_replays_size(one_chip):
    from scalerl_tpu.ops.pallas_per import pallas_sample

    p, _cfg = _cell("gpt2m_packed_learn")
    _compile(
        lambda flat, targets: pallas_sample(flat, targets, interpret=False),
        jax.ShapeDtypeStruct((p["replay_rows"],), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((p["rows_per_step"],), jnp.float32, sharding=one_chip),
    )
