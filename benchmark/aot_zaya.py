"""Compile ``zaya_group_rollout``'s decode macro-step, widest prefill and
seeded-weights program for a described ``v5e:2x2``, print
``memory_analysis()`` and count, in the compiled decode text, what the
issue asks of it.

    JAX_PLATFORMS=cpu python benchmark/aot_zaya.py [lanes] [text_dir]

``aot_nemotron.py``'s way with this cell's name: no chip is needed and
nothing runs.  The engine is built for real in host memory (11.5 GB of
seeded weights, twice while they move, 2 GB of pools at 48 lanes), so give
it several minutes and 40 GB.  With ``lanes`` the cell's lane count is
overridden for this compile only: how the cell was sized.  With
``text_dir`` each compiled program's text is written there.

What to read in the output: the per-device bytes (arguments and
temporaries have to leave room on a 16 GB chip; the cell takes its lane
count from them), ``tpu_custom_call`` (``paged_decode`` once a layer in the
decode program), ``whole-array copies`` (a ``copy`` whose result has the
shape of a page pool, a layer's window or an expert bank means a carry is
not in place or a bank is relaid; ``asynchronous copies`` beside them are
``copy-start``s of those shapes, XLA's own prefetch of an array through
another memory space and back), ``wide writes`` (instructions outside
fused computations whose result holds the ``[lanes, vocabulary]`` shape, by
opcode: how many times the logits are written between the head and the
pick), and ``scoped fusions``: instructions whose ``op_name`` carries the
``cca_window`` and the ``zaya_router`` scope, a layer.
"""

import re
import sys
import time
from collections import Counter
from pathlib import Path

import aot_compile
import aot_nemotron
import harness

_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\(.*?\)|\S+) ([a-z\-]+)\((.*)$")


def _top_level(text):
    """``(head, result, opcode, rest)`` of every instruction outside fused
    computations."""
    fused = set(re.findall(r"calls=%([^\s,)}]+)", text))
    inside_fused = False
    for line in text.splitlines():
        opened = re.match(r"^(?:ENTRY )?%(\S+) .*\{$", line)
        if opened:
            inside_fused = opened.group(1) in fused
            continue
        match = _INSTRUCTION.match(line)
        if match and not inside_fused:
            yield match.groups()


def _wide_writes(text, wide):
    """Instructions (not control flow, not a tuple's plumbing) whose
    result holds the shape ``wide``, by opcode and element type."""
    skip = ("while", "conditional", "call", "tuple", "get-tuple-element", "parameter", "bitcast")
    writes = Counter()
    for _head, result, op, _rest in _top_level(text):
        if op in skip:
            continue
        for dtype in re.findall(r"([a-z0-9]+)" + re.escape(wide), result):
            writes[f"{op}:{dtype}"] += 1
    return dict(writes)


def _scoped(text, scope):
    return sum(1 for _h, _r, _op, rest in _top_level(text) if f"/{scope}/" in rest)


def main(argv):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    lanes = int(argv[0]) if argv else None
    text_dir = Path(argv[1]) if len(argv) > 1 else None
    if lanes:
        load_json = harness.load_json

        def with_lanes(kind, name):
            loaded = load_json(kind, name)
            if kind == "workloads":
                loaded["params"]["lanes"] = lanes
            return loaded

        harness.load_json = with_lanes
    cell = "zaya_group_rollout"
    workload = harness.load_json("workloads", cell)
    cfg = harness.load_json("configs", workload["config"])
    import zaya_work as work

    L = int(workload["params"]["lanes"])
    d, f, e = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["num_experts"]
    layers = cfg["num_hidden_layers"]
    window = f"f32[{L},{work.window_rows(cfg) * work.window_channels(cfg)}]"  # a ring, rows side by side
    banks = (f"bf16[{e},{d},{f}]", f"bf16[{e},{f},{d}]")
    wide = f"[{L},{cfg['vocab_size']}]"
    report = aot_compile._report

    def report_and_look(name, lowered, t0):
        once = aot_nemotron._Compiled(lowered)
        per_device = report(name, once, t0)
        text = once.compiled.as_text()
        pools = sorted(set(re.findall(
            r"f32\[\d+,8," + str(cfg["num_key_value_heads"] * cfg["head_dim"]) + r"\]", text
        )))
        print(f"  whole-array copies: {aot_nemotron._whole_copies(text, (window,) + banks + tuple(pools))}", flush=True)
        asynchronous = {
            shape: sum(
                1 for line in text.splitlines()
                if " copy-start(" in line and f"= ({shape}" in line
            )
            for shape in (window,) + banks + tuple(pools)
        }
        print(f"  asynchronous copies (copy-start, through another memory space): {asynchronous}", flush=True)
        print(f"  wide writes {wide}: {_wide_writes(text, wide)}", flush=True)
        print(
            f"  scoped fusions a layer: cca_window {_scoped(text, 'cca_window') / layers:.1f}, "
            f"zaya_router {_scoped(text, 'zaya_router') / layers:.1f}", flush=True,
        )
        if text_dir is not None:
            text_dir.mkdir(parents=True, exist_ok=True)
            (text_dir / (re.sub(r"[^A-Za-z0-9]+", "_", name) + ".txt")).write_text(text)
        return per_device

    aot_compile._report = report_and_look
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    # the seeded weights are made on the device in one program: it has to fit too
    ctx = aot_compile._ctx(cell)
    driver = harness.load_module("traffic", "group_rollout")
    from scalerl_tpu.config import GenRLArguments, parse_args
    from scalerl_tpu.trainer.sequence_rl import build_genrl_model

    args = parse_args(
        GenRLArguments,
        list(workload["params"]["argv"]) + ctx.reference.program_argv(cfg)
        + ["--prompt-len", str(workload["params"]["prompt_len"][1]),
           "--max-new-tokens", str(workload["params"]["max_new_tokens"])],
    )
    model = build_genrl_model(args)
    real_jit = jax.jit
    seeded = {}

    def keep_jit(fun, *a, **kw):
        seeded["fn"] = real_jit(fun, *a, **kw)
        raise _Lowered

    jax.jit = keep_jit
    try:
        driver._seeded_weights(model, 0, int(cfg["eos_token_id"]), float(workload["params"]["eos_prob"]), args.vocab_size)
    except _Lowered:
        pass
    finally:
        jax.jit = real_jit
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=SingleDeviceSharding(topo.devices[0]))
    t0 = time.perf_counter()
    report(f"{cell} seeded weights", seeded["fn"].lower(key), t0)
    aot_compile.decode(topo, cell=cell)


class _Lowered(Exception):
    """The jitted function was caught before it ran."""


if __name__ == "__main__":
    main(sys.argv[1:])
