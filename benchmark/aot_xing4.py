"""Compile ``xing4_group_rollout``'s decode macro-step, widest prefill and
seeded-weights program for a described ``v5e:2x2``, print
``memory_analysis()`` and count, in the compiled decode text, what the
issue asks of it.

    JAX_PLATFORMS=cpu python benchmark/aot_xing4.py [lanes] [text_dir] [layers]

``aot_zaya.py``'s way with this cell's name: no chip is needed and nothing
runs.  The engine is built for real in host memory (10.5 GB of seeded
weights, twice while they move, 1.5 GB of pools at 96 lanes), so give it
several minutes and 40 GB.  With ``lanes`` the cell's lane count is
overridden for this compile only: how the cell was sized.  With
``text_dir`` each compiled program's text is written there.  With
``layers`` the stack is cut to that many layers (one dense) for this
compile only: the counts a sublayer below do not depend on the depth, and
two layers build in 3 GB.

What to read in the output: the per-device bytes (arguments and
temporaries have to leave room on a 16 GB chip; the cell takes its lane
count from them), ``tpu_custom_call`` (``paged_decode_latent`` once a layer
in the decode program), ``whole-array copies`` (a ``copy`` whose result has
the shape of a latent pool or an expert bank means a carry is not in place
or a bank is relaid; ``asynchronous copies`` beside them are
``copy-start``s of those shapes), ``wide writes`` (instructions outside
fused computations whose result holds the ``[lanes, vocabulary]`` shape, by
opcode: how many times the logits are written between the head and the
pick), ``while`` loops (the decode loop's own and none for the Sinkhorn
iterations), and ``mhc a sublayer``: the top-level instructions (device
operations: fusions and what stands alone) whose ``op_name`` carries the
``mhc_maps`` and the ``mhc_mix`` scope, over the stack's hyper-connections,
and the instructions inside fused computations under each scope.
"""

import re
import sys
import time
from collections import Counter
from pathlib import Path

import aot_compile
import aot_nemotron
import aot_zaya
import harness


# instructions that are no device operation: a tuple's plumbing, a view
_PLUMBING = ("get-tuple-element", "tuple", "bitcast", "constant", "parameter")


def _scoped_inside(text, scope):
    """Instructions of any computation, fused ones included, under a scope."""
    return sum(1 for line in text.splitlines() if f"/{scope}/" in line or f"/{scope}\"" in line)


def main(argv):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    lanes = int(argv[0]) if argv else None
    text_dir = Path(argv[1]) if len(argv) > 1 else None
    layers = int(argv[2]) if len(argv) > 2 else None
    load_json = harness.load_json

    def overridden(kind, name):
        loaded = load_json(kind, name)
        if kind == "workloads" and lanes:
            loaded["params"]["lanes"] = lanes
        if kind == "configs" and layers:
            loaded["num_hidden_layers"] = layers
        return loaded

    harness.load_json = overridden
    cell = "xing4_group_rollout"
    workload = harness.load_json("workloads", cell)
    cfg = harness.load_json("configs", workload["config"])
    import xing4_work as work

    L = int(workload["params"]["lanes"])
    d, f, e = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    banks = (f"bf16[{e},{d},{f}]", f"bf16[{e},{f},{d}]")
    wide = f"[{L},{cfg['vocab_size']}]"
    report = aot_compile._report

    def report_and_look(name, lowered, t0):
        once = aot_nemotron._Compiled(lowered)
        per_device = report(name, once, t0)
        text = once.compiled.as_text()
        pools = sorted(set(re.findall(r"f32\[\d+,8,640\]", text)))
        shapes = banks + tuple(pools)
        print(f"  whole-array copies: {aot_nemotron._whole_copies(text, shapes)}", flush=True)
        asynchronous = {
            shape: sum(1 for line in text.splitlines() if " copy-start(" in line and f"= ({shape}" in line)
            for shape in shapes
        }
        print(f"  asynchronous copies (copy-start, through another memory space): {asynchronous}", flush=True)
        print(f"  wide writes {wide}: {aot_zaya._wide_writes(text, wide)}", flush=True)
        ops = Counter(op for _h, _r, op, _rest in aot_zaya._top_level(text))
        print(f"  while loops: {ops.get('while', 0)}; top-level instructions by opcode: {dict(ops.most_common(8))}", flush=True)
        n = work.sublayers(cfg)
        for scope in ("mhc_maps", "mhc_mix"):
            by_op = Counter(
                op for _h, _r, op, rest in aot_zaya._top_level(text)
                if op not in _PLUMBING and (f"/{scope}/" in rest or f"/{scope}\"" in rest)
            )
            print(
                f"  {scope} a sublayer: {sum(by_op.values()) / n:.1f} device operations "
                f"({ {k: round(v / n, 1) for k, v in by_op.items()} }), "
                f"{_scoped_inside(text, scope) / n:.0f} instructions in all",
                flush=True,
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
        raise aot_zaya._Lowered

    jax.jit = keep_jit
    try:
        driver._seeded_weights(model, 0, int(cfg["eos_token_id"]), float(workload["params"]["eos_prob"]), args.vocab_size)
    except aot_zaya._Lowered:
        pass
    finally:
        jax.jit = real_jit
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=SingleDeviceSharding(topo.devices[0]))
    t0 = time.perf_counter()
    report(f"{cell} seeded weights", seeded["fn"].lower(key), t0)
    aot_compile.decode(topo, cell=cell)


if __name__ == "__main__":
    main(sys.argv[1:])
