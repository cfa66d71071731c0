"""Compile ``qwen3next_group_rollout``'s decode macro-step and widest
prefill for a described ``v5e:2x2``, print ``memory_analysis()`` and count,
in the compiled text, what touches a Gated DeltaNet layer's state.

    JAX_PLATFORMS=cpu python benchmark/aot_qwen3next.py [lanes] [text_dir]

``aot_nemotron.py``'s way with this cell's name: no chip is needed and
nothing runs.  The engine is built for real in host memory (6.8 GB of
seeded weights, twice while they move, 3.8 GB of recurrent state and 2.4
GB of pools at 96 lanes), so give it several minutes and 30 GB.  With
``lanes`` the cell's lane count is overridden for this compile only: how
the cell was sized.  With ``text_dir`` each compiled program's text is
written there.

What to read in the output: the per-device bytes (they have to leave room
on a 16 GB chip), ``tpu_custom_call`` (``paged_decode`` once an attention
layer in the decode program), ``whole-array copies`` (a ``copy`` whose
result has the shape of a layer's recurrent state or of a page pool means
the carry is not in place), and ``state readers``: the instructions of the
entry and loop bodies that take an ``f32[lanes,32,128,128]`` state as an
operand, by opcode, and how many of them give the state back (``state
writers``).  One reader that is also the writer a layer is one pass in and
out; a reduction beside an elementwise writer is two reads a layer.
"""

import re
import sys
from collections import Counter
from pathlib import Path

import aot_compile
import aot_nemotron
import harness

_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\(.*?\)|\S+) ([a-z\-]+)\((.*)$")


def _state_traffic(text, state):
    """``(readers, writers)`` among the ``fusion`` / ``custom-call`` /
    ``copy`` instructions outside fused computations: a reader (counted
    by its head, numbers taken off) has an operand of the shape ``state``
    (operands are printed by name: their shapes come from the lines that
    define them); of the readers, a writer has it in its result too."""
    fused = set(re.findall(r"calls=%([^\s,)}]+)", text))
    shapes, bodies, inside_fused = {}, [], False
    for line in text.splitlines():
        opened = re.match(r"^(?:ENTRY )?%(\S+) .*\{$", line)
        if opened:
            inside_fused = opened.group(1) in fused
            continue
        match = _INSTRUCTION.match(line)
        if match:
            shapes[match.group(1)] = match.group(2)
            if not inside_fused and match.group(3) in ("fusion", "custom-call", "copy"):
                bodies.append(match.groups())
    readers, writers = Counter(), 0
    for head, result, op, rest in bodies:
        operands = re.findall(r"%([^\s,)]+)", rest.split("), ")[0])
        if any(shapes.get(name, "").startswith(state) for name in operands):
            readers[f"{op}:{re.sub(r'[.0-9]+$', '', head)}"] += 1
            writers += state in result
    return dict(readers), writers


def main(argv):
    from jax.experimental import topologies

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
    cell = "qwen3next_group_rollout"
    workload = harness.load_json("workloads", cell)
    cfg = harness.load_json("configs", workload["config"])
    import qwen3next_work as work

    L = int(workload["params"]["lanes"])
    state = (
        f"f32[{L},{cfg['linear_num_value_heads']},{cfg['linear_key_head_dim']},{cfg['linear_value_head_dim']}]",
        f"f32[{L},{cfg['linear_conv_kernel_dim'] - 1},{work.gdn_channels(cfg)}]",
    )
    report = aot_compile._report

    def report_and_look(name, lowered, t0):
        once = aot_nemotron._Compiled(lowered)
        per_device = report(name, once, t0)
        text = once.compiled.as_text()
        pools = sorted(set(re.findall(
            r"f32\[\d+,8," + str(cfg["num_key_value_heads"] * cfg["head_dim"]) + r"\]", text
        )))
        print(f"  whole-array copies: {aot_nemotron._whole_copies(text, state + tuple(pools))}", flush=True)
        readers, writers = _state_traffic(text, state[0])
        print(f"  state readers: {readers}; of them state writers: {writers}", flush=True)
        if text_dir is not None:
            text_dir.mkdir(parents=True, exist_ok=True)
            (text_dir / (re.sub(r"[^A-Za-z0-9]+", "_", name) + ".txt")).write_text(text)
        return per_device

    aot_compile._report = report_and_look
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    aot_compile.decode(topo, cell=cell)


if __name__ == "__main__":
    main(sys.argv[1:])
