"""Compile ``nemotron_group_rollout``'s decode macro-step and widest prefill
for a described ``v5e:2x2``, print ``memory_analysis()`` and look for
copies of a whole state array or pool in the compiled text.

    JAX_PLATFORMS=cpu python benchmark/aot_nemotron.py [lanes] [text_dir]

``aot_compile.py``'s ``decode`` with the cell's name: no chip is needed
and nothing runs.  The engine is built for real in host memory (6.9 GB of
seeded weights, twice while they move, 4.8 GB of recurrent state and 1.2
GB of pools at 96 lanes), so give it several minutes and 30 GB.  With
``lanes`` the cell's lane count is overridden for this compile only: how
the cell was sized.  With ``text_dir`` each compiled program's text is
written there.

What to read in the output: the per-device bytes (they have to leave room
on a 16 GB chip), ``tpu_custom_call`` (``paged_decode`` once an attention
layer in the decode program; the recurrent update is one XLA fusion a
Mamba layer, ``state updates``), and ``whole-array copies``: a ``copy``
whose result has the shape of a layer's recurrent state or of a page pool
means the carry is not in place.
"""

import re
import sys
from pathlib import Path

import aot_compile
import harness


class _Compiled:
    """A lowered program that compiles once, for two readers."""

    def __init__(self, lowered):
        self.compiled = lowered.compile()

    def compile(self):
        return self.compiled


def _whole_copies(text, shapes):
    """``copy`` instructions whose result is one of ``shapes`` (an HLO
    shape's head, as ``f32[96,64,64,128]``)."""
    found = {}
    for shape in shapes:
        pattern = re.compile(r"= " + re.escape(shape) + r"[^ ]* copy\(")
        found[shape] = sum(1 for line in text.splitlines() if pattern.search(line))
    return found


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
    cell = "nemotron_group_rollout"
    workload = harness.load_json("workloads", cell)
    cfg = harness.load_json("configs", workload["config"])
    L = int(workload["params"]["lanes"])
    channels = cfg["mamba_num_heads"] * cfg["mamba_head_dim"] + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    state = (
        f"f32[{L},{cfg['mamba_num_heads']},{cfg['mamba_head_dim']},{cfg['ssm_state_size']}]",
        f"f32[{L},{cfg['conv_kernel'] - 1},{channels}]",
    )
    report = aot_compile._report

    def report_and_look(name, lowered, t0):
        once = _Compiled(lowered)
        per_device = report(name, once, t0)
        text = once.compiled.as_text()
        pools = sorted(set(re.findall(r"f32\[\d+,8," + str(cfg["num_key_value_heads"] * cfg["head_dim"]) + r"\]", text)))
        print(f"  whole-array copies: {_whole_copies(text, state + tuple(pools))}", flush=True)
        updates = [
            line for line in text.splitlines()
            if re.search(r"= \(.*" + re.escape(state[0]) + r".*\) fusion\(.*ssm_decode_update", line)
        ]
        print(f"  state updates (fusions that give the state beside y): {len(updates)}", flush=True)
        if text_dir is not None:
            text_dir.mkdir(parents=True, exist_ok=True)
            (text_dir / (re.sub(r"[^A-Za-z0-9]+", "_", name) + ".txt")).write_text(text)
        return per_device

    aot_compile._report = report_and_look
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    aot_compile.decode(topo, cell=cell)


if __name__ == "__main__":
    main(sys.argv[1:])
