"""The rollout cells' lowered programs as text with source locations taken
out, for "is this commit's program the parent's?".

    JAX_PLATFORMS=cpu python benchmark/tools/program_texts.py <checkout> <out_dir> [cell ...]

Lowers (does not compile) each named rollout cell's decode macro-step and
widest prefill for a described ``v5e:2x2`` through ``<checkout>``'s own
``benchmark/aot_compile.py`` ``decode`` (default cells:
``gpt2m_group_rollout``, ``olmoe_group_rollout``), writes the StableHLO
text under ``<out_dir>`` and prints one digest a program.  A lowered
module's text carries no source location of its own, but a Mosaic kernel's
body inside ``tpu_custom_call``'s ``backend_config`` does (PERF.md,
section 7): each body is parsed and replaced by the digest of its MLIR
printed without debug info, so two checkouts of one program give one
digest.  Run it on two checkouts and compare the lines.
"""

from __future__ import annotations

import base64
import hashlib
import os
import re
import sys


def _strip_kernel_locations(text: str) -> str:
    from jax._src.interpreters import mlir as jmlir
    from jax._src.lib.mlir import ir

    def body(match):
        ctx = jmlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            module = ir.Module.parse(base64.b64decode(match.group(1)))
            asm = module.operation.get_asm(enable_debug_info=False)
        return '\\22body\\22: \\22' + hashlib.sha256(asm.encode()).hexdigest() + '\\22'

    return re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', body, text)


def main(argv):
    root, out = os.path.abspath(argv[0]), os.path.abspath(argv[1])
    cells = argv[2:] or ["gpt2m_group_rollout", "olmoe_group_rollout"]
    os.makedirs(out, exist_ok=True)
    os.chdir(root)
    sys.path[:0] = [os.path.join(root, "benchmark"), root]
    import aot_compile
    from jax.experimental import topologies

    def report(name, lowered, _t0):
        text = _strip_kernel_locations(lowered.as_text())
        path = os.path.join(out, re.sub(r"[^A-Za-z0-9]+", "_", name)[:80] + ".txt")
        with open(path, "w") as f:
            f.write(text)
        print(hashlib.sha256(text.encode()).hexdigest()[:16], name, flush=True)
        return 0

    aot_compile._report = report
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    for cell in cells:
        aot_compile.decode(topo, cell=cell)


if __name__ == "__main__":
    main(sys.argv[1:])
