"""Compile ``joyai_packed_learn``'s learn step for a described ``v5e:2x2``
and print ``memory_analysis()``.

    JAX_PLATFORMS=cpu python benchmark/aot_joyai.py [rows ...]

``aot_compile.py``'s learn-step compile with the cell's name: no chip is
needed and nothing runs.  The learner is built for real in host memory
(491.6 M parameters: the bfloat16 weights, their frozen copy and two
float32 Adam moments, about 6 GB), so give it a few minutes.  With
``rows`` the step is compiled at those row counts instead of the cell's
own: how the cell was sized (4 rows if that leaves half a gigabyte of the
chip's 15.75, else 2).
"""

import sys

import aot_compile
import harness


def main(argv):
    from jax.experimental import topologies

    cell = "joyai_packed_learn"
    rows = [int(a) for a in argv] or [int(harness.load_json("workloads", cell)["params"]["rows_per_step"])]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    aot_compile._learn(topo, cell, 1, rows)


if __name__ == "__main__":
    main(sys.argv[1:])
