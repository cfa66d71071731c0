"""Compile ``longcat_group_rollout``'s decode macro-step and widest prefill
for a described ``v5e:2x2`` and print ``memory_analysis()``.

    JAX_PLATFORMS=cpu python benchmark/aot_longcat.py [lanes]

``aot_compile.py``'s ``decode`` with the cell's name: no chip is needed
and nothing runs.  The engine is built for real in host memory (10.5 GB of
seeded weights, twice while they move, and 2.7 GB of pools), so give it
several minutes and 30 GB.  With ``lanes`` the cell's lane count is
overridden for this compile only: how the cell was sized.
"""

import sys

import aot_compile
import harness


def main(argv):
    from jax.experimental import topologies

    if argv:
        load_json = harness.load_json

        def with_lanes(kind, name):
            loaded = load_json(kind, name)
            if kind == "workloads":
                loaded["params"]["lanes"] = int(argv[0])
            return loaded

        harness.load_json = with_lanes
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    aot_compile.decode(topo, cell="longcat_group_rollout")


if __name__ == "__main__":
    main(sys.argv[1:])
