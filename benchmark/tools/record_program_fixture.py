"""Record the small device trace that ``program_trace.py`` is checked on.

    chiprun --chips 1 -- python benchmark/tools/record_program_fixture.py

Runs on the chip only.  Three steps of a toy loop shaped like the
program's hot paths: a dispatch of two differently named Pallas kernels
(``toy_fwd``, ``toy_bwd``, written here) and a matmul, a host sleep, then
a blocking read, each phase under the program's own ``tracing.span`` (so
the ``scalerl.*`` annotations are the ones the program makes, through the
hook ``utils/profiling.py`` installs), each step inside a ``bench.step``
annotation, with a sleep under no program span between the steps.  Writes
the ``.xplane.pb`` and a text dump to ``chiprun_out/fixture/``; the dump
is what the expected numbers in the test were worked out from.
"""

from __future__ import annotations

import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "benchmark"))
sys.path.insert(0, str(ROOT))


def main() -> None:
    import jax

    jax.config.update("jax_platforms", "tpu")
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    import program_trace
    import trace_reduce
    from scalerl_tpu.runtime import tracing
    from scalerl_tpu.utils import profiling  # noqa: F401  (installs the annotator)

    out = ROOT / "chiprun_out" / "fixture"
    out.mkdir(parents=True, exist_ok=True)

    def add_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] + 1.0

    def kernel(name, x):
        return pl.pallas_call(
            add_kernel, name=name, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype)
        )(x)

    @jax.jit
    def toy_step(small, big):
        with jax.named_scope("block_7"):  # as a flax module would scope it
            y = kernel("toy_fwd", small)
            z = jnp.tanh(big @ big) * 0.01
            return kernel("toy_bwd", kernel("toy_bwd", y)), z

    small = jnp.ones((512, 512), jnp.float32)
    big = jnp.ones((2048, 2048), jnp.float32)
    jax.block_until_ready(toy_step(small, big))

    trace_dir = out / "program_trace"
    shutil.rmtree(trace_dir, ignore_errors=True)
    trace_reduce.start_trace(str(trace_dir))
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                with tracing.span("toy.step"):
                    with tracing.span("toy.dispatch"):
                        result = toy_step(small, big)
                    time.sleep(0.002)  # the step's own time
                    with tracing.span("toy.read"):
                        jax.block_until_ready(result)
            time.sleep(0.001)  # no program span open
    path = trace_reduce.stop_trace(str(trace_dir))
    shutil.copy(path, out / "program_spans.xplane.pb")

    lines = trace_reduce.dump(path)
    (out / "program_spans.dump.txt").write_text("\n".join(lines) + "\n")
    shown = (" bench.", " scalerl.", " DoEnqueueProgram", " %", " jit_")
    print("\n".join(
        line for line in lines if not line.startswith("    ") or any(w in line for w in shown)
    ))
    print("\n".join(program_trace.report(program_trace.reduce(path))))
    print("reduced:", trace_reduce.reduce_trace(path))


if __name__ == "__main__":
    main()
