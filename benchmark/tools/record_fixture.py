"""Record the small device trace that ``trace_reduce.py`` is checked on.

    chiprun --chips 1 -- python benchmark/tools/record_fixture.py

Runs on the chip only.  A few XLA matmuls, one Mosaic kernel (a Pallas
``x + 1`` written here, so the fixture depends on no kernel of the
program), and host sleeps between them under ``bench.*`` annotations, so
that the trace has busy spans, a Mosaic custom call and idle gaps whose
owners are known.  Writes the ``.xplane.pb`` and a plain-text dump of
every device event to ``chiprun_out/fixture/``; the dump is what the
expected numbers in the test were worked out from.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "benchmark"))


def main() -> None:
    import jax

    jax.config.update("jax_platforms", "tpu")
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    import trace_reduce

    out = ROOT / "chiprun_out" / "fixture"
    shutil.rmtree(out, ignore_errors=True)
    out.mkdir(parents=True)

    def add_one_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] + 1.0

    @jax.jit
    def mosaic_add_one(x):
        return pl.pallas_call(
            add_one_kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype)
        )(x)

    @jax.jit
    def matmuls(x):
        for _ in range(3):
            x = jnp.tanh(x @ x) * 0.01
        return x

    x = jnp.ones((2048, 2048), jnp.float32)
    small = jnp.ones((512, 512), jnp.float32)
    matmuls(x).block_until_ready()
    mosaic_add_one(small).block_until_ready()

    trace_dir = out / "trace"
    trace_reduce.start_trace(str(trace_dir))
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.alpha"):
            matmuls(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.sleep"):
            time.sleep(0.004)
        with jax.profiler.TraceAnnotation("bench.beta"):
            mosaic_add_one(small).block_until_ready()
            matmuls(x).block_until_ready()
        time.sleep(0.002)
        with jax.profiler.TraceAnnotation("bench.gamma"):
            mosaic_add_one(small).block_until_ready()
    window_s = time.perf_counter() - t0
    path = trace_reduce.stop_trace(str(trace_dir))
    print("trace file:", path, os.path.getsize(path), "bytes")
    shutil.copy(path, out / "small.xplane.pb")

    lines = trace_reduce.dump(path)
    (out / "small.dump.txt").write_text("\n".join(lines) + "\n")
    print("\n".join(lines[:200]))
    reduced = trace_reduce.reduce_trace(path, span_prefix="bench.")
    print("window_s host clock:", window_s)
    print("reduced:", reduced)
    print("files:", [os.path.basename(p) for p in glob.glob(str(out / "*"))])


if __name__ == "__main__":
    main()
