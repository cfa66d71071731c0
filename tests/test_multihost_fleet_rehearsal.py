"""Combined multi-host rehearsal: mesh learner + TCP actor fleet together.

The v5e-16 production topology in miniature (VERDICT r2 #9): TWO
``jax.distributed`` CPU processes form one global 2-device mesh (the ICI/
DCN collective plane), and EACH rank simultaneously hosts a
``WorkerServer`` + ``RemoteCluster`` actor fleet over localhost TCP (the
DCN control/data plane, ``fleet/cluster.py`` — parity:
``scalerl/hpc/worker.py:269-341``).  Until now the two planes were only
tested separately (``test_multihost.py``, ``test_fleet.py``).

Each rank drains real rollout results from its own fleet into its local
batch shard, runs a ``psum``-synchronized learn step over the global mesh
(``shard_map`` over ``dp``), and publishes the updated weights back to its
fleet — weights flow learner -> server -> gather -> worker over TCP while
gradients flow rank <-> rank over the distributed runtime, in the same
process, at the same time.

Asserts: results arrived on both ranks, final params are bitwise-identical
across ranks (the cross-host psum really synchronized), and late rollouts
report a bumped ``param_version`` (workers really pulled republished
weights mid-run).
"""

import socket
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from tests.multihost_support import multiprocess_cpu_unsupported  # noqa: E402

# without multi-process CPU collectives this rehearsal burned its whole
# 270 s subprocess budget (the surviving rank idles at the first psum
# after its peer dies); the cached probe skips cleanly instead
pytestmark = pytest.mark.skipif(
    bool(multiprocess_cpu_unsupported()),
    reason=multiprocess_cpu_unsupported() or "",
)

_RANK = textwrap.dedent(
    """
    import os, sys, time

    sys.path.insert(0, {repo!r})
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax import shard_map
    from jax.experimental.multihost_utils import process_allgather

    from scalerl_tpu.parallel.multihost import initialize_multihost
    from scalerl_tpu.fleet import FleetConfig, RemoteCluster, WorkerServer
    from tests.fleet_rehearsal_helpers import (
        FEATURE_DIM, CountingTaskSource, bandit_runner,
    )

    # ---- plane 1: the global device mesh over 2 processes (DCN collectives)
    assert initialize_multihost(
        coordinator_address={coord!r}, num_processes=2, process_id={pid}
    )
    assert jax.process_count() == 2 and jax.device_count() == 2
    mesh = Mesh(np.array(jax.devices()), ("dp",))
    shard = NamedSharding(mesh, P("dp"))

    # ---- plane 2: this rank's own actor fleet over localhost TCP
    config = FleetConfig(
        num_workers=2, workers_per_gather=2, upload_batch=1,
        entry_port={entry_port}, worker_port={worker_port},
    )
    server = WorkerServer(
        config, CountingTaskSource(lambda: server.params.version)
    )
    w_host = np.zeros(FEATURE_DIM, np.float32)
    server.publish({{"w": w_host}})
    server.start(listen=True)
    cluster = RemoteCluster(config, bandit_runner)
    cluster.start()

    def drain(n, timeout=90.0):
        out, deadline = [], time.monotonic() + timeout
        while len(out) < n and time.monotonic() < deadline:
            r = server.get_result(timeout=0.2)
            if r is not None:
                out.append(r)
        assert len(out) == n, f"rank {pid}: fleet produced {{len(out)}}/{{n}}"
        return out

    # ---- the combined loop: fleet rollouts -> sharded batch -> psum step
    PER_RANK = 4

    def step(w, X, y):
        pred = X @ w
        g = X.T @ (pred - y) / (2.0 * y.size)  # global batch = 2*local
        g = jax.lax.psum(g, "dp")              # <- crosses the process boundary
        return w - 0.5 * g

    learn = jax.jit(
        shard_map(step, mesh=mesh, in_specs=(P(), P("dp"), P("dp")),
                  out_specs=P())
    )

    w = jnp.asarray(w_host)
    results = []
    for it in range(3):
        batch = drain(PER_RANK)
        results.extend(batch)
        X_local = np.stack([r["features"] for r in batch])
        y_local = np.ones(PER_RANK, np.float32)  # regress reward -> 1.0
        X = jax.make_array_from_process_local_data(shard, X_local)
        y = jax.make_array_from_process_local_data(shard, y_local)
        w = learn(w, X, y)
        # w is replicated over the global mesh (out_specs=P()); the local
        # device holds a full copy — fetch that (device_get on a global,
        # non-fully-addressable array is not allowed)
        w_host = np.asarray(w.addressable_data(0)).astype(np.float32)
        server.publish({{"w": w_host}})  # learner -> fleet weight pub

    # workers pull republished weights: task generation outruns the learn
    # loop (workers mint tasks continuously), so keep draining until a
    # result minted after a republish arrives — its task carried the newer
    # wanted version, forcing the worker's params re-pull over TCP
    versions = set(r.get("param_version", 0) for r in results)
    deadline = time.monotonic() + 60.0
    while max(versions) < 2 and time.monotonic() < deadline:
        r = server.get_result(timeout=0.2)
        if r is not None:
            versions.add(r.get("param_version", 0))
    assert max(versions) >= 2, sorted(versions)

    cluster.join()
    server.stop()

    # params synchronized across hosts: every rank ends bitwise-identical
    gathered = process_allgather(w_host)  # host copies, stacked per process
    np.testing.assert_array_equal(
        np.asarray(gathered[0]), np.asarray(gathered[1])
    )
    print(f"proc {pid} OK versions={{sorted(versions)}}", flush=True)
    """
)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.slow
def test_mesh_learner_plus_tcp_fleet_rehearsal():
    coord = f"127.0.0.1:{_free_port()}"
    procs = [
        subprocess.Popen(
            [
                sys.executable,
                "-c",
                _RANK.format(
                    repo=str(REPO),
                    coord=coord,
                    pid=pid,
                    entry_port=_free_port(),
                    worker_port=_free_port(),
                ),
            ],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )
        for pid in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=270)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {pid} failed:\n{out}"
        assert f"proc {pid} OK" in out
