"""Multi-host (DCN) bring-up: the fleet-rendezvous capability, JAX-native.

The reference bootstraps a multi-node fleet with a hand-rolled TCP entry
handshake on port 9999 (``scalerl/hpc/worker.py:300-341``: worker sends its
arg dict, server assigns a base worker id and returns the full config).
For the *mesh* itself JAX ships this: ``jax.distributed.initialize`` against
a coordinator address enrolls every host's chips into one global device
set.  Off-mesh CPU actor fleets still use the explicit transport in
``scalerl_tpu.runtime`` (the hpc-protocol parity lives there).
"""

from __future__ import annotations

import os
from typing import Optional

import jax

from scalerl_tpu.utils.logging import get_logger

logger = get_logger(__name__)


def initialize_multihost(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    local_device_ids: Optional[list] = None,
) -> bool:
    """Join the global JAX runtime; returns True if distributed init ran.

    All-``None`` args fall back to env autodetection (TPU pod metadata or
    ``JAX_COORDINATOR_ADDRESS``/``JAX_NUM_PROCESSES``/``JAX_PROCESS_ID``),
    and a plain single-host run is a no-op — so trainers can call this
    unconditionally, the way the reference calls ``Accelerator()``
    unconditionally (``examples/test_dqn.py:17``).
    """
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS"
    )
    if num_processes is None and os.environ.get("JAX_NUM_PROCESSES"):
        num_processes = int(os.environ["JAX_NUM_PROCESSES"])
    if process_id is None and os.environ.get("JAX_PROCESS_ID"):
        process_id = int(os.environ["JAX_PROCESS_ID"])

    if coordinator_address is None and num_processes is None:
        # single-host (or TPU-pod autodetect handled by jax itself on real
        # pod slices); nothing to do.
        return False
    # CPU backends need an explicit cross-process collectives implementation:
    # without one the client forms (rendezvous succeeds, device_count sums)
    # but the FIRST multi-process computation dies with "Multiprocess
    # computations aren't implemented on the CPU backend".  Gloo ships in
    # jaxlib; select it before the backend initializes.  TPU/GPU runtimes
    # bring their own collectives and ignore this knob.
    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu") or (
        jax.config.jax_platforms or ""
    ).startswith("cpu"):
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        local_device_ids=local_device_ids,
    )
    logger.info(
        "multihost: process %d/%d, %d local / %d global devices",
        jax.process_index(),
        jax.process_count(),
        jax.local_device_count(),
        jax.device_count(),
    )
    return True
