"""Chip-only test suite: everything here exists to exercise *compiled* TPU
execution — Pallas kernel tiling/VMEM legality, bf16 numerics on the MXU,
donation and the transfer guard on real device buffers — which interpret
mode on the CPU cannot validate.

Deliberately separate from ``tests/`` (whose conftest pins the CPU
backend).  Run it on the chip, in one process:

    python -m pytest tests_tpu -q

A suite whose only purpose is the chip FAILS without one: the backend is
pinned to ``tpu`` through :func:`scalerl_tpu.utils.platform.setup_platform`
(which also places the persistent compilation cache, the same way
``chip_smoke.py`` does), so a missing chip is JAX's own initialization
error on every test, never a skip.
"""

import pytest


def pytest_collection_modifyitems(config, items):
    for item in items:
        item.add_marker(pytest.mark.tpu)


@pytest.fixture(scope="session", autouse=True)
def _tpu_backend():
    from scalerl_tpu.utils.platform import setup_platform

    backend = setup_platform("tpu")
    assert backend == "tpu", f"tests_tpu needs a TPU backend, got {backend!r}"
