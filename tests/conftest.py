"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip sharding tests run on a simulated mesh via
``--xla_force_host_platform_device_count=8`` (SURVEY.md §4's prescription),
so the full dp/mesh path executes on any machine.

``JAX_PLATFORMS=cpu`` (the tier-1 command sets it) works as documented;
``jax.config.update('jax_platforms', 'cpu')`` below pins the CPU as well, so
the suite stays off the chip on a machine that has one even when the
variable is missing.  The chip is exercised by ``tests_tpu/`` and
``chip_smoke.py``, never from here.
"""

import faulthandler
import os

# Hang diagnosis for the WHOLE suite: crashes (SIGSEGV etc.) dump all-thread
# stacks, and per-test stall dumps come from pytest's faulthandler plugin
# (``faulthandler_timeout`` in pytest.ini).  pytest enables faulthandler for
# its own run; this covers spawned helpers that import conftest and any
# runner invoking the tests without the plugin.
faulthandler.enable()

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Opt-in runtime sanitizer (docs/LINTING.md): SCALERL_SANITIZE=1 turns on
# jax's tracer-leak checking (JG004's runtime twin — leaked tracers raise at
# the leak site instead of exploding later) and NaN debugging (re-runs the
# offending primitive un-jitted and points at it) for the whole fast suite.
# Off by default: both disable async dispatch and slow the suite down.
if os.environ.get("SCALERL_SANITIZE") == "1":
    jax.config.update("jax_check_tracer_leaks", True)
    jax.config.update("jax_debug_nans", True)

assert jax.default_backend() == "cpu", (
    "tests must run on CPU; got " + jax.default_backend()
)
assert len(jax.devices()) == 8, "expected 8 virtual CPU devices"

# Files that take minutes and sort last by name: under ``--dist loadfile`` a
# file is one worker's from start to end and files are handed out in
# collection order, so such a file would start when the others are nearly
# done and run on alone.  They go first; every other file keeps its place.
_STARTS_FIRST = ("test_zaya_block.py", "test_xing4_block.py")


def pytest_collection_modifyitems(items):
    items.sort(key=lambda item: item.path.name not in _STARTS_FIRST)
