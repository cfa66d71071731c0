"""Platform selection honoring ``RLArguments.platform``, and the one place
the persistent compilation cache is configured.

``JAX_PLATFORMS`` works as JAX documents it; ``--platform cpu|tpu`` goes
through ``jax.config.update('jax_platforms', ...)`` *before* first backend
use, so it also overrides the environment for that process.
"""

from __future__ import annotations

import os
import sys
from typing import Mapping, Optional

# <repo>/.jax_cache, derived from this file's own location: the directory is
# part of the cache key, so it must not depend on the cwd, the user, a pid or
# a temporary name — two processes of one checkout always agree on it
_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def jax_runtime_initialized() -> bool:
    """True iff a JAX backend has been created in this process.

    Passive: never imports jax or triggers backend init itself (backend
    init opens the accelerator, which then belongs to this process).  Used
    to decide the multiprocessing start method — forking after XLA has
    started its thread pools clones held mutexes into the child, which can
    deadlock (the reference never hits this: torch tolerates fork; JAX
    does not).
    """
    if "jax" not in sys.modules:
        return False
    try:
        from jax._src import xla_bridge as xb

        return bool(xb._backends)
    except Exception:  # noqa: BLE001 — jax-internals drift: assume not init
        return False


def safe_mp_context(requested: Optional[str] = None) -> Optional[str]:
    """Resolve a multiprocessing start-method name.

    Explicit ``requested`` always wins.  Otherwise: ``"spawn"`` when a JAX
    backend already lives in this process (fork would be unsafe — see
    ``jax_runtime_initialized``), else ``None`` (the platform default,
    fork on Linux, which is cheapest when no runtime is at risk).
    Call sites must keep worker targets/runners picklable so the spawn
    path works when it triggers.
    """
    if requested is not None:
        return requested
    return "spawn" if jax_runtime_initialized() else None


def compilation_cache_dir(
    environ: Optional[Mapping[str, str]] = None,
) -> Optional[str]:
    """The directory this code must set for JAX's persistent compilation
    cache, or ``None`` when it must set nothing.

    JAX reads ``JAX_COMPILATION_CACHE_DIR`` into ``jax_compilation_cache_dir``
    by itself, so when the variable is set the cache is placed from outside
    and the code stays out of the way.  Otherwise the cache lives at the one
    fixed in-checkout path ``<repo>/.jax_cache`` (git-ignored).
    """
    environ = os.environ if environ is None else environ
    if environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return _DEFAULT_CACHE_DIR


def setup_platform(platform: str = "auto") -> str:
    """Pin the JAX backend. Call before any jax array/computation is created.

    ``auto`` keeps JAX's default (TPU when present, else CPU); an explicit
    platform that cannot initialize is JAX's hard error — measurement paths
    (``benchmark/run.py``, ``chip_smoke.py``, ``tests_tpu``) pass ``"tpu"`` for
    exactly that reason.  Returns the backend actually in use.

    On accelerator backends this also places JAX's persistent compilation
    cache (:func:`compilation_cache_dir`): first compiles of the fused loop
    take tens of seconds and every entry script re-traces the same
    programs, so relaunch compiles become disk reads.  JAX's default
    min-compile-time threshold (~1 s) stays: the expensive programs clear
    it and trivial ones don't bloat the directory.  CPU is deliberately
    excluded: XLA:CPU caches AOT machine code whose recorded target
    features can mismatch the loading host (the loader warns about
    possible SIGILL).
    """
    import jax

    if platform and platform != "auto":
        jax.config.update("jax_platforms", platform)
    backend = jax.default_backend()
    if backend in ("tpu", "gpu"):
        cache_dir = compilation_cache_dir()
        if cache_dir is not None:
            jax.config.update("jax_compilation_cache_dir", cache_dir)
    return backend
