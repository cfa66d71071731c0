"""Orbax checkpointing of train-state pytrees.

Parity target: per-agent ``save_checkpoint``/``load_checkpoint``
(``scalerl/algorithms/dqn/dqn_agent.py:210-233``, interface
``algorithms/base.py:102-116``) and IMPALA's periodic checkpoints
(``impala_atari.py:496-515``), upgraded to Orbax: atomic directory writes,
async-friendly, and shard-aware for multi-host meshes (the reference's
``torch.save`` has none of these).

Crash-safety contract (the supervision layer leans on this):

- a save NEVER has a window where no complete checkpoint exists on disk:
  the new state lands in ``path.tmp`` first, the previous checkpoint is
  *retained* as ``path.prev`` (…``path.prevK`` up to ``keep_last``) while the
  new one swaps in — never deleted before the swap;
- a restore that finds the latest dir corrupt/partial (a preemption mid-swap,
  a torn filesystem) falls back through the retained ``.prev`` chain instead
  of failing the run.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from scalerl_tpu.utils.logging import get_logger

logger = get_logger(__name__)

# per-leaf digest manifest written INSIDE every checkpoint dir; orbax
# ignores foreign files, and the manifest travels with the dir through the
# .prev rotation for free
MANIFEST_NAME = "integrity_manifest.json"


class CheckpointIntegrityError(RuntimeError):
    """Restored leaves do not match the manifest digests (silent corruption
    orbax cannot see — a flipped bit in a data file still parses)."""


def _leaf_digest(leaf: Any) -> str:
    arr = np.ascontiguousarray(np.asarray(jax.device_get(leaf)))
    h = hashlib.sha256()
    h.update(str((arr.dtype.str, arr.shape)).encode())
    h.update(arr.tobytes())
    return h.hexdigest()


def _tree_digests(state: Any) -> List[Dict[str, str]]:
    """Per-leaf sha256 digests, with save-time key paths for diagnostics.

    Verification compares the digest MULTISET, not the paths: a restore
    without a ``target`` materializes container types (dicts) different
    from the saved dataclasses, which reorders/renames paths while the leaf
    bytes — the thing integrity is about — are unchanged.
    """
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        out.append({"path": jax.tree_util.keystr(path), "sha256": _leaf_digest(leaf)})
    return out


def write_manifest(path: str, state: Any) -> str:
    manifest = {"format": 1, "leaves": _tree_digests(state)}
    target = os.path.join(path, MANIFEST_NAME)
    with open(target, "w") as f:
        json.dump(manifest, f, indent=1)
    return target


def verify_manifest(path: str, restored: Any) -> None:
    """Raise :class:`CheckpointIntegrityError` if ``restored`` does not
    reproduce the digests recorded at save time.  Checkpoints predating the
    manifest (no file) pass — upgrade compatibility."""
    mpath = os.path.join(path, MANIFEST_NAME)
    if not os.path.exists(mpath):
        return
    try:
        with open(mpath) as f:
            manifest = json.load(f)
        expected = sorted(leaf["sha256"] for leaf in manifest["leaves"])
    except (ValueError, KeyError, TypeError) as e:
        raise CheckpointIntegrityError(
            f"unreadable integrity manifest at {mpath}: {e}"
        ) from e
    actual = sorted(d["sha256"] for d in _tree_digests(restored))
    if expected != actual:
        bad = len(set(expected).symmetric_difference(actual))
        raise CheckpointIntegrityError(
            f"checkpoint {path} failed digest verification: "
            f"{bad} leaf digest(s) differ from the save-time manifest"
        )


def _prev_path(path: str, k: int) -> str:
    """k-th displaced checkpoint: ``path.prev``, ``path.prev2``, ..."""
    return path + (".prev" if k == 1 else f".prev{k}")


def checkpoint_fallbacks(path: str) -> List[str]:
    """Existing retained predecessors of ``path``, newest first."""
    out: List[str] = []
    k = 1
    while True:
        p = _prev_path(path, k)
        if not os.path.exists(p):
            break
        out.append(p)
        k += 1
    return out


def save_checkpoint(path: str, state: Any, keep_last: int = 1) -> str:
    """Save a pytree to ``path`` (write-new-then-rotate). Returns the path.

    The full save lands in a ``.tmp`` sibling first; the previous checkpoint
    is then ROTATED to ``path.prev`` (not deleted) before the atomic
    ``rename(tmp, path)``, so every instant of the sequence has at least one
    complete checkpoint on disk — a preemption mid-save costs nothing, and a
    corrupt latest restores from ``.prev`` (``load_checkpoint`` falls back
    automatically).

    ``keep_last``: how many displaced checkpoints to retain
    (``path.prev`` … ``path.prevN``); 0 deletes the predecessor after the
    new checkpoint has landed (still no unprotected window — the delete
    happens strictly after the rename).
    """
    # orbax is imported where a checkpoint is written or read, not with
    # this module: its import pulls google.cloud.logging, whose packages
    # each walk every installed distribution's file list, 9 s of every
    # process start on the chip's host and 10-25 s more after some import
    # orders (PERF.md, PR 28).  A loop that saves at a preemption imports it
    # at set-up (BaseTrainer.install_preemption_guard)
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    tmp = path + ".tmp"
    checkpointer = ocp.StandardCheckpointer()
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    checkpointer.save(tmp, state)
    checkpointer.wait_until_finished()
    # per-leaf digest manifest INSIDE the dir (before the atomic rename, so
    # a checkpoint is never visible without its manifest): load_checkpoint
    # verifies restored bytes against it and falls back through .prev on a
    # mismatch — deterministic corruption detection, not "hope orbax raises"
    write_manifest(tmp, state)
    # rotate the retention chain oldest-first so each rename target is free
    if os.path.exists(path):
        oldest = _prev_path(path, max(keep_last, 1))
        if os.path.exists(oldest):
            shutil.rmtree(oldest)
        for k in range(max(keep_last, 1) - 1, 0, -1):
            src = _prev_path(path, k)
            if os.path.exists(src):
                os.rename(src, _prev_path(path, k + 1))
        os.rename(path, _prev_path(path, 1))
    os.rename(tmp, path)
    if keep_last <= 0:
        prev = _prev_path(path, 1)
        if os.path.exists(prev):
            shutil.rmtree(prev)
    inj = _chaos_active()
    if inj is not None:
        # chaos: leave the freshly-landed checkpoint partial (a preemption
        # mid-flush) — restores must fall back through the .prev chain
        inj.corrupt_checkpoint(path)
    _telemetry().record_event("checkpoint_save", path=path)
    _telemetry().get_registry().counter("checkpoint.saves").inc()
    return path


def load_checkpoint(
    path: str, target: Optional[Any] = None, fallback: bool = True
) -> Any:
    """Restore a pytree from ``path``; ``target`` provides structure/dtypes.

    ``fallback``: when the latest checkpoint is corrupt or partial (restore
    raises), fall back through the retained ``path.prev`` chain — the
    preemption-safety contract of ``save_checkpoint``.  The original error
    is chained if every candidate fails.
    """
    path = os.path.abspath(path)
    candidates = [path] + (checkpoint_fallbacks(path) if fallback else [])
    first_err: Optional[Exception] = None
    for cand in candidates:
        try:
            restored = _restore(cand, target)
            _telemetry().record_event(
                "checkpoint_restore", path=cand, fallback=cand != path
            )
            _telemetry().get_registry().counter("checkpoint.restores").inc()
            return restored
        except Exception as e:  # noqa: BLE001 — try the retained predecessor
            if first_err is None:
                first_err = e
            if fallback and cand != candidates[-1]:
                _telemetry().record_event(
                    "checkpoint_fallback", path=cand, error=repr(e)
                )
                _telemetry().get_registry().counter("checkpoint.fallbacks").inc()
                logger.warning(
                    "checkpoint %s failed to restore (%r); falling back to %s",
                    cand, e, candidates[candidates.index(cand) + 1],
                )
    assert first_err is not None
    raise first_err


def _restore(path: str, target: Optional[Any]) -> Any:
    import orbax.checkpoint as ocp

    checkpointer = ocp.StandardCheckpointer()
    if target is not None:
        abstract = jax.tree_util.tree_map(ocp.utils.to_shape_dtype_struct, target)
        restored = checkpointer.restore(path, abstract)
    else:
        restored = checkpointer.restore(path)
    verify_manifest(path, restored)
    return restored


def _chaos_active():
    from scalerl_tpu.runtime import chaos

    return chaos.active()


def _telemetry():
    # lazy: keep jax-free importers of runtime.telemetry from paying for
    # orbax, and this module from importing telemetry at module load
    from scalerl_tpu.runtime import telemetry

    return telemetry
