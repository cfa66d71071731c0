"""Process-rank-aware colored logging.

Capability parity with the reference's OpenMMLab-derived logger
(``scalerl/utils/logger/logging.py:30-110``, duplicated at
``scalerl/utils/logger_utils.py:29-110`` — the duplication is not carried
over): colored stream output, rank-0-only file handlers, and non-zero ranks
silenced to ERROR.  Rank here is the JAX process index (multi-host DCN), not a
torch.distributed rank.
"""

from __future__ import annotations

import logging
import os
import sys
from typing import Dict, Optional

_initialized_loggers: Dict[str, logging.Logger] = {}

_COLORS = {
    logging.DEBUG: "\x1b[36m",  # cyan
    logging.INFO: "\x1b[32m",  # green
    logging.WARNING: "\x1b[33m",  # yellow
    logging.ERROR: "\x1b[31m",  # red
    logging.CRITICAL: "\x1b[35m",  # magenta
}
_RESET = "\x1b[0m"


class _ColorFormatter(logging.Formatter):
    def __init__(self, use_color: bool = True) -> None:
        super().__init__("%(asctime)s - %(name)s - %(levelname)s - %(message)s")
        self.use_color = use_color

    def format(self, record: logging.LogRecord) -> str:
        msg = super().format(record)
        if self.use_color:
            color = _COLORS.get(record.levelno, "")
            if color:
                msg = f"{color}{msg}{_RESET}"
        return msg


def process_index() -> int:
    """Current distributed process index (0 on single-host).

    Deliberately does NOT force JAX backend initialization:
    ``get_logger`` runs at module-import time all over the package, and
    ``jax.process_index()`` would spin up the device runtime — and with it
    take the chip, which belongs to one process at a time, in processes
    that never meant to use it.  If no backend exists yet, the multihost
    process id — when
    ``jax.distributed`` was initialized — or the env override decides.
    """
    # env override wins (also the escape hatch if the private-API probes
    # below break on a jax upgrade — they are each isolated so a rename
    # degrades to the next probe, never to an exception)
    env = os.environ.get("SCALERL_PROCESS_INDEX")
    if env is not None:
        return int(env)
    if "jax" not in sys.modules:
        # jax was never imported, so neither jax.distributed nor a backend
        # can be initialized — and importing jax here would charge every
        # jax-free fleet/disagg child the multi-second package import just
        # to learn the answer is 0
        return 0
    try:  # multihost: jax.distributed.initialize() recorded a process id
        from jax._src import distributed

        pid = getattr(distributed.global_state, "process_id", None)
        if pid:  # 0 is also the uninitialized default -> fall through
            return int(pid)
    except Exception:  # pragma: no cover - private-API drift
        pass
    try:  # backend already up -> querying it is cheap and safe
        import jax
        from jax._src import xla_bridge

        if getattr(xla_bridge, "_backends", None):
            return jax.process_index()
    except Exception:  # pragma: no cover - private-API drift
        pass
    return 0


def get_logger(
    name: str = "scalerl_tpu",
    log_file: Optional[str] = None,
    log_level: int = logging.INFO,
) -> logging.Logger:
    """Return a logger writing colored stream output; file output on rank 0 only.

    Non-zero ranks are raised to ERROR so a multi-host run logs once
    (reference behavior: ``logger/logging.py:95-102``).
    """
    logger = logging.getLogger(name)
    if name in _initialized_loggers:
        return logger
    logger.propagate = False

    stream = logging.StreamHandler(sys.stderr)
    stream.setFormatter(_ColorFormatter(use_color=sys.stderr.isatty()))
    handlers: list[logging.Handler] = [stream]

    rank = process_index()
    if rank == 0 and log_file is not None:
        os.makedirs(os.path.dirname(log_file) or ".", exist_ok=True)
        fh = logging.FileHandler(log_file, "a")
        fh.setFormatter(_ColorFormatter(use_color=False))
        handlers.append(fh)

    level = log_level if rank == 0 else logging.ERROR
    for h in handlers:
        h.setLevel(level)
        logger.addHandler(h)
    logger.setLevel(level)
    _initialized_loggers[name] = logger
    return logger
