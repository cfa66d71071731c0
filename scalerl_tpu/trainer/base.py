"""Trainer base: run-dir layout, logger wiring, main-process gating.

Parity target: ``BaseTrainer`` (``scalerl/trainer/base.py:26-179``): log-dir
layout ``work_dir/project/env/algo/{tb_log,text_log,model_dir}``, main-process
gating (JAX process index replaces ``accelerator.is_main_process``), and
TensorBoard-vs-W&B logger selection.
"""

from __future__ import annotations

import os
import time
from typing import Optional

from scalerl_tpu.config import RLArguments
from scalerl_tpu.utils.loggers import BaseLogger, make_logger
from scalerl_tpu.utils.logging import get_logger, process_index


class BaseTrainer:
    def __init__(self, args: RLArguments, run_name: Optional[str] = None) -> None:
        self.args = args
        self.is_main_process = process_index() == 0
        self.resuming = bool(getattr(args, "resume", ""))
        if self.resuming:
            # resume into the old run dir so tb events append and the resume
            # checkpoint under model_dir is found
            root = args.resume.rstrip("/")
            run_name = os.path.basename(root)
        else:
            stamp = time.strftime("%Y%m%d_%H%M%S")
            run_name = run_name or f"{args.algo_name}_{args.seed}_{stamp}"
            root = os.path.join(
                args.work_dir, args.project, args.env_id, args.algo_name, run_name
            )
        self.work_dir = root
        self.tb_log_dir = os.path.join(root, "tb_log")
        self.text_log_dir = os.path.join(root, "text_log")
        self.model_save_dir = os.path.join(root, "model_dir")
        self.video_dir = os.path.join(root, "video_dir")
        if self.is_main_process:
            for d in (self.tb_log_dir, self.text_log_dir, self.model_save_dir):
                os.makedirs(d, exist_ok=True)

        self.text_logger = get_logger(
            "scalerl_tpu",
            log_file=os.path.join(self.text_log_dir, f"{run_name}.log")
            if self.is_main_process
            else None,
        )
        if self.is_main_process and args.logger_backend != "none":
            self.logger: BaseLogger = make_logger(
                args.logger_backend,
                self.tb_log_dir,
                project=args.project,
                name=run_name,
                config=vars(args),
                train_interval=args.logger_frequency,
                update_interval=args.logger_frequency,
            )
        else:
            self.logger = make_logger("none", self.tb_log_dir)

        # telemetry plane: periodic JSONL + Prometheus exposition off the
        # process registry (runtime/telemetry.py); the same registry the
        # interval-gated logger backends read via log_registry.
        # telemetry_interval_s <= 0 is the FAST-OFF toggle: trainers gate
        # every registry write on self._instrument, so the instrument path
        # is compiled out of the hot loops, not skipped at runtime
        # (docs/PERFORMANCE.md "Guard & telemetry amortization").
        self.telemetry_export = None
        interval_s = float(getattr(args, "telemetry_interval_s", 0.0) or 0.0)
        self._instrument = interval_s > 0
        if self.is_main_process and interval_s > 0:
            from scalerl_tpu.runtime.telemetry import (
                TelemetryExportLoop,
                get_registry,
            )

            out_dir = getattr(args, "telemetry_dir", "") or os.path.join(
                root, "telemetry"
            )
            self.telemetry_export = TelemetryExportLoop(
                out_dir, interval_s=interval_s
            ).start()
            get_registry().set_gauges(
                {"seed": float(args.seed)}, prefix="run."
            )

    # -- preemption ----------------------------------------------------
    def install_preemption_guard(self):
        """The loop's SIGTERM/SIGINT guard, or None with
        ``handle_preemption`` off.  Where the guard's safe point writes a
        checkpoint, orbax is imported here, at set-up: its import takes
        9-29 s on the chip's host (PERF.md, PR 28) and must not fall inside
        the preemption grace window."""
        if not self.args.handle_preemption:
            return None
        if self.args.save_model and not self.args.disable_checkpoint:
            import orbax.checkpoint  # noqa: F401
        from scalerl_tpu.runtime.supervisor import PreemptionGuard

        return PreemptionGuard().install()

    # -- resume checkpointing ------------------------------------------
    @property
    def resume_ckpt_path(self) -> str:
        return os.path.join(self.model_save_dir, "resume")

    def save_resume_checkpoint(self, state: dict, env_step: int, grad_step: int) -> None:
        """Write the full-trainer resume state + logger save markers.

        ``state``: pytree of everything needed to continue (train state,
        replay state, counters).  Logger markers mirror the reference's
        ``save_data`` (``tensorboard.py:41-63``) so ``restore_data`` can
        recover the interval-gating counters from the event files alone.
        """
        if not self.is_main_process:
            return
        from scalerl_tpu.utils.checkpoint import save_checkpoint

        # keep-last-N retention: the displaced checkpoint survives as
        # resume.prev (…prevN) and load falls back to it when the latest is
        # corrupt — a preemption mid-save can never cost the run
        save_checkpoint(
            self.resume_ckpt_path,
            state,
            keep_last=getattr(self.args, "checkpoint_keep_last", 1),
        )
        self.logger.save_data(0, env_step, grad_step)

    def load_resume_checkpoint(self, target: dict) -> Optional[dict]:
        """Restore the resume pytree + logger counters.

        When the user explicitly asked for ``--resume`` but no checkpoint
        exists at the target, raise instead of returning None — silently
        retraining from step 0 into the old run dir would corrupt the tb
        event stream the user believes is a continuation.
        """
        if not os.path.exists(self.resume_ckpt_path):
            if self.resuming:
                raise FileNotFoundError(
                    f"--resume={self.args.resume}: no resume checkpoint at "
                    f"{self.resume_ckpt_path} (pass the run directory that "
                    "holds model_dir/resume, written at save_frequency)"
                )
            return None
        from scalerl_tpu.utils.checkpoint import load_checkpoint

        state = load_checkpoint(self.resume_ckpt_path, target)
        self.logger.restore_data()
        return state

    def close(self) -> None:
        if self.telemetry_export is not None:
            self.telemetry_export.stop()  # final flush: files hold end state
            self.telemetry_export = None
        self.logger.close()
