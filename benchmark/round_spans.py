"""The closed round's phases from the program's always-on span totals.

``runtime/tracing.span`` keeps, per span name, a count and the seconds
spent (``tracing.span_totals``), whether or not a profiler runs.  The
``closed_round`` driver snapshots them at the window's two ends and puts
the difference into its counters as ``span_totals``; this module turns it
into the round's mean length and into five shares of the round that sum
to 100: ``generate``, ``learn`` (sample and learn step), ``push``, and
``host`` (score, pack, insert, and whatever the root holds beside its
children).  A program that keeps no totals (the parent of the PR that
brought them) leaves the table empty and every reader returns ``None``.

A share is a reading of the chip run: in a CPU rehearsal the shares are
logged and not reported, like every other share of the benchmark.
"""

from __future__ import annotations

from typing import Dict, Optional

ROOT = "genrl.round"
PHASES = {
    "generate": ("round.generate",),
    "learn": ("round.sample", "round.learn"),
    "push": ("round.push",),
    "host": ("round.score", "round.pack", "round.seq_add"),
}
_CHILDREN = tuple(name for names in PHASES.values() for name in names)


def totals(r) -> Dict[str, Dict[str, float]]:
    return r["result"].get("counters", {}).get("span_totals") or {}


def round_ms_mean(r) -> Optional[float]:
    root = totals(r).get(ROOT)
    if not root or root["count"] <= 0:
        return None
    return 1e3 * root["seconds"] / root["count"]


def shares(r) -> Optional[Dict[str, float]]:
    """Percent of ``genrl.round``'s seconds by phase; the root's own time
    (its seconds less all of its children's) goes to ``host``.  Logged
    once a run."""
    if "round_shares" not in r:
        spans = totals(r)
        root = spans.get(ROOT, {}).get("seconds", 0.0)
        if root <= 0.0:
            r["round_shares"] = None
        else:
            took = lambda name: spans.get(name, {}).get("seconds", 0.0)  # noqa: E731
            out = {
                phase: 100.0 * sum(took(n) for n in names) / root
                for phase, names in PHASES.items()
            }
            own = 100.0 * (root - sum(took(n) for n in _CHILDREN)) / root
            out["host"] += own
            r["round_shares"] = out
            r["ctx"].log(
                "round shares (%): " + ", ".join(f"{k} {v:.3f}" for k, v in out.items())
                + f"; sum {sum(out.values()):.3f}; the root's own time {own:.3f}"
            )
    return r["round_shares"]


def share(r, phase: str) -> Optional[float]:
    out = shares(r)
    if out is None or r["ctx"].rehearse:
        return None
    return out[phase]
