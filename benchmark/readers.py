"""Readers shared by the per-layer metric files under ``metrics/``.

A reader takes the run's ``reading`` (the context with its spans and
compile counts, the driver's result and counters, the reduced trace, the
device entry, the peaks) and returns a number, or ``None`` when what it
reads is not there: the harness then leaves the metric out.
"""

from __future__ import annotations

from harness import percentile


def span_p50_ms(r, name):
    ctx = r["ctx"]
    p50 = percentile(ctx.spans.durations(name, ctx.t_open, ctx.t_close), 50)
    return None if p50 is None else 1e3 * p50


def counter(r, name):
    return r["result"]["counters"].get(name)


def idle_share(r):
    trace = r["trace"]
    return None if trace is None else 100.0 * trace["idle_share"]


def mosaic_share(r):
    trace = r["trace"]
    if trace is None or trace["busy_s"] <= 0:
        return None
    return 100.0 * trace["mosaic_s"] / trace["busy_s"]


def collective_exposed_share(r):
    trace = r["trace"]
    if trace is None or trace["busy_s"] <= 0:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["busy_s"]


def mfu(r, flops_per_unit, units_per_s):
    """Model operations per second over chips times the bf16 peak."""
    if r["peaks"] is None or flops_per_unit is None or units_per_s is None:
        return None
    chips = int(r["ctx"].workload["chips"])
    return 100.0 * flops_per_unit * units_per_s / (chips * r["peaks"]["bf16_flops_per_s"])
