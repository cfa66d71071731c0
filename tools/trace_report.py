"""Trace merge + critical-path analyzer for the distributed tracer.

Input: a directory of per-host span files (``spans_<host>_<pid>.jsonl``)
written by ``scalerl_tpu/runtime/tracing.py`` — one JSON object per line:
span records, one ``meta`` line per file, and optional ``skew`` lines
carrying the writer's per-peer clock offsets (estimated off heartbeat
ping/pong RTTs).  Output, in one pass:

1. **merged trace trees** — spans grouped by trace id, skew-corrected onto
   the observer's clock, roots identified, orphans counted (a span whose
   parent id is absent from its trace — the completeness failure mode a
   lost host file produces);
2. **Chrome/Perfetto ``trace_event`` JSON** (``--chrome``, default
   ``<dir>/trace_events.json``) — one ``ph: "X"`` complete event per span,
   ``pid`` = host, ``tid`` = trace, so chrome://tracing renders each
   sequence lifecycle as one row spanning generation host -> learner;
3. a **critical-path breakdown** — top traces by duration with per-edge
   attribution, plus the aggregate % of traced wall-clock spent on
   queue-wait vs compute vs wire.  Attribution walks each trace's
   timeline from root start to last span end, charging every interval to
   the span covering it (ties: the later-starting span) or to
   ``untracked`` — so per-edge durations sum to the end-to-end latency
   EXACTLY, and the report can never double-count overlap.

The last stdout line is a one-line JSON verdict
(``{"metric": "trace_report", ...}``) for a caller to gate
the trace soak on: ``sequence_traces`` vs ``complete_sequences``
(root -> learn_step present) and ``orphan_spans``.

``--traffic`` additionally runs the tier-attribution walk
(``scalerl_tpu.runtime.attribution``) over every traffic trace
(``traffic.request`` / ``serve.request`` roots), prints the per-tier
latency table, and emits a second verdict line
(``{"metric": "traffic_report", "bottleneck_tier": ...}``) — the offline
twin of the streaming ``TierLedger`` that multi-host runs use, since the
ledger can only see spans recorded through the local tracer.

jax-free: the trace-tree grouping and the exact-sum attribution walk
live in ``scalerl_tpu.runtime.attribution`` (shared with the online
ledger) and are re-exported here for compatibility.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from scalerl_tpu.runtime.attribution import (  # noqa: F401  (re-exports)
    TRAFFIC_ROOTS,
    LatencyDigest,
    attribute_edges,
    attribute_tiers,
    build_traces,
)

# edge-name -> cost class for the queue/compute/wire rollup
EDGE_CLASSES = {
    "seq.queue_wait": "queue",
    "seq.replay_wait": "queue",
    "serve.queue_wait": "queue",
    "seq.decode": "compute",
    "seq.seq_add": "compute",
    "seq.learn_step": "compute",
    "serve.flush": "compute",
    "task.episode": "compute",
    "genrl.macro_step": "compute",
    "genrl.admit": "compute",
    "genrl.dispatch": "compute",
    "genrl.harvest": "compute",
    "genrl.push_params": "compute",
    "seq.draft": "compute",
    "seq.verify": "compute",
    "round.generate": "compute",
    "round.pack": "compute",
    "round.score": "compute",
    "round.seq_add": "compute",
    "round.sample": "compute",
    "round.learn": "compute",
    "round.push": "compute",
    "learn.step": "compute",
    "learn.dispatch": "compute",
    "loop.dispatch": "compute",
    # the host blocked on the device: one batched read each
    "genrl.read": "wait",
    "dispatch.read": "wait",
    "seq.upload": "wire",
    "snapshot.fetch": "wire",
    "snapshot_publish": "wire",
    "serve.request": "wire",
}

# roots whose traces the completeness verdict inspects, and the leaf edge
# that must be present for the lifecycle to count as complete
COMPLETENESS = {"sequence": "seq.learn_step"}


def classify(name: str) -> str:
    return EDGE_CLASSES.get(name, "other")


def load_dir(trace_dir: str) -> Tuple[List[Dict], Dict[str, float]]:
    """All span records in ``trace_dir``, skew-corrected.

    Skew lines carry ``offsets[peer] = peer_wall - observer_wall`` as
    measured by the writing host; the host with the most measured peers
    (the learner — it pings everyone) becomes the reference, and every
    measured peer's spans shift by ``-offset`` onto its clock.  Files
    without skew data pass through untouched (same-machine soaks).
    """
    spans: List[Dict] = []
    skew_by_observer: Dict[str, Dict[str, float]] = {}
    for path in sorted(glob.glob(os.path.join(trace_dir, "spans_*.jsonl"))):
        with open(path, errors="replace") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    obj = json.loads(line)
                except ValueError:
                    continue  # a torn last line from a SIGTERM'd host
                if "span" in obj:
                    spans.append(obj)
                elif obj.get("kind") == "skew":
                    skew_by_observer.setdefault(
                        str(obj.get("host")), {}
                    ).update(obj.get("offsets") or {})
    offsets: Dict[str, float] = {}
    if skew_by_observer:
        reference = max(
            skew_by_observer, key=lambda h: len(skew_by_observer[h])
        )
        offsets = dict(skew_by_observer[reference])
        offsets.pop(reference, None)
    for s in spans:
        off = offsets.get(str(s.get("host")))
        if off:
            s["t0"] = float(s["t0"]) - off
    return spans, offsets


def build_traffic_report(
    traces: Dict[str, Dict[str, Any]], relative_error: float = 0.01
) -> Dict[str, Any]:
    """Per-tier latency table + bottleneck verdict over the traffic
    traces (``TRAFFIC_ROOTS``-rooted) in an already-built trace set."""
    tier_digests: Dict[str, LatencyDigest] = {}
    tier_totals: Dict[str, float] = {}
    e2e_digest = LatencyDigest(relative_error=relative_error)
    n = 0
    max_sum_err = 0.0
    for t in traces.values():
        root = t["root"]
        if root is None or root["name"] not in TRAFFIC_ROOTS:
            continue
        n += 1
        tiers = attribute_tiers(t)
        max_sum_err = max(
            max_sum_err, abs(sum(tiers.values()) - t["e2e"])
        )
        e2e_digest.observe(t["e2e"])
        for tier, dur in tiers.items():
            tier_totals[tier] = tier_totals.get(tier, 0.0) + dur
            tier_digests.setdefault(
                tier, LatencyDigest(relative_error=relative_error)
            ).observe(dur)
    total = sum(tier_totals.values()) or 1.0
    table = {
        tier: {
            "share": round(tier_totals[tier] / total, 4),
            "total_s": round(tier_totals[tier], 6),
            "p50_ms": round(d.quantile(0.50) * 1e3, 3),
            "p95_ms": round(d.quantile(0.95) * 1e3, 3),
            "p99_ms": round(d.quantile(0.99) * 1e3, 3),
            "count": d.count,
        }
        for tier, d in tier_digests.items()
    }
    bottleneck = (
        max(table, key=lambda k: table[k]["p95_ms"]) if table else None
    )
    return {
        "metric": "traffic_report",
        "traffic_traces": n,
        "bottleneck_tier": bottleneck,
        "tiers": table,
        "max_sum_err_s": max_sum_err,
        "e2e_p50_ms": round(e2e_digest.quantile(0.50) * 1e3, 3),
        "e2e_p95_ms": round(e2e_digest.quantile(0.95) * 1e3, 3),
        "e2e_p99_ms": round(e2e_digest.quantile(0.99) * 1e3, 3),
        "relative_error": relative_error,
    }


def print_traffic_report(tr: Dict[str, Any], out=sys.stdout) -> None:
    print(
        f"traffic tiers ({tr['traffic_traces']} traces, max attribution "
        f"error {tr['max_sum_err_s'] * 1e6:.3f}us):",
        file=out,
    )
    for tier, row in sorted(
        tr["tiers"].items(), key=lambda kv: -kv[1]["share"]
    ):
        print(
            f"  {tier:<16} {100 * row['share']:5.1f}%  "
            f"p50={row['p50_ms']:.2f}ms p95={row['p95_ms']:.2f}ms "
            f"p99={row['p99_ms']:.2f}ms  (n={row['count']})",
            file=out,
        )
    print(f"bottleneck tier: {tr['bottleneck_tier']}", file=out)


def build_report(trace_dir: str, top: int = 5) -> Dict[str, Any]:
    spans, offsets = load_dir(trace_dir)
    traces = build_traces(spans)
    orphan_spans = sum(len(t["orphans"]) for t in traces.values())
    # completeness: every root-named lifecycle must reach its leaf edge
    seq_traces = incomplete = 0
    for t in traces.values():
        root = t["root"]
        leaf = root is not None and COMPLETENESS.get(root["name"])
        if not leaf:
            continue
        seq_traces += 1
        if not any(s["name"] == leaf for s in t["spans"]):
            incomplete += 1
    # per-trace edge attribution + the queue/compute/wire rollup
    per_trace: List[Dict[str, Any]] = []
    agg_edges: Dict[str, float] = {}
    agg_classes: Dict[str, float] = {}
    for tid, t in traces.items():
        edges = attribute_edges(t)
        for name, dur in edges.items():
            agg_edges[name] = agg_edges.get(name, 0.0) + dur
            cls = "untracked" if name == "untracked" else classify(name)
            agg_classes[cls] = agg_classes.get(cls, 0.0) + dur
        per_trace.append(
            {
                "trace": tid,
                "name": t["root"]["name"] if t["root"] else "<orphaned>",
                "e2e_ms": t["e2e"] * 1e3,
                "edges": edges,
                "edge_sum_ms": sum(edges.values()) * 1e3,
            }
        )
    per_trace.sort(key=lambda r: r["e2e_ms"], reverse=True)
    total = sum(agg_classes.values()) or 1.0
    e2es = sorted(t["e2e"] for t in traces.values())
    return {
        "dir": trace_dir,
        "spans": len(spans),
        "traces": traces,
        "top_traces": per_trace[:top],
        "agg_edges": agg_edges,
        "agg_classes": agg_classes,
        "class_fractions": {
            k: v / total for k, v in sorted(agg_classes.items())
        },
        "skew_offsets": offsets,
        "verdict": {
            "metric": "trace_report",
            "spans": len(spans),
            "traces": len(traces),
            "sequence_traces": seq_traces,
            "complete_sequences": seq_traces - incomplete,
            "incomplete": incomplete,
            "orphan_spans": orphan_spans,
            "tracked_fraction": round(
                1.0 - agg_classes.get("untracked", 0.0) / total, 4
            ),
            "p50_e2e_ms": round(e2es[len(e2es) // 2] * 1e3, 3)
            if e2es
            else 0.0,
            "max_e2e_ms": round(e2es[-1] * 1e3, 3) if e2es else 0.0,
        },
    }


def write_chrome(report: Dict[str, Any], path: str) -> str:
    """Chrome/Perfetto ``trace_event`` JSON: complete ("X") events, host as
    pid, trace as tid — load in chrome://tracing or ui.perfetto.dev."""
    t_base = min(
        (t["t0"] for t in report["traces"].values()), default=0.0
    )
    events = []
    for tid, t in report["traces"].items():
        for s in t["spans"]:
            events.append(
                {
                    "ph": "X",
                    "name": s["name"],
                    "cat": s.get("kind") or "span",
                    "pid": str(s.get("host", "?")),
                    "tid": tid,
                    "ts": round((float(s["t0"]) - t_base) * 1e6, 1),
                    "dur": round(float(s["dur"]) * 1e6, 1),
                    "args": dict(s.get("attrs") or {}, span=s["span"]),
                }
            )
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
    return path


def print_report(report: Dict[str, Any], out=sys.stdout) -> None:
    v = report["verdict"]
    print(
        f"trace report: {v['spans']} spans, {v['traces']} traces "
        f"({v['sequence_traces']} sequence lifecycles, "
        f"{v['complete_sequences']} complete, {v['orphan_spans']} orphan "
        "spans)",
        file=out,
    )
    if report["skew_offsets"]:
        print(
            "clock-skew correction applied: "
            + ", ".join(
                f"{h}={o * 1e3:+.3f}ms"
                for h, o in sorted(report["skew_offsets"].items())
            ),
            file=out,
        )
    print("wall-clock attribution (all traces):", file=out)
    for cls, frac in sorted(
        report["class_fractions"].items(), key=lambda kv: -kv[1]
    ):
        print(
            f"  {cls:<10} {100 * frac:5.1f}%  "
            f"({report['agg_classes'][cls] * 1e3:.1f} ms)",
            file=out,
        )
    print("top traces by end-to-end latency:", file=out)
    for r in report["top_traces"]:
        edges = "  ".join(
            f"{name}={dur * 1e3:.1f}ms"
            for name, dur in sorted(
                r["edges"].items(), key=lambda kv: -kv[1]
            )
        )
        print(
            f"  {r['name']}[{r['trace'][:8]}] e2e={r['e2e_ms']:.1f}ms "
            f"(edges sum {r['edge_sum_ms']:.1f}ms): {edges}",
            file=out,
        )


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("trace_dir", help="directory of spans_*.jsonl files")
    parser.add_argument(
        "--chrome",
        default=None,
        help="trace_event JSON output path (default <dir>/trace_events.json)",
    )
    parser.add_argument("--top", type=int, default=5)
    parser.add_argument(
        "--traffic",
        action="store_true",
        help="also run the tier-attribution walk over traffic traces and "
        "emit a traffic_report verdict line",
    )
    parser.add_argument(
        "--relative-error",
        type=float,
        default=0.01,
        help="digest quantile relative-error bound for --traffic",
    )
    args = parser.parse_args(argv)

    report = build_report(args.trace_dir, top=args.top)
    chrome = args.chrome or os.path.join(args.trace_dir, "trace_events.json")
    report["verdict"]["chrome"] = write_chrome(report, chrome)
    print_report(report)
    if args.traffic:
        traffic = build_traffic_report(
            report["traces"], relative_error=args.relative_error
        )
        print_traffic_report(traffic)
        print(json.dumps(traffic), flush=True)
    # the gate line LAST: callers read the newest matching object
    print(json.dumps(report["verdict"]), flush=True)
    ok = (
        report["verdict"]["orphan_spans"] == 0
        and report["verdict"]["incomplete"] == 0
    )
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
