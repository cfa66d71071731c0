"""Run one cell as ``benchmark/run.py`` does, then print what the program's
always-on span accounting saw over the whole process: per span name the
count and the seconds (``tracing.span_totals``), and every ``slow_span``
event the flight recorder still holds (``tracing.slow_spans``).

    python benchmark/tools/run_with_span_totals.py --workload <cell> --seed n --seconds 51 --trace 0

The arguments are ``run.py``'s and so is the result line, which stays the
last line but two of standard output; a JSON line ``{"span_totals": ...}``
and one ``{"slow_spans": [...]}`` follow it.  For the cells whose drivers
do not read the totals themselves (all but ``closed_round``): the
profiler is stopped and ``SCALERL_TRACE_SAMPLE`` unset in a ``--trace 0``
run, so this is what a span's own clock gives with everything else off.
A slow span is also logged by the program when it ends (``slow span:``
in the run's output), whoever runs the cell.  On a program that keeps no
totals (before PR 34) both tables are empty.
"""

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import run  # noqa: E402  (benchmark/run.py)


def main(argv=None) -> int:
    code = run.main(argv)
    from scalerl_tpu.runtime import tracing

    totals = getattr(tracing, "span_totals", dict)()
    slow = getattr(tracing, "slow_spans", list)()
    print(json.dumps({"span_totals": totals}), flush=True)
    print(json.dumps({"slow_spans": slow}, default=str), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
