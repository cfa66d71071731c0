"""The power-of-two bucket ladder: one shape-stability util, many planes.

Every dynamic-arrival plane in the codebase pads ragged sizes up a fixed
ladder so its jitted programs compile once per bucket and never retrace on
arrival patterns (graftlint JG003 designed out rather than linted out):

- the serving plane buckets *batch lanes* (``serving/batcher.py``);
- the generation engine buckets *prompt/response lengths* on the time axis
  (``genrl/continuous.py``) and additionally buckets *admitted-prefill
  batch sizes*;
- the page allocator sizes page tables off the largest bucket pair.

Extracted here (ISSUE 11) so the ladder has ONE definition and direct unit
tests; ``serving.batcher`` re-exports both names for compatibility.
jax-free by design.
"""

from __future__ import annotations

from typing import List, Tuple


def default_buckets(max_size: int) -> Tuple[int, ...]:
    """Power-of-two ladder up to (and always including) ``max_size``."""
    buckets: List[int] = []
    b = 1
    while b < max_size:
        buckets.append(b)
        b *= 2
    buckets.append(max_size)
    return tuple(buckets)


def bucket_for(size: int, buckets: Tuple[int, ...]) -> int:
    """Smallest bucket >= size; oversize requests get their own
    next-power-of-two bucket (a rare extra trace, never an error)."""
    for b in buckets:
        if size <= b:
            return b
    b = buckets[-1] if buckets else 1
    while b < size:
        b *= 2
    return b
