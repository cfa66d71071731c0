"""Per-layer metric ``learn_read_wait_ms_p50.round``.

``learn_read_wait_ms_p50`` (its file reads it) under a name of this cell's: a metric
moves one end-to-end metric, and ``gpt2m_closed_round`` reports the whole
loop's ``rollout_tokens_per_s``, not ``learn_tokens_per_s`` (the learner is
paced by generation there: PERF.md, section 4).
"""

import harness

_same = harness.load_module("metrics", "learn_read_wait_ms_p50")

NAME = "learn_read_wait_ms_p50.round"
UNIT, LAYER = _same.UNIT, _same.LAYER
MOVES = "rollout_tokens_per_s"
read = _same.read
