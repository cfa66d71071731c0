"""Traffic driver ``cca_moe_group_rollout``: ``group_rollout``'s closed
loop of group sampling on the continuous engine, for a configuration whose
every layer is a compressed convolutional attention (its two-token window
by lane beside its own KV pages) and routed experts behind an MLP router,
every expert held (``configs/zaya1-8b.json``).

The traffic is ``group_rollout``'s own and the engine is built and warmed
by ``moe_group_rollout``'s ``build``, both loaded and not copied; the
window's checks (the hand-off beside a planted fault, the window-precision
reading, the recorded reference) and the freeing of the device are
``hybrid_moe_group_rollout``'s, loaded likewise: that file's "state" is
here the window.  A cell of this driver and one of those differ in the
model alone.  What differs here:

- **the configuration's keys** are ZAYA1's, and the bytes come from
  ``zaya_work.py``: every attention, convolution and router matrix and the
  262k-row head once a substep, every expert's three matrices once a
  substep whoever was picked (the streamed form a substep's few tokens
  take reads every bank), each live lane's window in and out, K and V of
  every layer.  ``traced_kv_bytes`` and ``traced_cca_moe_bytes`` are what
  ``paged_decode_roofline`` and ``cca_moe_decode_roofline`` divide.
- **the pick identity**: every layer has a router and every expert is
  held, so held picks are ``k`` x tokens x ALL the layers and absent and
  zero-compute ones are none.
- **the window's counters** are held to ``zaya_work``'s count from shapes
  (``state_bytes_per_lane``), forks and skipped prefix lookups as in
  ``hybrid_moe_group_rollout``.

Parameters (``workloads/<cell>.json``): ``hybrid_moe_group_rollout``'s.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import harness

_base = harness.load_module("traffic", "group_rollout")
_moe = harness.load_module("traffic", "moe_group_rollout")
_hybrid = harness.load_module("traffic", "hybrid_moe_group_rollout")
_STORED = _hybrid._STORED  # bf16 blocks, f32 head and pools

build_engine = _moe.build_engine
build = _moe.build
_cumulative = _hybrid._cumulative


def run(ctx, st):
    import zaya_work as work

    engine, cfg = st.engine, ctx.config
    experts, layers = int(cfg["num_experts"]), int(cfg["num_hidden_layers"])
    in_window = []
    s0 = engine.stats()
    t0_tokens = st.meter.total
    ctx.open_window()
    while True:
        _base._cycle(ctx, st, record=in_window)
        if ctx.tick(_cumulative(st)):
            break
    ctx.close_window(_cumulative(st))
    tokens = st.meter.total - t0_tokens
    s1 = engine.stats()
    steps = s1["macro_steps"] - s0["macro_steps"]
    occupancy = (
        s1["mean_occupancy"] * s1["macro_steps"] - s0["mean_occupancy"] * s0["macro_steps"]
    ) / max(steps, 1)
    response = sum(len(c.response_tokens) for c in in_window)
    routed = s1["expert_tokens"] - s0["expert_tokens"]  # [layers, experts]
    pairs = s1["expert_substeps"] - s0["expert_substeps"]  # (substep, layer) pairs
    counters = {
        "tokens_in_window": tokens,
        "sequences_in_window": len(in_window),
        "macro_steps_in_window": steps,
        "lane_occupancy": occupancy,
        "mean_response_len": response / len(in_window) if in_window else None,
        "prefill_tokens": s1["prefill_tokens"] - s0["prefill_tokens"],
        "moe_experts_hit": (
            (s1["expert_hits"] - s0["expert_hits"]) / (pairs * experts) if pairs else None
        ),
        "moe_max_load": (
            float(np.mean(routed.max(axis=1) / routed.mean(axis=1))) if routed.sum(axis=1).all() else None
        ),
        "expert_picks": int(routed.sum()),
        "state_forks": s1["state_forks"] - s0["state_forks"],
        "wide_head_shape": [int(ctx.params["lanes"]), int(cfg["vocab_size"])],
    }
    start, end = ctx.trace_counters.get("start"), ctx.trace_counters.get("end")
    if end and in_window:
        traced = end["tokens"] - start["tokens"]  # live lanes x substeps while tracing
        # cached tokens each decoded token had to read, from the window's
        # completed sequences, times the tokens decoded while tracing
        per_token = _base._kv_tokens_read(in_window) / max(response, 1)
        substeps = (end["expert_substeps"] - start["expert_substeps"]) / layers
        counters["traced_kv_bytes"] = traced * per_token * work.kv_bytes_per_token(cfg, _STORED["kv_bytes"])
        counters["traced_weight_bytes"] = (
            substeps * work.decode_dense_bytes(cfg, _STORED["block_bytes"], _STORED["head_bytes"])
            + work.decode_expert_bytes(cfg, substeps, _STORED["block_bytes"])
        )
        counters["traced_cca_moe_bytes"] = (
            counters["traced_weight_bytes"] + counters["traced_kv_bytes"]
            + traced * work.window_decode_bytes_per_token(cfg)
        )
    return {
        "attempted": st.lanes_submitted,
        "failed": 0,
        "end_to_end": {"rollout_tokens_per_s": tokens / ctx.window_s},
        "counters": counters,
        "in_window": in_window,
    }


def check(ctx, st, result):
    """``moe_group_rollout``'s check (prefill then decode through pages AND
    the window against the reference's full forward: median and maximum
    bounds, the float8 reading, the near-tie share, the exact counts,
    every decoded token at ``k`` router outputs in every layer), run after
    the device is freed; then ``hybrid_moe_group_rollout``'s
    window-precision reading and hand-off check, the pick identity over
    all the layers and the window's counters."""
    import zaya_work as work

    buckets = st.engine.config.resolved_prompt_buckets()
    stats = _hybrid._free_the_device(st)
    recorded = _hybrid._Recorded(ctx.reference)
    ok, notes = _moe.check(dataclasses.replace(ctx, reference=recorded), st, result)
    _hybrid._state_control(ctx, st, recorded, notes)
    handoff_ok = _hybrid._state_handoff(ctx, st, buckets, notes)
    cfg = ctx.config
    k, layers = int(cfg["num_experts_per_tok"]), int(cfg["num_hidden_layers"])
    kinds = {name: int(stats[f"{name}_expert_tokens"]) for name in ("zero", "held", "absent")}
    picks_ok = (
        kinds["zero"] == 0 and kinds["absent"] == 0
        and kinds["held"] == k * int(st.meter.total) * layers
    )
    groups = st.submitted
    state_ok = (
        stats["state_bytes_per_lane"] == work.state_bytes_per_lane(cfg)
        and stats["state_forks"] == st.lanes_submitted - groups
        and stats["prefix_skipped_recurrent"] == groups
    )
    notes.update(
        picks_ok=picks_ok, state_ok=state_ok, state_handoff_ok=handoff_ok,
        state_bytes_per_lane=stats["state_bytes_per_lane"],
        state_forks=stats["state_forks"],
        prefix_skipped_recurrent=stats["prefix_skipped_recurrent"],
        **{f"{name}_picks": n for name, n in kinds.items()},
    )
    return ok and picks_ok and state_ok and handoff_ok, notes
