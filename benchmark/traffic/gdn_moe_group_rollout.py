"""Traffic driver ``gdn_moe_group_rollout``: ``group_rollout``'s closed
loop of group sampling on the continuous engine, for a configuration whose
plain layers mix Gated DeltaNet and attention and whose router scores more
experts than the chip holds (``configs/qwen3-next-80b-a3b.json``).

The traffic is ``group_rollout``'s own and the engine is built and warmed
by ``moe_group_rollout``'s ``build``, both loaded and not copied; the
state's checks (the handoff beside a planted fault, the state-precision
reading, the recorded reference) and the freeing of the device are
``hybrid_moe_group_rollout``'s, loaded likewise.  A cell of this driver
and one of that differ in the model alone.  What differs here:

- **the configuration's keys** are Qwen3-Next's, and the bytes come from
  ``qwen3next_work.py``: the matrix state in and out for every decoded
  token, K and V of the attention layers alone, every Gated DeltaNet,
  attention, router and shared-expert matrix once a substep, and every
  HELD expert's three matrices once a substep (the streamed form a
  substep's few tokens take reads every bank, whoever was picked).
  ``traced_gdn_state_bytes``, ``traced_kv_bytes`` and
  ``traced_gdn_hybrid_bytes`` are what the three ``gdn_*`` metrics and
  ``paged_decode_roofline`` divide.
- **the pick identity**: every layer has a router, so held + absent picks
  are ``k`` x tokens x ALL the layers, and there are no zero-compute ones.
- **the state's counters** are held to ``qwen3next_work``'s count from
  shapes (``state_bytes_per_lane``), forks and skipped prefix lookups as
  in ``hybrid_moe_group_rollout``.

Parameters (``workloads/<cell>.json``): ``hybrid_moe_group_rollout``'s.
"""

from __future__ import annotations

import dataclasses

import harness

_base = harness.load_module("traffic", "group_rollout")
_moe = harness.load_module("traffic", "moe_group_rollout")
_hybrid = harness.load_module("traffic", "hybrid_moe_group_rollout")
_STORED = _hybrid._STORED  # bf16 blocks, f32 head and pools

build_engine = _moe.build_engine
build = _moe.build
_cumulative = _hybrid._cumulative


def run(ctx, st):
    import qwen3next_work as work

    engine, cfg = st.engine, ctx.config
    held = int(cfg["num_experts"])
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
    pairs = s1["expert_substeps"] - s0["expert_substeps"]  # (substep, layer) pairs
    held_picks = s1["held_expert_tokens"] - s0["held_expert_tokens"]
    counters = {
        "tokens_in_window": tokens,
        "sequences_in_window": len(in_window),
        "macro_steps_in_window": steps,
        "lane_occupancy": occupancy,
        "mean_response_len": response / len(in_window) if in_window else None,
        "prefill_tokens": s1["prefill_tokens"] - s0["prefill_tokens"],
        "moe_experts_hit": (
            (s1["expert_hits"] - s0["expert_hits"]) / (pairs * held) if pairs else None
        ),
        "moe_held_picks_per_expert": held_picks / (pairs * held) if pairs else None,
        "expert_picks": int((s1["expert_tokens"] - s0["expert_tokens"]).sum()),
        "state_forks": s1["state_forks"] - s0["state_forks"],
    }
    start, end = ctx.trace_counters.get("start"), ctx.trace_counters.get("end")
    if end and in_window:
        traced = end["tokens"] - start["tokens"]  # live lanes x substeps while tracing
        # cached tokens each decoded token had to read, from the window's
        # completed sequences, times the tokens decoded while tracing
        per_token = _base._kv_tokens_read(in_window) / max(response, 1)
        substeps = (end["expert_substeps"] - start["expert_substeps"]) / work.layer_counts(cfg)["experts"]
        counters["traced_kv_bytes"] = traced * per_token * work.kv_bytes_per_token(cfg, _STORED["kv_bytes"])
        counters["traced_gdn_state_bytes"] = traced * work.gdn_decode_bytes_per_token(cfg)
        counters["traced_weight_bytes"] = (
            substeps * work.decode_dense_bytes(cfg, _STORED["block_bytes"], _STORED["head_bytes"])
            + work.decode_expert_bytes(cfg, substeps, _STORED["block_bytes"])
        )
        counters["traced_gdn_hybrid_bytes"] = (
            counters["traced_weight_bytes"] + counters["traced_kv_bytes"]
            + traced * work.recurrent_decode_bytes_per_token(cfg)
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
    state against the reference's full forward: median and maximum
    bounds, the float8 reading, the near-tie share, the exact counts,
    every decoded token at ``k`` router outputs in every layer), run after
    the device is freed; then ``hybrid_moe_group_rollout``'s state-precision
    reading and handoff check, the pick identity over all the layers and
    the state's counters."""
    import qwen3next_work as work

    buckets = st.engine.config.resolved_prompt_buckets()
    stats = _hybrid._free_the_device(st)
    recorded = _hybrid._Recorded(ctx.reference)
    ok, notes = _moe.check(dataclasses.replace(ctx, reference=recorded), st, result)
    _hybrid._state_control(ctx, st, recorded, notes)
    handoff_ok = _hybrid._state_handoff(ctx, st, buckets, notes)
    cfg = ctx.config
    k, layers = int(cfg["num_experts_per_tok"]), work.layer_counts(cfg)["experts"]
    kinds = {name: int(stats[f"{name}_expert_tokens"]) for name in ("zero", "held", "absent")}
    picks_ok = (
        kinds["zero"] == 0 and kinds["held"] + kinds["absent"] == k * int(st.meter.total) * layers
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
