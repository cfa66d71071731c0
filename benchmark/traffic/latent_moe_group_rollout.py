"""Traffic driver ``latent_moe_group_rollout``: ``group_rollout``'s closed
loop of group sampling on the continuous engine, for a configuration whose
attention caches latent rows and whose router scores more experts than the
chip holds (``configs/longcat-flash-chat.json``).

The traffic is ``group_rollout``'s own, loaded from that file and not
copied (the same seeded prompts, groups, EOS shaping and submit-then-step
cycle), and the engine is built and warmed by ``moe_group_rollout``'s
``build``, loaded likewise: the seeded weights are made on the device,
fetched to the host and freed, and the engine is built from the host's
tree, so that the chip holds the weights once.  A cell of this driver and
one of those two differ in the model alone.  What differs here:

- **the configuration's keys** are LongCat's, and the bytes come from
  ``longcat_work.py``: latent rows for the cache, two attentions, two
  dense FFNs and a router a layer for the weights, and an expert's three
  matrices for each HELD expert that was hit.
- **the expert counters**: the engine's ``stats()`` at the window's two
  ends (and at the trace's) give ``moe_experts_hit`` (of the held
  experts), ``moe_zero_pick_share``, ``moe_held_picks_per_expert`` and the
  bytes the two rooflines divide.
- **the check** frees everything the run left on the device first (the
  window is over; the reference's layers need the room), then is
  ``moe_group_rollout``'s own: the MEDIAN absolute error of the recorded
  log-probabilities and values held to one bound and the maximum to a
  second, wider one (where the 12th and 13th router scores lie closer than
  the rounding of the router's input, bfloat16 and float32 pick different
  experts for that token), the share of checked (token, layer) pairs whose
  gap is under 1% of the 12th score, the median error of the reference
  itself with its matmul operands rounded to float8 (which the median
  bound has to refuse), the exact counts, every decoded token at
  ``moe_topk`` router outputs in every layer.  Added here: each pick is of
  one kind, zero + held + absent.

Parameters (``workloads/<cell>.json``): ``moe_group_rollout``'s.
"""

from __future__ import annotations

import dataclasses
from types import SimpleNamespace

import harness

_base = harness.load_module("traffic", "group_rollout")
_moe = harness.load_module("traffic", "moe_group_rollout")
_STORED = {"block_bytes": 2, "head_bytes": 4, "row_bytes": 4}  # bf16 blocks, f32 head and pools

build_engine = _moe.build_engine
build = _moe.build


def _cumulative(st):
    stats = st.engine.stats()
    return {
        "tokens": st.meter.total,
        "expert_hits": stats["expert_hits"],
        "expert_substeps": stats["expert_substeps"],
    }


def run(ctx, st):
    import longcat_work

    engine, cfg = st.engine, ctx.config
    held = int(cfg["n_routed_experts"])
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
    picks = int((s1["expert_tokens"] - s0["expert_tokens"]).sum())
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
        "moe_zero_pick_share": (
            (s1["zero_expert_tokens"] - s0["zero_expert_tokens"]) / picks if picks else None
        ),
        "moe_held_picks_per_expert": held_picks / (pairs * held) if pairs else None,
        "expert_picks": picks,
    }
    start, end = ctx.trace_counters.get("start"), ctx.trace_counters.get("end")
    if end and in_window:
        # cached tokens each decoded token had to read, from the window's
        # completed sequences, times the tokens decoded while tracing
        per_token = _base._kv_tokens_read(in_window) / max(response, 1)
        counters["traced_latent_bytes"] = (
            (end["tokens"] - start["tokens"]) * per_token
            * longcat_work.latent_bytes_per_token(cfg, _STORED["row_bytes"])
        )
        substeps = (end["expert_substeps"] - start["expert_substeps"]) / int(cfg["num_layers"])
        counters["traced_weight_bytes"] = (
            substeps * longcat_work.decode_dense_bytes(cfg, _STORED["block_bytes"], _STORED["head_bytes"])
            + (end["expert_hits"] - start["expert_hits"])
            * longcat_work.expert_bytes(cfg, _STORED["block_bytes"])
        )
    return {
        "attempted": st.lanes_submitted,
        "failed": 0,
        "end_to_end": {"rollout_tokens_per_s": tokens / ctx.window_s},
        "counters": counters,
        "in_window": in_window,
    }


def _free_the_device(st):
    """The window is over and the engine's counters are read: drop the
    engine and delete every array still on the device (its weights, pools
    and lane state), so that the reference's layers have the chip.  What
    the check still asks of the engine is its last ``stats()``."""
    import gc

    import jax

    stats = st.engine.stats()
    st.engine = SimpleNamespace(stats=lambda: stats)
    gc.collect()
    for array in jax.live_arrays():
        array.delete()
    return stats


def check(ctx, st, result):
    """``moe_group_rollout``'s check, loaded and not copied (prefill then
    decode through the cache against the reference's full forward, the
    median and maximum bounds, the float8 reading, the exact counts, every
    token at ``k`` router outputs in every layer), run after the device is
    freed and under that file's name for ``k``; then the picks' three
    kinds: zero + held + absent are all of them."""
    stats = _free_the_device(st)
    k = int(ctx.config["moe_topk"])
    ok, notes = _moe.check(
        dataclasses.replace(ctx, config={**ctx.config, "num_experts_per_tok": k}), st, result
    )
    kinds = {name: int(stats[f"{name}_expert_tokens"]) for name in ("zero", "held", "absent")}
    picks_ok = sum(kinds.values()) == k * int(st.meter.total) * int(ctx.config["num_layers"])
    notes.update(picks_ok=picks_ok, **{f"{name}_picks": n for name, n in kinds.items()})
    return ok and picks_ok, notes
