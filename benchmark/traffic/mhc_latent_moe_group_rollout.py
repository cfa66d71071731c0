"""Traffic driver ``mhc_latent_moe_group_rollout``: ``group_rollout``'s
closed loop of group sampling on the continuous engine, for a configuration
whose attention caches latent rows, whose router's every expert the chip
holds, and whose residual stream is a stream of rows mixed a sublayer by
hyper-connections (``configs/xing4.0-29b-a4b.json``).

The traffic is ``group_rollout``'s own and the engine is built and warmed
by ``moe_group_rollout``'s ``build``, both loaded and not copied, as
``latent_moe_group_rollout`` does; the freeing of the device and the
recording reference are that file's and ``hybrid_moe_group_rollout``'s,
loaded likewise.  A cell of this driver and one of those differ in the
model alone.  What differs here:

- **the configuration's keys** are Xing4.0's, and the bytes come from
  ``xing4_work.py``: every attention, shared-expert, router, dense-layer,
  ``Phi`` and head matrix once a substep, every expert's three matrices
  once a substep whoever was picked (the streamed form a substep's few
  tokens take reads every bank), the live lanes' latent rows (6 pools),
  each live lane's stream of rows in and out a sublayer.
  ``traced_latent_bytes`` is what ``latent_decode_roofline`` divides,
  ``traced_mhc_moe_bytes`` what ``mhc_moe_decode_roofline`` does and
  ``traced_mhc_bytes`` what ``mhc_stream_roofline`` does; ``mhc_stream_shape``
  says which operations ``mhc_time_share`` looks for.
- **the pick identity**: every expert is held, so held picks are ``k`` x
  tokens x the routed layers and absent and zero-compute ones are none.
- **no lane state**: the stream is an activation, so the engine's
  ``state_bytes_per_lane`` and ``prefix_skipped_recurrent`` are 0 and the
  prefix cache serves hits (``prefix_tokens_saved`` grows with every
  group: its members share their leader's prompt pages).
- **the hyper-connections' controls.**  Beside the sound readings stand
  the median distance between the reference and itself with
  ``mhc_control_iters`` Sinkhorn iterations in place of the configuration's
  and with the maps computed from a flattened norm rounded to
  ``mhc_control_maps``.  On the chip the first is no larger than the sound
  reading itself (bfloat16 rounding grows fourfold through a four-row
  stream whose every expert is held behind a 64-way sigmoid top-4, which
  flips a pick for most tokens: PERF.md, PR 51), so no median limit can
  pass the one and refuse the other.  The iteration is held by a PAIRED
  reading instead: over the same checked tokens, the program's recorded
  log-probabilities and values have to lie NEARER the configuration's
  reference than the few-iterations one, by ``mhc_control_min_ratio``
  (median distance to the control over median distance to the reference: a
  program that ran the control's iterations would read under 1).  A run
  whose ratio is under the limit is not correct.

Parameters (``workloads/<cell>.json``): ``moe_group_rollout``'s, and
``mhc_control_iters``, ``mhc_control_maps``, ``mhc_control_min_ratio``.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import harness

_base = harness.load_module("traffic", "group_rollout")
_moe = harness.load_module("traffic", "moe_group_rollout")
_latent = harness.load_module("traffic", "latent_moe_group_rollout")
_hybrid = harness.load_module("traffic", "hybrid_moe_group_rollout")
# bf16 blocks and stream, f32 head, pools and hyper-connection parameters
_STORED = {"block_bytes": 2, "head_bytes": 4, "row_bytes": 4, "stream_bytes": 2}

build_engine = _moe.build_engine
build = _moe.build
_cumulative = _latent._cumulative


def run(ctx, st):
    import xing4_work as work

    engine, cfg = st.engine, ctx.config
    experts, routed = int(cfg["n_routed_experts"]), work.routed_layers(cfg)
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
    picked = s1["expert_tokens"] - s0["expert_tokens"]  # [routed layers, experts]
    pairs = s1["expert_substeps"] - s0["expert_substeps"]  # (substep, routed layer) pairs
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
            float(np.mean(picked.max(axis=1) / picked.mean(axis=1))) if picked.sum(axis=1).all() else None
        ),
        "expert_picks": int(picked.sum()),
        "mhc_stream_shape": work.stream_shape(cfg, int(ctx.params["lanes"])),
    }
    start, end = ctx.trace_counters.get("start"), ctx.trace_counters.get("end")
    if end and in_window:
        traced = end["tokens"] - start["tokens"]  # live lanes x substeps while tracing
        # cached tokens each decoded token had to read, from the window's
        # completed sequences, times the tokens decoded while tracing
        per_token = _base._kv_tokens_read(in_window) / max(response, 1)
        substeps = (end["expert_substeps"] - start["expert_substeps"]) / max(routed, 1)
        counters["traced_latent_bytes"] = (
            traced * per_token * work.latent_bytes_per_token(cfg, _STORED["row_bytes"])
        )
        counters["traced_weight_bytes"] = (
            substeps * work.decode_dense_bytes(cfg, _STORED["block_bytes"], _STORED["head_bytes"])
            + work.decode_expert_bytes(cfg, substeps, _STORED["block_bytes"])
        )
        counters["traced_mhc_bytes"] = work.mhc_bytes(cfg, traced, substeps, _STORED["stream_bytes"])
        # Phi rides in the dense bytes already: the stream's part alone is added
        counters["traced_mhc_moe_bytes"] = (
            counters["traced_weight_bytes"] + counters["traced_latent_bytes"]
            + traced * work.stream_bytes_per_token(cfg, _STORED["stream_bytes"])
        )
    return {
        "attempted": st.lanes_submitted,
        "failed": 0,
        "end_to_end": {"rollout_tokens_per_s": tokens / ctx.window_s},
        "counters": counters,
        "in_window": in_window,
    }


def _checked(ctx, st):
    """The completed sequences ``moe_group_rollout``'s check samples (by
    length, evenly), with each one's response positions in its row of the
    checked batch."""
    pool = sorted(st.completed, key=lambda c: c.prompt_len + len(c.response_tokens))
    picks = np.linspace(0, len(pool) - 1, int(ctx.params["check_sequences"])).astype(int)
    sample = [pool[i] for i in sorted(set(picks.tolist()))] if pool else []
    return [
        (c, slice(int(c.prompt_len) - 1, int(c.prompt_len) + len(c.response_tokens) - 1))
        for c in sample
    ]


def _mhc_controls(ctx, st, recorded, notes):
    """The two control readings (reference against reference) and the
    paired one that decides: how much nearer the program's own recorded
    tokens lie to the configuration's reference than to the one with
    ``mhc_control_iters`` iterations.  True when both ratios reach
    ``mhc_control_min_ratio``."""
    p = ctx.params
    if None not in recorded.calls:
        return False
    toks, (logp, values, _gaps) = recorded.calls[None]
    logp, values = np.asarray(logp), np.asarray(values)

    def control(name, geo):
        low_logp, low_values, _g = recorded._reference.token_logprobs(st.params, toks, geo)
        low_logp, low_values = np.asarray(low_logp), np.asarray(low_values)
        notes[f"{name}_reference_logp_median_err"] = float(np.median(np.abs(low_logp - logp)))
        notes[f"{name}_reference_value_median_err"] = float(np.median(np.abs(low_values - values)))
        return low_logp, low_values

    iters = int(p["mhc_control_iters"])
    few_logp, few_values = control(f"sinkhorn{iters}", recorded.geometry(ctx.config, hc_iters=iters))
    maps = p.get("mhc_control_maps")
    if maps:
        control(f"{maps}_maps", recorded.geometry(ctx.config, map_round_to=maps))
    near = {"logp": [], "value": []}
    far = {"logp": [], "value": []}
    for i, (c, at) in enumerate(_checked(ctx, st)):
        near["logp"].append(np.abs(logp[i, at] - c.behavior_logp))
        far["logp"].append(np.abs(few_logp[i, at] - c.behavior_logp))
        near["value"].append(np.abs(values[i, at] - c.values))
        far["value"].append(np.abs(few_values[i, at] - c.values))
    ok = bool(near["logp"])
    for kind in ("logp", "value"):
        if not near[kind]:
            continue
        # a float32 rehearsal's distance to its own reference is rounding
        sound = max(float(np.median(np.concatenate(near[kind]))), 1e-9)
        ratio = float(np.median(np.concatenate(far[kind]))) / sound
        notes[f"mhc_control_{kind}_ratio"] = ratio
        ok = ok and ratio >= float(p["mhc_control_min_ratio"])
    notes["mhc_control_refused"] = ok
    return ok


def check(ctx, st, result):
    """``moe_group_rollout``'s check (prefill then decode through the
    latent cache against the reference's full forward: median and maximum
    bounds, the float8 reading, the near-tie share, the exact counts,
    every decoded token at ``k`` router outputs in every routed layer),
    run after the device is freed; then the hyper-connections' controls
    and the paired reading that holds the iteration,
    the pick identity over the routed layers, no lane state and prefix
    hits served."""
    import xing4_work as work

    saved = int(st.engine.prefix_tokens_saved)
    stats = _latent._free_the_device(st)
    recorded = _hybrid._Recorded(ctx.reference)
    ok, notes = _moe.check(dataclasses.replace(ctx, reference=recorded), st, result)
    control_ok = _mhc_controls(ctx, st, recorded, notes)
    cfg = ctx.config
    k, routed = int(cfg["num_experts_per_tok"]), work.routed_layers(cfg)
    kinds = {name: int(stats[f"{name}_expert_tokens"]) for name in ("zero", "held", "absent")}
    picks_ok = (
        kinds["zero"] == 0 and kinds["absent"] == 0
        and kinds["held"] == k * int(st.meter.total) * routed
    )
    stateless_ok = (
        not stats.get("state_bytes_per_lane") and not stats.get("prefix_skipped_recurrent")
        and not stats.get("state_forks")
    )
    prefix_ok = saved > 0
    notes.update(
        picks_ok=picks_ok, stateless_ok=stateless_ok, prefix_ok=prefix_ok,
        prefix_tokens_saved=saved,
        state_bytes_per_lane=stats.get("state_bytes_per_lane", 0),
        prefix_skipped_recurrent=stats.get("prefix_skipped_recurrent", 0),
        page_adjacent_share=stats.get("page_adjacent_share"),
        pages_per_copy=stats.get("pages_per_copy"),
        **{f"{name}_picks": n for name, n in kinds.items()},
    )
    return ok and control_ok and picks_ok and stateless_ok and prefix_ok, notes
