"""Traffic driver ``hybrid_moe_group_rollout``: ``group_rollout``'s closed
loop of group sampling on the continuous engine, for a configuration whose
stack mixes state-space, attention and routed-expert layers and whose
router scores more experts than the chip holds
(``configs/nemotron-3-nano-30b-a3b.json``).

The traffic is ``group_rollout``'s own, loaded from that file and not
copied (the same seeded prompts, groups, EOS shaping and submit-then-step
cycle), and the engine is built and warmed by ``moe_group_rollout``'s
``build``, loaded likewise, as ``latent_moe_group_rollout`` does: the
seeded weights are made on the device, fetched to the host and freed, and
the engine is built from the host's tree, so that the chip holds the
weights once.  A cell of this driver and one of those differ in the model
alone.  What differs here:

- **the configuration's keys** are Nemotron-H's, and the bytes come from
  ``nemotron_work.py``: the recurrent state in and out for every decoded
  token, K and V of the attention layers alone, every Mamba, attention,
  router and shared-expert matrix once a substep, and every HELD expert's
  two matrices once a substep (the streamed form a substep's few tokens
  take reads every bank, whoever was picked).
- **the state's counters**: the engine's ``stats()`` give
  ``state_bytes_per_lane`` (held to ``nemotron_work``'s count from
  shapes), ``state_forks`` (every group member's state was written by a
  fork: lanes submitted less groups) and ``prefix_skipped_recurrent``
  (every admission skipped the prefix cache, and ``prefix_saved_ratio``
  counts group shares alone).
- **the check** frees everything the run left on the device first, then is
  ``moe_group_rollout``'s own (prefill then decode through pages AND
  state against the reference's full forward, the recurrence one token at
  a time: median and maximum bounds, the float8 reading, the near-tie
  share, the exact counts, every decoded token at ``k`` router outputs in
  every expert layer).  Added here: held + absent picks are all of them
  (``k`` x tokens x expert layers), the counters above, and, where the
  workload names a ``state_control`` dtype, the reading of a reference
  whose recurrent STATE is rounded to it after every token (it says
  whether the median bound can tell a float32 state from a lower one; it
  decides nothing).
- **the state's handoff** (:func:`_state_handoff`): the medians above run
  over whole responses, and a state that was handed over wrong is
  forgotten within tens of tokens.  So ``state_groups`` whole groups (each
  its one leader, whose state the prefill wrote, and its members, whose
  state the fork wrote) are checked over the ``state_window`` tokens
  decoded right after the handoff alone, against
  ``state_logp_median_atol`` and ``state_value_median_atol``; and beside
  it stands the reading of a reference with a planted fault (the state
  taken at the prompt's bucket's end and not at its true length), which
  those limits have to refuse.

Parameters (``workloads/<cell>.json``): ``moe_group_rollout``'s, and
``state_control``, ``state_groups``, ``state_window``,
``state_logp_median_atol``, ``state_value_median_atol``.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import harness

_base = harness.load_module("traffic", "group_rollout")
_moe = harness.load_module("traffic", "moe_group_rollout")
_latent = harness.load_module("traffic", "latent_moe_group_rollout")
_STORED = {"block_bytes": 2, "head_bytes": 4, "kv_bytes": 4}  # bf16 blocks, f32 head and pools

build_engine = _moe.build_engine
build = _moe.build
_cumulative = _latent._cumulative
_free_the_device = _latent._free_the_device


def run(ctx, st):
    import nemotron_work

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
    pairs = s1["expert_substeps"] - s0["expert_substeps"]  # (substep, expert layer) pairs
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
        "moe_held_picks_per_expert": held_picks / (pairs * held) if pairs else None,
        "expert_picks": picks,
        "state_forks": s1.get("state_forks", 0) - s0.get("state_forks", 0),
    }
    start, end = ctx.trace_counters.get("start"), ctx.trace_counters.get("end")
    if end and in_window:
        traced = end["tokens"] - start["tokens"]  # live lanes x substeps while tracing
        # cached tokens each decoded token had to read, from the window's
        # completed sequences, times the tokens decoded while tracing
        per_token = _base._kv_tokens_read(in_window) / max(response, 1)
        counters["traced_kv_bytes"] = (
            traced * per_token * nemotron_work.kv_bytes_per_token(cfg, _STORED["kv_bytes"])
        )
        counters["traced_ssm_state_bytes"] = traced * nemotron_work.ssm_decode_bytes_per_token(cfg)
        substeps = (end["expert_substeps"] - start["expert_substeps"]) / max(
            nemotron_work.layer_counts(cfg)["experts"], 1
        )
        counters["traced_weight_bytes"] = (
            substeps * nemotron_work.decode_dense_bytes(cfg, _STORED["block_bytes"], _STORED["head_bytes"])
            + nemotron_work.decode_expert_bytes(cfg, substeps, _STORED["block_bytes"])
        )
        counters["traced_hybrid_bytes"] = (
            counters["traced_weight_bytes"] + counters["traced_kv_bytes"]
            + traced * nemotron_work.recurrent_decode_bytes_per_token(cfg)
        )
    return {
        "attempted": st.lanes_submitted,
        "failed": 0,
        "end_to_end": {"rollout_tokens_per_s": tokens / ctx.window_s},
        "counters": counters,
        "in_window": in_window,
    }


class _Recorded:
    """The reference module, remembering what ``token_logprobs`` was
    asked and what it gave, by the precision asked for."""

    def __init__(self, reference):
        self._reference = reference
        self.calls = {}

    def __getattr__(self, name):
        return getattr(self._reference, name)

    def token_logprobs(self, params, toks, geo):
        out = self._reference.token_logprobs(params, toks, geo)
        self.calls[geo.round_to] = (toks, out)
        return out


def _state_control(ctx, st, recorded, notes):
    """Median distance, over every position of the checked rows, between
    the reference and itself with the recurrent state rounded to
    ``state_control`` after every token."""
    dtype = ctx.params.get("state_control")
    if not dtype or None not in recorded.calls:
        return
    toks, (logp, values, _gaps) = recorded.calls[None]
    low = recorded.geometry(ctx.config, state_round_to=dtype)
    low_logp, low_values, _gaps = recorded._reference.token_logprobs(st.params, toks, low)
    notes[f"{dtype}_state_reference_logp_median_err"] = float(
        np.median(np.abs(np.asarray(low_logp) - np.asarray(logp)))
    )
    notes[f"{dtype}_state_reference_value_median_err"] = float(
        np.median(np.abs(np.asarray(low_values) - np.asarray(values)))
    )


def _state_handoff(ctx, st, buckets, notes):
    """The tokens decoded right after the state was handed over, in
    ``state_groups`` whole groups: a group's leader decodes from the state
    its prefill wrote at the prompt's true length, its members from the
    rows the fork copied.  A row is the prompt, pads up to its bucket (as
    the prefill saw it), then the first ``state_window + 1`` response
    tokens; the reference passes the pads by (``real``), and the planted
    fault runs the recurrence through them.  The groups with the most pads
    are taken: a prompt that fills its bucket plants no fault.  The first
    response token comes from the prefill's own logits and is not
    compared.  True when both medians are within their limits."""
    p, ref = ctx.params, ctx.reference
    n, w = int(p["samples_per_prompt"]), int(p["state_window"])
    groups = {}
    for c in st.completed:
        groups.setdefault(c.tag, []).append(c)

    def pads(group):
        m = int(group[0].prompt_len)
        return min(b for b in buckets if b >= m) - m

    whole = sorted((g for g in groups.values() if len(g) == n), key=pads, reverse=True)
    rows = [c for g in whole[: int(p["state_groups"])] for c in g if len(c.response_tokens) > 1]
    if not rows:
        return False
    total = max(int(c.prompt_len) + pads([c]) for c in rows) + w + 1
    toks, real, at = np.zeros((len(rows), total), np.int32), np.zeros((len(rows), total), bool), []
    for i, c in enumerate(rows):
        m, r = int(c.prompt_len), min(len(c.response_tokens), w + 1)
        first = m + pads([c])  # where the response starts in this row
        toks[i, :m], toks[i, first : first + r] = c.prompt[:m], c.response_tokens[:r]
        real[i, :m] = real[i, first : first + r] = True
        at.append((i, slice(first, first + r - 1), slice(1, r)))  # response tokens 1 .. r-1
    sound = [np.asarray(a) for a in ref.token_logprobs(st.params, toks, ref.geometry(ctx.config), real)]
    fault = [
        np.asarray(a) for a in
        ref.token_logprobs(st.params, toks, ref.geometry(ctx.config, state_through_pads=True), real)
    ]
    err = {"logp": [], "value": [], "fault_logp": [], "fault_value": []}
    for i, row, resp in at:
        c = rows[i]
        err["logp"].append(np.abs(sound[0][i, row] - c.behavior_logp[resp]))
        err["value"].append(np.abs(sound[1][i, row] - c.values[resp]))
        err["fault_logp"].append(np.abs(fault[0][i, row] - sound[0][i, row]))
        err["fault_value"].append(np.abs(fault[1][i, row] - sound[1][i, row]))
    med = {name: float(np.median(np.concatenate(a))) for name, a in err.items()}
    notes.update(
        state_logp_median_err=med["logp"], state_value_median_err=med["value"],
        pad_fault_state_logp_median_err=med["fault_logp"],
        pad_fault_state_value_median_err=med["fault_value"],
        state_tokens_checked=int(sum(len(a) for a in err["logp"])),
        state_rows_checked=len(rows), state_pads=[pads(g) for g in whole[: int(p["state_groups"])]],
    )
    return (
        med["logp"] <= float(p["state_logp_median_atol"])
        and med["value"] <= float(p["state_value_median_atol"])
    )


def check(ctx, st, result):
    """``moe_group_rollout``'s check, loaded and not copied, run after the
    device is freed; then the state's handoff, the picks' two kinds, the
    state's counters and the state-precision reading."""
    import nemotron_work

    buckets = st.engine.config.resolved_prompt_buckets()
    stats = _free_the_device(st)
    recorded = _Recorded(ctx.reference)
    ok, notes = _moe.check(dataclasses.replace(ctx, reference=recorded), st, result)
    _state_control(ctx, st, recorded, notes)
    handoff_ok = _state_handoff(ctx, st, buckets, notes)
    cfg = ctx.config
    k, layers = int(cfg["num_experts_per_tok"]), nemotron_work.layer_counts(cfg)["experts"]
    kinds = {name: int(stats[f"{name}_expert_tokens"]) for name in ("zero", "held", "absent")}
    picks_ok = (
        kinds["zero"] == 0 and kinds["held"] + kinds["absent"] == k * int(st.meter.total) * layers
    )
    groups = st.submitted
    state_ok = (
        stats.get("state_bytes_per_lane") == nemotron_work.state_bytes_per_lane(cfg)
        and stats.get("state_forks") == st.lanes_submitted - groups
        and stats.get("prefix_skipped_recurrent") == groups
    )
    notes.update(
        picks_ok=picks_ok, state_ok=state_ok, state_handoff_ok=handoff_ok,
        state_bytes_per_lane=stats.get("state_bytes_per_lane"),
        state_forks=stats.get("state_forks"),
        prefix_skipped_recurrent=stats.get("prefix_skipped_recurrent"),
        **{f"{name}_picks": n for name, n in kinds.items()},
    )
    return ok and picks_ok and state_ok and handoff_ok, notes
