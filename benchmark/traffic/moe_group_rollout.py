"""Traffic driver ``moe_group_rollout``: ``group_rollout``'s closed loop
of group sampling on the continuous engine, for a routed-experts
configuration that one chip holds once.

The traffic is ``group_rollout``'s own, loaded from that file and not
copied: the same seeded prompts, groups, EOS shaping and submit-then-step
cycle, so that a cell of this driver and one of that differ in the model
alone.  What differs here:

- **the weights exist once on the chip.**  The engine keeps its own copy
  of what it is handed; two copies of 7.3 GB beside 4.3 GB of pools are
  more than a chip.  The seeded weights are made on the device, fetched
  to the host and freed, and the engine is built from the host's tree, as
  a rollout worker is that receives its weights over the wire.  The check
  hands the reference that host tree, a layer at a time.
- **the configuration's keys** are OLMoE's, and the KV and weight bytes
  come from ``moe_work.py``.
- **the expert counters**: the engine's ``stats()`` at the window's two
  ends (and at the trace's) give ``moe_experts_hit``, ``moe_max_load`` and
  the bytes ``moe_decode_roofline`` divides.
- **the check** holds the MEDIAN absolute error of the recorded
  log-probabilities and values to one bound and the maximum to a second,
  wider one: where the 8th and 9th router probabilities lie closer than
  the rounding of the router's input, bfloat16 and float32 pick different
  experts for that token, which a maximum alone would have to hide.  It
  prints the share of checked (token, layer) pairs whose gap is under 1%
  of the 8th probability, and the median error of the reference itself
  when its matmul operands are rounded to float8: the nearest precision
  below the configuration's, which the median bound has to refuse.

Parameters (``workloads/<cell>.json``): ``group_rollout``'s, with
``logp_median_atol``, ``logp_max_atol``, ``value_median_atol``,
``value_max_atol`` in place of its two bounds.
"""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np

import harness

_base = harness.load_module("traffic", "group_rollout")
_STORED = {"block_bytes": 2, "head_bytes": 4, "kv_bytes": 4}  # bf16 blocks, f32 head and pools


def build_engine(ctx):
    """The model, its seeded weights (on the host) and the engine built
    from them; nothing has run."""
    import jax
    from scalerl_tpu.config import GenRLArguments, parse_args
    from scalerl_tpu.genrl.continuous import ContinuousConfig, ContinuousEngine
    from scalerl_tpu.runtime import telemetry
    from scalerl_tpu.trainer.sequence_rl import build_genrl_model

    p = ctx.params
    eos = int(ctx.config["eos_token_id"])
    argv = (
        list(p["argv"]) + ctx.reference.program_argv(ctx.config)
        + ["--genrl-engine", "continuous", "--genrl-lanes", str(p["lanes"]),
           "--samples-per-prompt", str(p["samples_per_prompt"]),
           "--prompt-len", str(p["prompt_len"][1]),
           "--max-new-tokens", str(p["max_new_tokens"]),
           "--eos-token", str(eos), "--seed", str(ctx.seed),
           "--platform", "cpu" if ctx.rehearse else "tpu"]
    )
    args = parse_args(GenRLArguments, argv)
    args.validate()
    model = build_genrl_model(args)
    on_device = _base._seeded_weights(model, ctx.seed, eos, float(p["eos_prob"]), args.vocab_size)
    params = jax.device_get(on_device)
    jax.tree_util.tree_map(lambda x: x.delete(), on_device)
    ctx.log("weights made on the device and moved to the host")
    # the engine exactly as SequenceRLTrainer configures it from the args
    engine = ContinuousEngine(
        model, params,
        ContinuousConfig(
            vocab_size=args.vocab_size, max_prompt_len=args.prompt_len,
            max_new_tokens=args.max_new_tokens, temperature=args.temperature,
            top_k=args.top_k, eos_token=args.eos_token, seed=args.seed,
            lanes=args.genrl_lanes, page_size=args.genrl_page_size,
            num_pages=args.genrl_num_pages, steps_per_macro=args.genrl_macro_steps,
            admit_max_wait_s=args.genrl_admit_wait_ms / 1e3,
            max_pending=args.genrl_max_pending, paged_attn=args.genrl_paged_attn,
            steps_in_flight=args.genrl_steps_in_flight,
            prefix_cache=args.genrl_prefix_cache,
        ),
        iter_mode=args.genrl_iter_mode,
    )
    return SimpleNamespace(
        args=args, model=model, engine=engine, eos=eos, params=params,
        rng=np.random.default_rng(ctx.seed), completed=[], submitted=0, lanes_submitted=0,
        meter=telemetry.get_registry().meter(_base._DECODE_METER),
    )


def build(ctx):
    """``group_rollout``'s warm-up on this driver's engine: one small
    group in each prompt bucket the traffic can reach, then seeded traffic
    until ``warmup_tokens`` are harvested and a full group was admitted."""
    st = build_engine(ctx)
    p, engine = ctx.params, st.engine
    lo, hi = p["prompt_len"]
    buckets = engine.config.resolved_prompt_buckets()
    st.forced = sorted({min(b, hi) for b in buckets if b >= lo} | {hi})
    st.forced.append(st.forced[0])
    st.forced_n = max(1, int(p["lanes"]) // len(st.forced))
    full_groups = len(st.forced) + 1
    base = st.meter.total
    while st.meter.total - base < p["warmup_tokens"] or st.submitted < full_groups:
        _base._cycle(ctx, st, record=None)
    ctx.log(
        f"warm: {int(st.meter.total - base)} tokens, {len(st.completed)} "
        f"sequences, {engine.stats()['macro_steps']} macro-steps"
    )
    return st


def _cumulative(st):
    stats = st.engine.stats()
    return {
        "tokens": st.meter.total,
        "expert_hits": stats["expert_hits"],
        "expert_substeps": stats["expert_substeps"],
    }


def run(ctx, st):
    import moe_work

    engine, cfg = st.engine, ctx.config
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
            (s1["expert_hits"] - s0["expert_hits"]) / (pairs * routed.shape[1]) if pairs else None
        ),
        "moe_max_load": (
            float(np.mean(routed.max(axis=1) / routed.mean(axis=1))) if routed.sum(axis=1).all() else None
        ),
        "expert_assignments": int(routed.sum()),
    }
    start, end = ctx.trace_counters.get("start"), ctx.trace_counters.get("end")
    if end and in_window:
        # cached tokens each decoded token had to read, from the window's
        # completed sequences, times the tokens decoded while tracing
        per_token = _base._kv_tokens_read(in_window) / max(response, 1)
        counters["traced_kv_bytes"] = (
            (end["tokens"] - start["tokens"]) * per_token
            * moe_work.olmoe_kv_bytes_per_token(cfg, _STORED["kv_bytes"])
        )
        substeps = (end["expert_substeps"] - start["expert_substeps"]) / routed.shape[0]
        counters["traced_weight_bytes"] = (
            substeps * moe_work.olmoe_decode_dense_bytes(cfg, _STORED["block_bytes"], _STORED["head_bytes"])
            + (end["expert_hits"] - start["expert_hits"])
            * moe_work.olmoe_expert_bytes(cfg, _STORED["block_bytes"])
        )
    return {
        "attempted": st.lanes_submitted,
        "failed": 0,
        "end_to_end": {"rollout_tokens_per_s": tokens / ctx.window_s},
        "counters": counters,
        "in_window": in_window,
    }


def _pad_to(n, multiple):
    return -(-n // multiple) * multiple


def check(ctx, st, result):
    """Prefill then decode through the paged cache against the reference's
    full forward: the log-probability and the value the engine recorded for
    every response token of a seeded sample of completed sequences; then
    ``group_rollout``'s exact counts, and that every decoded token was
    routed to ``k`` experts in every layer (nothing dropped)."""
    p, ref = ctx.params, ctx.reference
    notes = {}
    pool = sorted(st.completed, key=lambda c: c.prompt_len + len(c.response_tokens))
    picks = np.linspace(0, len(pool) - 1, int(p["check_sequences"])).astype(int)
    sample = [pool[i] for i in sorted(set(picks.tolist()))] if pool else []
    ok = bool(sample)
    if sample:
        # one batch, padded to one length: causal, so a pad tail changes
        # nothing before it
        total = _pad_to(max(int(c.prompt_len) + len(c.response_tokens) for c in sample), 128)
        toks = np.zeros((len(sample), total), np.int32)
        for i, c in enumerate(sample):
            m, r = int(c.prompt_len), len(c.response_tokens)
            toks[i, :m] = c.prompt[:m]
            toks[i, m : m + r] = c.response_tokens
        geo = ref.geometry(ctx.config)
        logp, values, gaps = (np.asarray(a) for a in ref.token_logprobs(st.params, toks, geo))
        low = ref.geometry(ctx.config, round_to="float8_e4m3fn")
        low_logp, low_values, _gaps = (np.asarray(a) for a in ref.token_logprobs(st.params, toks, low))
        err_logp, err_value, err_low, err_low_value, near = [], [], [], [], []
        for i, c in enumerate(sample):
            m, r = int(c.prompt_len), len(c.response_tokens)
            at = slice(m - 1, m + r - 1)
            err_logp.append(np.abs(logp[i, at] - c.behavior_logp))
            err_value.append(np.abs(values[i, at] - c.values))
            err_low.append(np.abs(low_logp[i, at] - logp[i, at]))
            err_low_value.append(np.abs(low_values[i, at] - values[i, at]))
            near.append(gaps[:, i, : m + r].ravel() < 0.01)
        err_logp, err_value, err_low, err_low_value, near = (
            np.concatenate(a) for a in (err_logp, err_value, err_low, err_low_value, near)
        )
        notes.update(
            logp_median_err=float(np.median(err_logp)), logp_max_err=float(np.max(err_logp)),
            value_median_err=float(np.median(err_value)), value_max_err=float(np.max(err_value)),
            float8_reference_logp_median_err=float(np.median(err_low)),
            float8_reference_value_median_err=float(np.median(err_low_value)),
            near_tie_share=float(np.mean(near)), tokens_checked=int(err_logp.size),
        )
        ok = ok and notes["logp_median_err"] <= float(p["logp_median_atol"])
        ok = ok and notes["logp_max_err"] <= float(p["logp_max_atol"])
        ok = ok and notes["value_median_err"] <= float(p["value_median_atol"])
        ok = ok and notes["value_max_err"] <= float(p["value_max_atol"])
    # exact counts: every harvested token belongs to a sequence the driver
    # submitted, and the engine's own count agrees with the sequences
    seqs = result["in_window"]
    counters = result["counters"]
    lengths_ok = all(
        1 <= len(c.response_tokens) <= int(p["max_new_tokens"])
        and len(c.behavior_logp) == len(c.response_tokens)
        and np.all(np.isfinite(c.behavior_logp))
        for c in st.completed
    )
    ended_ok = all(
        c.response_tokens[-1] == st.eos or len(c.response_tokens) == int(p["max_new_tokens"])
        for c in st.completed
    )
    stats = st.engine.stats()
    live_tokens = st.meter.total - sum(len(c.response_tokens) for c in st.completed)
    notes.update(
        sequences=len(st.completed), in_window=len(seqs),
        tokens_in_window=counters["tokens_in_window"],
        tokens_in_live_lanes=live_tokens,
    )
    ok = ok and lengths_ok and ended_ok
    ok = ok and stats["completed"] == len(st.completed)
    ok = ok and 0 <= live_tokens <= int(p["lanes"]) * int(p["max_new_tokens"])
    # dropless: each layer routed every harvested token to exactly k experts
    k = int(ctx.config["num_experts_per_tok"])
    routed_ok = bool(np.all(stats["expert_tokens"].sum(axis=1) == k * int(st.meter.total)))
    notes.update(dropless=routed_ok)
    return ok and routed_ok, notes
