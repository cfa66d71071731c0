"""Traffic driver ``moe_packed_learn``: ``packed_learn``'s learn steps on a
routed model with a multi-token-prediction module
(``configs/joyai-llm-flash.json``).

The traffic is ``packed_learn``'s own, loaded from that file and not
copied: the learner built from the program's arguments, the replay filled
in set-up with the same seeded completions through the program's own
packing, the probe step, the warm-up, and a window of ``seq_sample`` then
``TokenPPOAgent.learn``.  A cell of this driver and one of that differ in
the model alone.  What differs here:

- **the operations per token** come from ``joyai_work.py``: the matrices a
  token really runs (held picks only, from the window's own
  ``moe_held_picks``), attention at q/k 192 and v 128, the module and both
  passes over the head.
- **the learn metrics' sums** go into ``counters``: held picks an expert
  a layer a step, the largest load, the module's loss and top-1 match,
  and the attention operations of the traced steps (for the segment
  kernels' roofline).
- **the check** holds the probe step to ``reference/joyai_flash.py``
  through ``reference/mtp_token_ppo.py`` in float32 at ``highest`` on the
  host's CPU: the logits at the probed sequence's place in a packed row,
  the PPO terms and ``mtp_loss``, the gradient's norm, the update.  Where
  two router scores lie closer than the rounding of the router's bfloat16
  input, the learner and the reference pick different experts for that
  token; the check reports the share of (token, routed layer) pairs that
  near a flip (``near_tie_share``) and, under ``float8_reference``, what
  the same reference reads with its matmul operands rounded to float8,
  which the bounds have to refuse.

Parameters (``workloads/<cell>.json``): ``packed_learn``'s, and
``logits_median_atol`` (the median absolute error of the probed logits and
values; the maximum is held only where ``logits_atol`` is given),
``mtp_loss_atol``, ``float8_reference`` (whether the check also takes the
float8 reading).
"""

from __future__ import annotations

import dataclasses

import harness
import numpy as np

_base = harness.load_module("traffic", "packed_learn")

build_learner = _base.build_learner


def _as_gpt2(cfg):
    """The keys ``packed_learn.build`` hands ``work.py`` for its own count
    of operations, which this driver replaces before anything reads it."""
    return {
        **cfg, "n_embd": cfg["hidden_size"], "n_layer": cfg["num_hidden_layers"],
        "n_inner": cfg["intermediate_size"],
    }


def build(ctx):
    import work

    st = _base.build(dataclasses.replace(ctx, config=_as_gpt2(ctx.config)))
    seg = np.asarray(st.host_rows.segment_ids)
    st.attended_keys = work.mean_attended_keys(
        [int(n) for row in seg for n in np.bincount(row[row > 0])[1:] if n > 0]
    )
    st.flops_per_token = None  # known once the window has counted its held picks
    return st


def _mean(steps, key):
    return float(sum(m[key] for m in steps) / len(steps))


def run(ctx, st):
    import joyai_work

    cfg = ctx.config
    result = _base.run(ctx, st)
    steps = st.steps
    layers = joyai_work.routed_layers(cfg)
    held = int(cfg["n_routed_experts"])
    counters = result["counters"]
    real = counters["real_tokens_in_window"]
    held_picks = sum(m["moe_held_picks"] for m in steps)
    counters.update(
        learn_held_picks_per_expert=held_picks / (len(steps) * layers * held),
        learn_moe_max_load=_mean(steps, "moe_max_load"),
        moe_absent_picks_in_window=float(sum(m["moe_absent_picks"] for m in steps)),
        mtp_loss=_mean(steps, "mtp_loss"),
        mtp_top1_match=_mean(steps, "mtp_top1_match"),
        attended_keys=st.attended_keys,
        train_flops_per_token=joyai_work.train_flops_per_token(
            cfg, st.attended_keys, held_picks / (real * layers)
        ),
    )
    start, end = ctx.trace_counters.get("start"), ctx.trace_counters.get("end")
    if end:
        traced = steps[int(start["steps"]) : int(end["steps"])]
        counters["traced_attention_flops"] = (
            sum(m["real_token_frac"] for m in traced) * st.slots
            * joyai_work.attention_flops_per_token(cfg, st.attended_keys)
        )
    return result


def _f32_on_host(tree, cpu):
    import jax

    return jax.device_put(
        jax.tree_util.tree_map(lambda x: np.asarray(x, np.float32), tree), cpu
    )


def check(ctx, st, result):
    """The probe step against the plain reference, which follows it in
    float32 on the host's CPU from the same seeded weights; then the
    window's steps' own flags and counts, as ``packed_learn``'s check."""
    import jax
    import jax.numpy as jnp

    p, cfg = ctx.params, ctx.config
    probe, args, ref = st.probe, st.args, ctx.reference
    plain = harness.load_module("reference", "token_ppo")
    composed = harness.load_module("reference", "mtp_token_ppo")
    hyper = {
        k: getattr(args, k) for k in (
            "clip_range", "value_cost", "entropy_cost", "kl_cost", "adv_norm",
            "learning_rate", "max_grad_norm", "mtp_loss_coef",
        )
    }
    cpu = jax.devices("cpu")[0]
    before, after = _f32_on_host(probe.before, cpu), _f32_on_host(probe.after, cpu)
    seq = jax.device_put(probe.seq, cpu)
    n = probe.length

    def follow(geo):
        with jax.default_device(cpu):
            out = composed.follow(
                plain, before, after, seq,
                lambda w, tokens: ref.forward_mtp(w, tokens, geo)[:3], hyper,
            )
            # what the reference's routers did with the probed tokens
            routing = ref.forward_mtp(before, seq["tokens"][None], geo)[3]
            gaps = jnp.stack([g[0, :n] for _s, _w, g in routing])
            held, absent, _max_load = ref.picks(
                routing, jnp.arange(seq["tokens"].shape[0])[None] < n, geo
            )
        return out, np.asarray(gaps), float(held), float(absent)

    want, gaps, held, absent = follow(ref.geometry(cfg))
    got = probe.metrics
    err = np.abs(probe.logits - want["logits"][:n])
    value_err = np.abs(probe.values - want["values"][:n])
    scale = float(np.max(np.abs(want["logits"][:n])))
    loss_keys = ("total_loss", "pg_loss", "value_loss", "entropy")
    notes = {
        "logits_max_err": float(err.max()),
        "logits_median_err": float(np.median(err)),
        "value_max_err": float(value_err.max()),
        "value_median_err": float(np.median(value_err)),
        "logits_scale": scale,
        "tokens_checked": n,
        "loss_err": max(abs(float(got[k]) - float(want[k])) for k in loss_keys),
        "mtp_loss_err": abs(float(got["mtp_loss"]) - float(want["mtp_loss"])),
        "mtp_loss": float(got["mtp_loss"]),
        "grad_norm_rel_err": abs(float(got["grad_norm"]) / float(want["grad_norm"]) - 1.0),
        "update_gain": float(want["update_gain"]),
        "update_norm_ratio": float(want["update_norm_ratio"]),
        # (token, routed layer) pairs whose last kept score is within a
        # bfloat16 rounding (2^-8 of it) of the first one left out
        "near_tie_share": float(np.mean(gaps < 2.0**-8)),
        "reference_held_picks": held,
        "reference_absent_picks": absent,
    }
    if p.get("float8_reference"):
        # what a precision below the configuration's reads: the reference
        # itself with both operands of every weight matmul rounded to
        # float8, against the float32 reference
        low, _g, _h, _a = follow(ref.geometry(cfg, round_to="float8_e4m3fn"))
        low_err = np.abs(low["logits"][:n] - want["logits"][:n])
        notes.update(
            float8_logits_median_err=float(np.median(low_err)),
            float8_logits_max_err=float(low_err.max()),
            float8_loss_err=max(abs(float(low[k]) - float(want[k])) for k in loss_keys),
            float8_mtp_loss_err=abs(float(low["mtp_loss"]) - float(want["mtp_loss"])),
            float8_grad_norm_rel_err=abs(float(low["grad_norm"]) / float(want["grad_norm"]) - 1.0),
        )
    # bfloat16 weights, activations and matmul operands through six
    # attentions and five routed layers and back, against a float32
    # reference; the bounds in the cell's file say where each was read.
    # The MAXIMUM error is a flipped pick's (a whole expert's output on one
    # token): on the chip it read 0.07-1.11 over eight seeds where the
    # float8 reference reads 1.33-1.75, so no limit between the two has
    # room and the chip's parameters hold the median alone; float32 on
    # both sides (the rehearsal) flips nothing and holds the maximum too
    ok = n > 1
    if "logits_atol" in p:
        tol = float(p["logits_atol"]) + float(p.get("logits_rtol", 0.0)) * scale
        ok = ok and notes["logits_max_err"] <= tol and notes["value_max_err"] <= tol
    ok = ok and notes["logits_median_err"] <= float(p["logits_median_atol"])
    ok = ok and notes["value_median_err"] <= float(p["logits_median_atol"])
    ok = ok and notes["loss_err"] <= float(p["loss_atol"])
    ok = ok and notes["mtp_loss_err"] <= float(p["mtp_loss_atol"])
    ok = ok and notes["grad_norm_rel_err"] <= float(p["grad_norm_rtol"])
    ok = ok and abs(notes["update_gain"] - 1.0) <= float(p["update_rtol"])
    ok = ok and abs(notes["update_norm_ratio"] - 1.0) <= float(p["update_rtol"])
    steps = st.steps
    counters = result["counters"]
    ok = ok and result["failed"] == 0
    ok = ok and all(m.get("nonfinite_grads", 0.0) == 0.0 for m in steps + [probe.metrics])
    # a step's real tokens are a whole number of its rows' slots
    whole = all(
        abs(m["real_token_frac"] * st.slots - round(m["real_token_frac"] * st.slots)) < 1e-2
        and 0 < m["real_token_frac"] <= 1.0
        for m in steps
    )
    # every real token of every routed layer made its picks, each of one kind
    import joyai_work

    k, layers = int(cfg["num_experts_per_tok"]), joyai_work.routed_layers(cfg)
    picks_ok = all(
        abs(m["moe_held_picks"] + m["moe_absent_picks"] - k * layers * m["real_token_frac"] * st.slots)
        < 0.5
        for m in steps
    )
    # every step was taken: the probe, the warm-up and the window's
    ok = ok and whole and picks_ok
    ok = ok and int(st.agent.state.step) == len(steps) + int(p["warmup_steps"]) + 1
    notes.update(
        steps=len(steps), total_loss=steps[-1]["total_loss"], picks_ok=picks_ok,
        real_tokens=counters["real_tokens_in_window"],
        mtp_loss_in_window=counters["mtp_loss"], mtp_top1_match=counters["mtp_top1_match"],
    )
    return bool(ok), notes
