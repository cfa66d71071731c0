"""Traffic driver ``packed_learn``: the packed token-PPO learner alone,
one step after another, from a replay filled in set-up.

Builds the token model with ``build_genrl_model`` and the learner as
``SequenceRLTrainer`` does (``TokenPPOAgent``, then
``maybe_enable_mesh_from_args``, so ``--dp-size``/``--mp-size`` in ``argv``
shard it), fills a sequence replay once with seeded synthetic completions
through the program's own packing (``pack_completions``,
``packed_rows_from_completions``, ``seq_add``), and then repeats what a
training round does after generation: ``seq_sample`` and
``TokenPPOAgent.learn``, whose return is a blocking read of the step's
metrics.  There is no engine.

Completions come from the seed: prompt lengths uniform in ``prompt_len``,
response lengths lognormal (``response_median``, ``response_sigma``) capped
at ``max_new_tokens``, tokens uniform over the vocabulary, behaviour
log-probabilities and values as a random-weight policy would have given.

``correct`` rests on one probe step, the learner's first, from the seeded
weights: a sampled batch whose loss mask is cut down to the first
``probe_len`` tokens of one packed sequence, so that the plain reference
(``reference/token_ppo.py`` over ``reference/<config>.py``) can follow it
in float32 on the host's CPU after the window.  Forward (logits of that
sequence at its offset in the packed row), loss, gradient norm and the
update the optimiser made of the gradient are each held to the reference.

Parameters (``workloads/<cell>.json``): ``argv``, ``completions``,
``prompt_len`` [lo, hi], ``response_median``, ``response_sigma``,
``max_new_tokens``, ``pack_len``, ``replay_rows`` (capacity, and the one
insert's row count), ``rows_per_step``, ``warmup_steps``, ``probe_len``,
and the bounds of the probe: ``logits_atol``, ``logits_rtol``,
``loss_atol``, ``grad_norm_rtol``, ``update_rtol``.
"""

from __future__ import annotations

import math
from contextlib import nullcontext
from types import SimpleNamespace

import numpy as np


def _synthetic_completions(ctx, vocab):
    p = ctx.params
    rng = np.random.default_rng(ctx.seed)
    lo, hi = p["prompt_len"]
    out = []
    for _ in range(int(p["completions"])):
        m = int(rng.integers(lo, hi + 1))
        r = int(np.clip(
            rng.lognormal(math.log(p["response_median"]), p["response_sigma"]),
            1, p["max_new_tokens"],
        ))
        out.append(SimpleNamespace(
            prompt=rng.integers(0, vocab, size=m).astype(np.int32), prompt_len=m,
            response_tokens=rng.integers(0, vocab, size=r).astype(np.int32),
            # log-softmax of N(0, 1) logits at a random token: -ln V - 1/2 +- 1
            behavior_logp=(-math.log(vocab) - 0.5 + rng.normal(size=r)).astype(np.float32),
            values=(0.1 * rng.normal(size=r)).astype(np.float32),
            generation=0,
        ))
    rewards = rng.random(len(out)).astype(np.float32)
    return out, rewards


def build_learner(ctx):
    """The model and the (sharded) learner; nothing has run."""
    import jax
    from scalerl_tpu.agents.token_ppo import TokenPPOAgent
    from scalerl_tpu.config import GenRLArguments, parse_args
    from scalerl_tpu.parallel.train_step import maybe_enable_mesh_from_args
    from scalerl_tpu.trainer.sequence_rl import build_genrl_model

    p = ctx.params
    argv = (
        list(p["argv"]) + ctx.reference.program_argv(ctx.config)
        + ["--learner-packing", "--learner-pack-len", str(p["pack_len"]),
           "--prompt-len", str(p["prompt_len"][1]),
           "--max-new-tokens", str(p["max_new_tokens"]),
           "--genrl-sample-batch", str(p["rows_per_step"]),
           "--genrl-batch", str(p["replay_rows"]),
           "--genrl-buffer-sequences", str(p["replay_rows"]),
           "--seed", str(ctx.seed), "--platform", "cpu" if ctx.rehearse else "tpu"]
    )
    args = parse_args(GenRLArguments, argv)
    args.validate()
    model = build_genrl_model(args)
    # the unsharded float32 train state (params, frozen reference copy, two
    # Adam moments) is built by the agent's constructor before any mesh
    # exists; a cell that takes more than one chip has a state that one
    # chip does not hold, so it is built in host memory and enable_mesh
    # moves each shard to its device
    staging = (
        jax.default_device(jax.devices("cpu")[0])
        if int(ctx.workload["chips"]) > 1 else nullcontext()
    )
    with staging:
        agent = TokenPPOAgent(args, model)
    maybe_enable_mesh_from_args(agent, args)
    return SimpleNamespace(args=args, model=model, agent=agent)


def build(ctx):
    import jax
    import work
    from scalerl_tpu.data.sequence_replay import seq_add, seq_init
    from scalerl_tpu.genrl.rollout import (
        pack_completions,
        packed_field_shapes,
        packed_rows_from_completions,
    )
    from scalerl_tpu.ops.pallas_per import resolve_sample_method

    p = ctx.params
    st = build_learner(ctx)
    args, agent = st.args, st.agent
    jax.block_until_ready(agent.state.params)
    ctx.log("learner built" + (" and sharded" if agent.mesh is not None else ""))

    completions, rewards = _synthetic_completions(ctx, args.vocab_size)
    packed = pack_completions(completions, p["prompt_len"][1], p["max_new_tokens"])
    rows = packed_rows_from_completions(packed, rewards, int(p["pack_len"]))
    if rows.sequences_shed or rows.rows > int(p["replay_rows"]):
        raise RuntimeError(
            f"{rows.rows} rows ({rows.sequences_shed} shed) do not fit "
            f"{p['replay_rows']} replay rows"
        )
    segment_lengths = [
        int(n) for row in rows.segment_ids
        for n in np.bincount(row[row > 0])[1:] if n > 0
    ]
    host_rows = rows
    rows = rows.bucketed(int(p["replay_rows"]))  # one insert shape for every seed
    fields, priorities = rows.fields()
    replay = seq_init(packed_field_shapes(int(p["pack_len"])), (), int(p["replay_rows"]))
    replay = seq_add(replay, fields, (), priorities)
    st.__dict__.update(
        replay=replay, host_rows=host_rows,
        key=jax.random.PRNGKey(ctx.seed + 1),
        method=resolve_sample_method("auto"),
        slots=int(p["rows_per_step"]) * int(p["pack_len"]),
        steps=[], flops_per_token=work.gpt2_train_flops_per_token(
            ctx.config, work.mean_attended_keys(segment_lengths)
        ),
    )
    ctx.log(
        f"replay holds {host_rows.rows} rows of {p['pack_len']}: "
        f"{host_rows.sequences_packed} sequences, {host_rows.real_tokens} real tokens"
    )
    st.probe = _probe(ctx, st)
    for _ in range(int(p["warmup_steps"])):
        _step(ctx, st)
    st.steps.clear()
    return st


def _sample(st):
    import jax
    from scalerl_tpu.data.sequence_replay import seq_sample

    st.key, sub = jax.random.split(st.key)
    batch, _core, _idx, weights = seq_sample(
        st.replay, sub, st.args.genrl_sample_batch, method=st.method
    )
    batch = dict(batch)
    batch["is_weight"] = weights
    return batch


def _step(ctx, st):
    """What a training round does after generation: sample, then learn."""
    with ctx.spans.span("bench.sample"):
        batch = _sample(st)
    with ctx.spans.span("bench.learn_call"):
        metrics = st.agent.learn(batch)  # one batched, blocking metric read
    st.steps.append(metrics)
    return metrics


def _probe(ctx, st):
    """The learner's first step, from the seeded weights, on a sampled
    batch whose loss counts one sequence's first ``probe_len`` tokens
    only: what the system made of it (logits at the sequence's place in
    the packed row, the step's metrics, the weights before and after),
    kept on the host for ``check``.  Every program it runs has the shapes
    of the window's own, whatever the seed."""
    import jax
    import jax.numpy as jnp

    P = int(ctx.params["probe_len"])
    batch = _sample(st)
    seg = np.asarray(batch["segment_ids"])
    # the second sequence of a row sits at an offset, behind another one
    # that it must not see; a row with one sequence gives that one
    row = next(r for r in range(len(seg)) if seg[r].max() > 0)
    which = min(2, int(seg[row].max()))
    where = np.flatnonzero(seg[row] == which)
    offset, length = int(where[0]), min(len(where), P)
    keep = np.zeros(seg.shape, np.float32)
    keep[row, offset : offset + length] = 1.0
    probe = dict(batch, mask=batch["mask"] * jnp.asarray(keep))

    def head(name):  # the sequence's first P slots of a per-token field
        out = np.zeros(P, np.asarray(probe[name]).dtype)
        out[:length] = np.asarray(probe[name])[row, offset : offset + length]
        return out

    seq = {k: head(k) for k in ("tokens", "mask", "behavior_logp", "value", "reward")}
    if seq["mask"].sum() < 1:
        raise RuntimeError(f"no response token in the first {P} of a {len(where)}-token sequence")

    # P slots of the row from ``start``: the sequence's probed tokens are
    # among them wherever in the row it sits
    start = min(offset, seg.shape[1] - P)

    @jax.jit
    def forward(params, tokens, positions, seg, row, start):
        out = st.agent.model.apply(params, tokens, positions=positions, segment_ids=seg)
        logits = jax.lax.dynamic_slice_in_dim(out.policy_logits[row], start, P)
        return logits, jax.lax.dynamic_slice_in_dim(out.baseline[row], start, P)

    before = jax.device_get(st.agent.state.params)  # the step donates them
    logits, values = jax.device_get(forward(
        st.agent.state.params,
        *(np.asarray(probe[k]) for k in ("tokens", "positions", "segment_ids")),
        np.int32(row), np.int32(start),
    ))
    at = slice(offset - start, offset - start + length)
    metrics = st.agent.learn(probe)
    after = jax.device_get(st.agent.state.params)
    ctx.log(f"probe step on {int(seq['mask'].sum())} response tokens of {length} at offset {offset}")
    return SimpleNamespace(
        seq=seq, length=length, before=before, after=after, metrics=metrics,
        logits=logits[at], values=values[at],
    )


def run(ctx, st):
    real = 0.0
    ctx.open_window()
    while True:
        m = _step(ctx, st)
        real += m["real_token_frac"] * st.slots
        if ctx.tick({"steps": len(st.steps)}):
            break
    ctx.close_window({"steps": len(st.steps)})
    steps = st.steps
    failed = sum(
        1 for m in steps
        if m.get("skipped_steps", 0.0) > 0.0 or not math.isfinite(m["total_loss"])
    )
    return {
        "attempted": len(steps),
        "failed": failed,
        "end_to_end": {"learn_tokens_per_s": real / ctx.window_s},
        "counters": {
            "steps_in_window": len(steps),
            "real_tokens_in_window": real,
            "real_token_frac": sum(m["real_token_frac"] for m in steps) / len(steps),
            "train_flops_per_token": st.flops_per_token,
        },
    }


def check(ctx, st, result):
    """The probe step against the plain reference, which follows it in
    float32 on the host's CPU from the same seeded weights; then the
    window's steps' own flags and counts."""
    import jax
    from harness import load_module

    p = ctx.params
    probe, args = st.probe, st.args
    plain = load_module("reference", "token_ppo")
    n_head = int(ctx.config["n_head"])
    hyper = {
        k: getattr(args, k) for k in (
            "clip_range", "value_cost", "entropy_cost", "kl_cost", "adv_norm",
            "learning_rate", "max_grad_norm",
        )
    }
    cpu = jax.devices("cpu")[0]
    ref = plain.follow(
        *jax.device_put((probe.before, probe.after, probe.seq), cpu),
        lambda w, t: ctx.reference.forward(w, t, n_head), hyper,
    )
    n = probe.length
    scale = float(np.max(np.abs(ref["logits"][:n])))
    notes = {
        "logits_max_err": float(np.max(np.abs(probe.logits - ref["logits"][:n]))),
        "value_max_err": float(np.max(np.abs(probe.values - ref["values"][:n]))),
        "logits_scale": scale,
        "tokens_checked": n,
        "loss_err": max(
            abs(float(probe.metrics[k]) - float(ref[k]))
            for k in ("total_loss", "pg_loss", "value_loss", "entropy")
        ),
        "grad_norm_rel_err": abs(float(probe.metrics["grad_norm"]) / float(ref["grad_norm"]) - 1.0),
        "update_gain": float(ref["update_gain"]),
        "update_norm_ratio": float(ref["update_norm_ratio"]),
    }
    # float32 operands rounded to bfloat16 on the MXU (the TPU default)
    # through every layer and back, against a float32 reference.  The v5e
    # showed at gpt2-medium (PERF.md, PR 22): logits 0.036 off at a scale
    # of 5, loss 0.007, gradient norm 0.3%, update 1e-4 of the
    # reference's; the bounds in the cells' files are three to ten times
    # that, and a dropped value-loss gradient is 8% and 5% off the last two
    tol = float(p["logits_atol"]) + float(p["logits_rtol"]) * scale
    ok = n > 1 and notes["logits_max_err"] <= tol and notes["value_max_err"] <= tol
    ok = ok and notes["loss_err"] <= float(p["loss_atol"])
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
    # every step was taken: the probe, the warm-up and the window's
    ok = ok and whole and int(st.agent.state.step) == len(steps) + int(p["warmup_steps"]) + 1
    notes.update(
        steps=len(steps), total_loss=steps[-1]["total_loss"],
        real_tokens=counters["real_tokens_in_window"],
    )
    return ok, notes
