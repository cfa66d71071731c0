"""Traffic driver ``group_rollout``: group sampling on the continuous
engine, closed loop, no learner.

Builds the token model with ``build_genrl_model`` and the
``ContinuousEngine`` with the configuration ``SequenceRLTrainer`` gives it,
from the same program arguments, and calls only ``submit_group``, ``step``,
``stats`` and ``push_params``.  The loop keeps the lanes full: before each
``step()`` it submits one group of ``samples_per_prompt`` over one new
prompt whenever that many lanes are free and none is queued, so that every
admission has one shape.  Prompts come from the seed: lengths uniform in
``prompt_len`` (inclusive), tokens uniform over the vocabulary without the
EOS id.  Output lengths are the engine's own: it stops a lane on
``eos_token`` or at ``max_new_tokens``, and the benchmark's seeded weights
give the EOS id a probability of about ``eos_prob`` per token (EOS column
of the policy head zeroed, its bias set), which makes lengths geometric.

Parameters (``workloads/<cell>.json``): ``argv``, ``lanes``,
``samples_per_prompt``, ``prompt_len`` [lo, hi], ``max_new_tokens``,
``eos_prob``, ``warmup_tokens`` (decode tokens harvested before the window
opens), ``check_sequences``, ``logp_atol``, ``value_atol``.
"""

from __future__ import annotations

import math
from types import SimpleNamespace

import numpy as np

_DECODE_METER = "genrl.decode_tokens_per_s"


def _seeded_weights(model, seed, eos_token, eos_prob, vocab):
    """``model.init`` and the EOS shaping in one jitted call on the device."""
    import jax
    import jax.numpy as jnp

    # E[exp(l)] for the other tokens' logits l ~ N(0, 1): the final norm has
    # unit variance per feature and the head is lecun-normal
    bias = math.log(eos_prob / (1.0 - eos_prob)) + math.log(vocab - 1) + 0.5

    def init(key):
        params = model.init(key, jnp.zeros((1, 2), jnp.int32))
        head = dict(params["params"]["policy_head"])
        head["kernel"] = head["kernel"].at[:, eos_token].set(0.0)
        head["bias"] = head["bias"].at[eos_token].set(bias)
        return {**params, "params": {**params["params"], "policy_head": head}}

    return jax.jit(init)(jax.random.PRNGKey(seed))


def build_engine(ctx):
    """The model, its seeded weights and the engine; nothing has run."""
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
    params = _seeded_weights(model, ctx.seed, eos, float(p["eos_prob"]), args.vocab_size)
    jax.block_until_ready(params)
    ctx.log("weights made on the device")
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
    # the engine keeps its own copy of the weights; the check makes the
    # benchmark's again from the seed rather than hold a second copy
    return SimpleNamespace(
        args=args, model=model, engine=engine, eos=eos, params=params,
        rng=np.random.default_rng(ctx.seed), completed=[], submitted=0, lanes_submitted=0,
        meter=telemetry.get_registry().meter(_DECODE_METER),
    )


def build(ctx):
    st = build_engine(ctx)
    del st.params
    p, engine = ctx.params, st.engine
    # warm-up: first one small group in each prompt bucket the traffic can
    # reach, all admitted at once (so that no compile waits for lanes to
    # free), then seeded traffic until ``warmup_tokens`` are harvested and
    # a full-size group has been admitted (its fork program).  The first
    # bucket comes twice: the engine's very first prefill sees its fresh
    # (uncommitted) lane state, and JAX builds that program again for the
    # committed state every later call passes (PERF.md, Findings)
    lo, hi = p["prompt_len"]
    buckets = engine.config.resolved_prompt_buckets()
    st.forced = sorted({min(b, hi) for b in buckets if b >= lo} | {hi})
    st.forced.append(st.forced[0])
    st.forced_n = max(1, int(p["lanes"]) // len(st.forced))
    full_groups = len(st.forced) + 1
    base = st.meter.total
    while st.meter.total - base < p["warmup_tokens"] or st.submitted < full_groups:
        _cycle(ctx, st, record=None)
    ctx.log(
        f"warm: {int(st.meter.total - base)} tokens, {len(st.completed)} "
        f"sequences, {engine.stats()['macro_steps']} macro-steps"
    )
    return st


def _cycle(ctx, st, record):
    """Submit at most one group, then one engine step."""
    p = ctx.params
    n = st.forced_n if st.forced else int(p["samples_per_prompt"])
    engine = st.engine
    with ctx.spans.span("bench.submit"):
        if engine.pending == 0 and engine.live_lanes + n <= int(p["lanes"]):
            lo, hi = p["prompt_len"]
            length = st.forced.pop(0) if st.forced else int(st.rng.integers(lo, hi + 1))
            prompt = st.rng.integers(0, st.args.vocab_size - 1, size=length)
            prompt = np.where(prompt >= st.eos, prompt + 1, prompt).astype(np.int32)
            if not engine.submit_group(prompt, n, length, tag=st.submitted):
                raise RuntimeError("the engine shed a group: the queue is unbounded here")
            st.submitted += 1
            st.lanes_submitted += n
    with ctx.spans.span("bench.engine_step"):
        done = engine.step()
    st.completed.extend(done)
    if record is not None:
        record.extend(done)


def _kv_tokens_read(seqs):
    import work

    return sum(
        work.decode_kv_tokens_read(c.prompt_len, len(c.response_tokens)) for c in seqs
    )


def run(ctx, st):
    import work

    engine = st.engine
    in_window = []
    s0 = engine.stats()
    t0_tokens = st.meter.total
    ctx.open_window()
    while True:
        _cycle(ctx, st, record=in_window)
        if ctx.tick({"tokens": st.meter.total}):
            break
    ctx.close_window({"tokens": st.meter.total})
    tokens = st.meter.total - t0_tokens
    s1 = engine.stats()
    steps = s1["macro_steps"] - s0["macro_steps"]
    occupancy = (
        s1["mean_occupancy"] * s1["macro_steps"] - s0["mean_occupancy"] * s0["macro_steps"]
    ) / max(steps, 1)
    response = sum(len(c.response_tokens) for c in in_window)
    counters = {
        "tokens_in_window": tokens,
        "sequences_in_window": len(in_window),
        "macro_steps_in_window": steps,
        "lane_occupancy": occupancy,
        "mean_response_len": response / len(in_window) if in_window else None,
        "prefill_tokens": s1["prefill_tokens"] - s0["prefill_tokens"],
    }
    if ctx.trace_counters.get("end") and in_window:
        # cached tokens each decoded token had to read, from the window's
        # completed sequences, times the tokens decoded while tracing
        traced = ctx.trace_counters["end"]["tokens"] - ctx.trace_counters["start"]["tokens"]
        per_token = _kv_tokens_read(in_window) / max(response, 1)
        counters["traced_kv_bytes"] = (
            traced * per_token * work.gpt2_kv_bytes_per_token(ctx.config, 4)
        )
    return {
        "attempted": st.lanes_submitted,
        "failed": 0,
        "end_to_end": {"rollout_tokens_per_s": tokens / ctx.window_s},
        "counters": counters,
        "in_window": in_window,
    }


def check(ctx, st, result):
    """Prefill then decode through the paged cache against the reference's
    full forward: the log-probability and the value the engine recorded for
    every response token of a seeded sample of completed sequences."""
    import jax
    import jax.numpy as jnp

    p = ctx.params
    notes = {}
    # completed sequences evenly spaced from the shortest to the longest
    pool = sorted(st.completed, key=lambda c: c.prompt_len + len(c.response_tokens))
    picks = np.linspace(0, len(pool) - 1, int(p["check_sequences"])).astype(int)
    sample = [pool[i] for i in sorted(set(picks.tolist()))] if pool else []
    ok = bool(sample)
    n_head = int(ctx.config["n_head"])
    st.params = _seeded_weights(
        st.model, ctx.seed, st.eos, float(p["eos_prob"]), st.args.vocab_size
    )
    ref = jax.jit(lambda params, toks: ctx.reference.token_logprobs(params, toks, n_head))
    err_logp = err_value = 0.0
    checked = 0
    for c in sample:
        m, r = int(c.prompt_len), len(c.response_tokens)
        # padded to the model's length so that one shape compiles; causal,
        # so the pad tail changes nothing before it
        total = st.model.max_len
        toks = np.zeros((1, total), np.int32)
        toks[0, :m] = c.prompt[:m]
        toks[0, m : m + r] = c.response_tokens
        logp, values = ref(st.params, jnp.asarray(toks))
        logp = np.asarray(logp)[0, m - 1 : m + r - 1]
        values = np.asarray(values)[0, m - 1 : m + r - 1]
        err_logp = max(err_logp, float(np.max(np.abs(logp - c.behavior_logp))))
        err_value = max(err_value, float(np.max(np.abs(values - c.values))))
        checked += r
    notes.update(logp_max_err=err_logp, value_max_err=err_value, tokens_checked=checked)
    ok = ok and err_logp <= float(p["logp_atol"]) and err_value <= float(p["value_atol"])
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
    # tokens harvested but not yet completed sit in live lanes: at most
    # lanes x max_new_tokens, never negative
    ok = ok and 0 <= live_tokens <= int(p["lanes"]) * int(p["max_new_tokens"])
    return ok, notes
