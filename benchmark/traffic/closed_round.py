"""Traffic driver ``closed_round``: the whole generate -> score -> learn ->
push loop, one round after another, through
``SequenceRLTrainer.train_round()`` and nothing inside it.

The two halves the other token cells measure apart run here on one chip
and take turns on it: the continuous engine samples one group of
``samples_per_prompt`` completions of one new prompt, the driver's seeded
reward scores them, the program packs them into learner rows and inserts
them into its sequence replay, the packed token-PPO learner takes one
step on ``rows_per_step`` sampled rows, and the new weights are pushed to
the engine (a device-side copy of the whole tree and a flush of the prefix
cache) before the next round's prompt is admitted.

Loaded and not copied: ``group_rollout``'s seeded weights (``model.init``
and the EOS shaping that makes output lengths geometric) and its check of
recorded log-probabilities against the plain reference forward;
``packed_learn``'s probe step and its check against
``reference/token_ppo.py``.  The learner and the engine are the program's
own, built by ``SequenceRLTrainer`` from one set of program arguments.

Prompts come from the seed (one a round: length uniform in ``prompt_len``,
tokens uniform over the vocabulary without the EOS id) and so does the
reward (a seeded table over the vocabulary, averaged over a completion's
tokens).  Set-up, in order: the learner with the seeded weights, the
trainer and its engine; one all-padding insert of every row bucket
(``seq_add`` builds once a bucket; priority 0 is never sampled); one group
generated and inserted outside any round, on which the learner takes its
first step, the **probe** (the plain reference follows an optimiser's
first step only); then warm-up rounds that visit every prompt bucket the
traffic can reach (the first one twice, as in ``group_rollout``).

The window runs from one completed round to the first round completed
after ``--seconds``.  The driver brackets each round with ``bench.round``
and takes, at the window's two ends, the program's always-on span totals
(``tracing.span_totals``: count and seconds per span name, no profiler
and no sampling) and afterwards its slow-span events; a program that has
neither (the parent of the PR that brought them) gives empty tables, and
the readers built on them return nothing.

``rollout_tokens_per_s`` is by its own definition: the engine's
decode-token count at the window's two ends over the window, response
tokens a second of the WHOLE loop, cross-checked against the rounds' own
counts (every round ends with all lanes drained, so the two agree to the
token).  ``paged_attn_roofline``'s bytes are the traced rounds' own: every
sequence of a round begins and ends inside it, and the seeded task keeps
the lengths it scored.

``correct``, all of:

- after the window one more group is generated under the learner's
  current weights and held to the plain reference forward
  (``group_rollout``'s check and bounds, with the learner's parameters in
  the seeded weights' place); its sequences carry the engine's newest
  generation;
- ``engine.generation`` equals the rounds taken (one push a learn step),
  and a few named leaves of the engine's snapshot equal the learner's bit
  for bit: at a learning rate of 1e-6 the log-probability bound alone
  could not tell a push that arrived from one that did not;
- the probe step against the reference (``packed_learn``'s check: forward,
  loss, gradient norm, the optimiser's update) and every step taken;
- every round's ``staleness`` within ``max_staleness``: what the replay
  ring allows under the traffic, and 1 only where a round's insert fills
  it (the trainer samples its learn batch from a prioritised ring of at
  least ``genrl_batch`` rows, a round inserts 1 to 4 of them; PERF.md,
  section 4).

``failed`` counts the window's rounds whose learn step was skipped or
whose staleness passed that bound.

Parameters (``workloads/<cell>.json``): ``argv``, ``lanes``,
``samples_per_prompt``, ``prompt_len`` [lo, hi], ``max_new_tokens``,
``eos_prob``, ``pack_len``, ``rows_per_step``, ``replay_rows``,
``learning_rate``, ``max_staleness``, ``check_sequences``, ``logp_atol``,
``value_atol``, ``probe_len`` and the probe's bounds (``logits_atol``,
``logits_rtol``, ``loss_atol``, ``grad_norm_rtol``, ``update_rtol``),
``snapshot_leaves`` (paths into the parameter tree), ``trace_seconds``.
"""

from __future__ import annotations

import dataclasses
import math
import time
from types import SimpleNamespace

import harness
import numpy as np

_base = harness.load_module("traffic", "group_rollout")
_learn = harness.load_module("traffic", "packed_learn")

ROOT_SPAN = "genrl.round"


class _SeededTask:
    """One prompt a call, and a reward, both from the seed."""

    def __init__(self, ctx, vocab, eos):
        self.lo, self.hi = (int(x) for x in ctx.params["prompt_len"])
        self.max_prompt_len = self.hi
        self.vocab, self.eos = vocab, eos
        self.table = np.random.default_rng(ctx.seed + 2).random(vocab).astype(np.float32)
        self.forced = []  # warm-up: prompt lengths to visit, in order
        self.lengths = []  # a scored round's (prompt, response) lengths

    def sample_prompts(self, batch, rng):
        lengths = np.array(
            [self.forced.pop(0) if self.forced else int(rng.integers(self.lo, self.hi + 1))
             for _ in range(batch)], np.int32,
        )
        prompts = rng.integers(0, self.vocab - 1, size=(batch, self.hi))
        prompts = np.where(prompts >= self.eos, prompts + 1, prompts).astype(np.int32)
        return np.where(np.arange(self.hi)[None] < lengths[:, None], prompts, 0), lengths

    def reward(self, response_tokens):
        return float(self.table[np.asarray(response_tokens)].mean())

    def score(self, prompts, prompt_len, response, response_len):
        self.lengths.append((np.array(prompt_len), np.array(response_len)))
        return np.array(
            [self.reward(response[i, : max(int(response_len[i]), 1)]) for i in range(len(response))],
            np.float32,
        )


def _program_args(ctx):
    from scalerl_tpu.config import GenRLArguments, parse_args

    p = ctx.params
    argv = (
        list(p["argv"]) + ctx.reference.program_argv(ctx.config)
        + ["--genrl-engine", "continuous", "--genrl-lanes", str(p["lanes"]),
           "--genrl-batch", str(p["samples_per_prompt"]),
           "--samples-per-prompt", str(p["samples_per_prompt"]),
           "--prompt-len", str(p["prompt_len"][1]),
           "--max-new-tokens", str(p["max_new_tokens"]),
           "--eos-token", str(ctx.config["eos_token_id"]),
           "--learner-packing", "--learner-pack-len", str(p["pack_len"]),
           "--genrl-sample-batch", str(p["rows_per_step"]),
           "--genrl-buffer-sequences", str(p["replay_rows"]),
           "--learning-rate", str(p["learning_rate"]), "--genrl-push-every", "1",
           "--seed", str(ctx.seed), "--platform", "cpu" if ctx.rehearse else "tpu"]
    )
    args = parse_args(GenRLArguments, argv)
    args.validate()
    return args


def _span_totals():
    """The program's always-on totals, ``{}`` where it keeps none."""
    from scalerl_tpu.runtime import tracing

    read = getattr(tracing, "span_totals", None)
    return read() if read is not None else {}


def _slow_spans(since, until):
    from scalerl_tpu.runtime import tracing

    read = getattr(tracing, "slow_spans", None)
    # the tracer stamps time.monotonic(), the window time.perf_counter():
    # one clock on Linux, and nothing is placed where they are two
    if read is None or abs(time.monotonic() - time.perf_counter()) > 1e-3:
        return None
    return read(since, until)


def _generate_group(st, tag):
    """One group through the engine alone, outside any round."""
    n = st.args.samples_per_prompt
    prompts, lengths = st.task.sample_prompts(1, st.rng)
    if not st.engine.submit_group(prompts[0], n, int(lengths[0]), tag=tag):
        raise RuntimeError("the engine shed a group: the queue is unbounded here")
    done = []
    while len(done) < n:
        done.extend(st.engine.step())
    return done


def _insert(st, completions, rows=None):
    """Pack completions into learner rows and insert them, as a round does:
    bucketed up the row ladder, or to ``rows`` (with no completions: rows
    of padding alone, priority 0)."""
    from scalerl_tpu.data.sequence_replay import seq_add
    from scalerl_tpu.genrl.rollout import pack_learner_batch
    from scalerl_tpu.utils.buckets import bucket_for

    packed = pack_learner_batch(
        [c.prompt for c in completions], [c.response_tokens for c in completions],
        [c.behavior_logp for c in completions], [c.values for c in completions],
        np.array([st.task.reward(c.response_tokens) for c in completions], np.float32),
        np.array([c.generation for c in completions], np.int32), st.args.learner_pack_len,
    )
    if rows is None:
        rows = bucket_for(max(packed.rows, 1), st.row_buckets)
    fields, priorities = packed.bucketed(rows).fields()
    st.trainer.replay = seq_add(st.trainer.replay, fields, (), priorities)


def build(ctx):
    import jax
    import jax.numpy as jnp
    from scalerl_tpu.agents.token_ppo import TokenPPOAgent
    from scalerl_tpu.ops.pallas_per import resolve_sample_method
    from scalerl_tpu.runtime import telemetry
    from scalerl_tpu.trainer.sequence_rl import SequenceRLTrainer, build_genrl_model
    from scalerl_tpu.utils.buckets import default_buckets

    p = ctx.params
    args = _program_args(ctx)
    model = build_genrl_model(args)
    eos = int(ctx.config["eos_token_id"])
    agent = TokenPPOAgent(args, model)
    # the benchmark's seeded weights (the agent's own init from the same
    # key, with the EOS column shaped) as the parameters and the frozen copy
    seeded = _base._seeded_weights(model, ctx.seed, eos, float(p["eos_prob"]), args.vocab_size)
    agent.state = agent.state.replace(
        params=seeded, ref_params=jax.tree_util.tree_map(jnp.copy, seeded)
    )
    del seeded
    task = _SeededTask(ctx, args.vocab_size, eos)
    trainer = SequenceRLTrainer(args, task=task, agent=agent)
    jax.block_until_ready(agent.state.params)
    ctx.log("learner and engine built" + (" (learner on a mesh)" if agent.mesh is not None else ""))
    st = SimpleNamespace(
        args=args, model=trainer.agent.model, agent=agent, trainer=trainer, engine=trainer.engine,
        task=task, eos=eos, rng=np.random.default_rng(ctx.seed + 3),
        row_buckets=default_buckets(args.genrl_batch),
        meter=telemetry.get_registry().meter(_base._DECODE_METER),
        rounds=[], warm_rounds=0,
    )
    # every insert shape once, on the empty replay: rows of padding alone
    for bucket in st.row_buckets:
        _insert(st, [], rows=bucket)
    # the first prompt bucket, outside any round: the probe's batch
    lo, hi = task.lo, task.hi
    buckets = st.engine.config.resolved_prompt_buckets()
    forced = sorted({min(b, hi) for b in buckets if b >= lo} | {hi})
    task.forced = [forced[0]]
    _insert(st, _generate_group(st, tag="probe"))
    probe_view = SimpleNamespace(
        args=args, agent=agent, replay=trainer.replay,
        key=jax.random.PRNGKey(ctx.seed + 4), method=resolve_sample_method("auto"),
    )
    st.probe = _learn._probe(ctx, probe_view)
    # warm-up rounds: every prompt bucket (the first again: the engine's very
    # first prefill saw its fresh lane state, group_rollout's note), every
    # program of a round, the first pushes
    task.forced = forced + [forced[0]]
    while task.forced:
        _round(ctx, st)
    st.warm_rounds = len(st.rounds)
    st.rounds.clear()
    task.lengths.clear()
    ctx.log(
        f"warm: {st.warm_rounds} rounds, {st.engine.stats()['macro_steps']} macro-steps, "
        f"generation {st.engine.generation}"
    )
    return st


def _round(ctx, st):
    with ctx.spans.span("bench.round"):
        metrics = st.trainer.train_round()
    st.rounds.append(metrics)
    return metrics


def _delta(end, start):
    return {
        name: {k: v - start.get(name, {}).get(k, 0.0) for k, v in row.items()}
        for name, row in end.items()
    }


def _cumulative(st):
    return {"tokens": st.meter.total, "rounds": len(st.rounds)}


def run(ctx, st):
    import trace_reduce
    import work

    p, engine = ctx.params, st.engine
    s0, t0_tokens, spans0 = engine.stats(), st.meter.total, _span_totals()
    ctx.open_window()
    while True:
        _round(ctx, st)
        if ctx.tick(_cumulative(st)):
            break
    ctx.close_window(_cumulative(st))
    spans = _delta(_span_totals(), spans0)
    tokens = st.meter.total - t0_tokens
    s1 = engine.stats()
    rounds = st.rounds
    steps = s1["macro_steps"] - s0["macro_steps"]
    occupancy = (
        s1["mean_occupancy"] * s1["macro_steps"] - s0["mean_occupancy"] * s0["macro_steps"]
    ) / max(steps, 1)
    sequences = len(rounds) * st.args.genrl_batch
    failed = sum(
        1 for m in rounds
        if m.get("skipped_steps", 0.0) > 0.0 or not math.isfinite(m["total_loss"])
        or m["staleness"] > float(p["max_staleness"])
    )
    counters = {
        "rounds_in_window": len(rounds),
        "tokens_in_window": tokens,
        "round_tokens_in_window": sum(m["decode_tokens"] for m in rounds),
        "sequences_in_window": sequences,
        "macro_steps_in_window": steps,
        "lane_occupancy": occupancy,
        "mean_response_len": tokens / sequences,
        "staleness_mean": sum(m["staleness"] for m in rounds) / len(rounds),
        "staleness_max": max(m["staleness"] for m in rounds),
        "real_tokens_in_window": sum(m["real_token_frac"] for m in rounds) * _slots(st),
        "span_totals": spans,
    }
    # the learner is paced by generation here (one step of 2 rows a round):
    # its rate says what round_ms_mean says, so it is logged, not judged
    ctx.log(
        f"learner over the whole loop: {counters['real_tokens_in_window'] / ctx.window_s:.1f} "
        f"real tokens/s, {len(rounds) / ctx.window_s:.3f} steps/s"
    )
    if ctx.trace_counters.get("end"):
        # cached tokens the decode steps of the traced rounds had to read:
        # every sequence of a round begins and ends inside it
        first, last = (ctx.trace_counters[k]["rounds"] for k in ("start", "end"))
        counters["traced_kv_bytes"] = work.gpt2_kv_bytes_per_token(ctx.config, 4) * sum(
            work.decode_kv_tokens_read(n, m)
            for prompt_len, response_len in st.task.lengths[first:last]
            for n, m in zip(prompt_len, response_len)
        )
    slow = _slow_spans(ctx.t_open, ctx.t_close)
    if slow is not None:
        # a slow read inside a slow macro-step inside a slow round counts once
        counters["slow_spans"] = slow
        counters["slow_span_s"] = trace_reduce.total(
            trace_reduce.union([(e["t_start"], e["t_end"]) for e in slow])
        )
        ctx.log(f"slow spans that began in the window: {len(slow)}, {counters['slow_span_s']:.3f} s in their union")
        for event in slow:
            ctx.log("  slow span:", {k: v for k, v in event.items() if k not in ("t_wall", "t_mono", "host_id", "seq", "kind")})
    root = spans.get(ROOT_SPAN)
    if root:
        ctx.log(
            f"span totals in the window: {ROOT_SPAN} {int(root['count'])} x "
            f"{1e3 * root['seconds'] / max(root['count'], 1):.1f} ms; "
            + ", ".join(
                f"{name} {100.0 * row['seconds'] / root['seconds']:.2f}%"
                for name, row in sorted(spans.items()) if name.startswith("round.")
            )
        )
    return {
        "attempted": len(rounds),
        "failed": failed,
        "end_to_end": {"rollout_tokens_per_s": tokens / ctx.window_s},
        "counters": counters,
    }


def _slots(st):
    return st.args.genrl_sample_batch * st.args.learner_pack_len


def _leaf(tree, path):
    for key in path.split("/"):
        tree = tree[key]
    return tree


def check(ctx, st, result):
    import jax
    import jax.numpy as jnp

    p = ctx.params
    counters = result["counters"]
    rounds, engine, agent = st.rounds, st.engine, st.agent
    pushes = st.warm_rounds + len(rounds)
    notes = {
        "rounds": len(rounds), "generation": engine.generation,
        "staleness_max": counters["staleness_max"],
        "tokens_in_window": counters["tokens_in_window"],
    }
    # one push a learn step, and every one of them arrived
    ok = engine.generation == pushes == st.trainer.learn_steps
    snapshot, _gen = engine._snapshot_params()
    same = {
        path: bool(jnp.array_equal(_leaf(snapshot, path), _leaf(agent.state.params, path)))
        for path in p["snapshot_leaves"]
    }
    moved = {
        path: float(jnp.max(jnp.abs(_leaf(agent.state.params, path) - _leaf(agent.state.ref_params, path))))
        for path in p["snapshot_leaves"]
    }
    notes.update(snapshot_equal=same, moved_from_frozen=moved)
    ok = ok and all(same.values()) and all(m > 0.0 for m in moved.values())
    # the rounds' own count of response tokens is the engine's
    ok = ok and counters["round_tokens_in_window"] == counters["tokens_in_window"]
    ok = ok and result["failed"] == 0 and 1.0 <= counters["staleness_max"] <= float(p["max_staleness"])

    # one more group, under the learner's current weights, against the
    # reference forward: group_rollout's check with those weights in the
    # seeded ones' place
    done0, tokens0 = engine.stats()["completed"], st.meter.total
    extra = _generate_group(st, tag="check")
    ok = ok and all(c.generation == engine.generation for c in extra)
    view = SimpleNamespace(
        completed=extra, model=st.model, eos=st.eos, args=st.args,
        engine=SimpleNamespace(stats=lambda: {"completed": engine.stats()["completed"] - done0}),
        meter=SimpleNamespace(total=st.meter.total - tokens0),
    )
    _base._seeded_weights = lambda *_a, **_k: agent.state.params
    rollout_ok, rollout_notes = _base.check(
        ctx, view, {"in_window": extra, "counters": {"tokens_in_window": view.meter.total}}
    )
    notes.update({f"rollout_{k}": v for k, v in rollout_notes.items()})

    # the probe step against the reference, and every step taken since
    learn_view = SimpleNamespace(
        probe=st.probe, args=st.args, agent=agent, steps=rounds, slots=_slots(st)
    )
    learn_ctx = dataclasses.replace(ctx, params={**p, "warmup_steps": st.warm_rounds})
    learn_ok, learn_notes = _learn.check(learn_ctx, learn_view, result)
    notes.update({f"learn_{k}": v for k, v in learn_notes.items()})
    return bool(ok and rollout_ok and learn_ok), notes
