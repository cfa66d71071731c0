"""Continuous-batching decode plane (ISSUE 11): parity with the greedy full
forward at temperature 0, the one-batched-transfer-per-macro-step
discipline, zero retraces after warmup, EOS/variable-length harvesting,
page exhaustion backpressure, fragmentation independence, quantized
snapshot pushes, and the trainer riding the engine.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from genrl_reference import greedy_full_forward
from scalerl_tpu.config import GenRLArguments
from scalerl_tpu.genrl.continuous import (
    CompletedSequence,
    ContinuousConfig,
    ContinuousEngine,
)
from scalerl_tpu.genrl.rollout import pack_completions, sequence_field_shapes
from scalerl_tpu.models.transformer import TransformerPolicy
from scalerl_tpu.runtime import telemetry
from scalerl_tpu.trainer.sequence_rl import SequenceRLTrainer

V = 11
P_MAX, R_MAX = 6, 4


def _model():
    return TransformerPolicy(
        num_actions=V, vocab_size=V, d_model=32, num_heads=2,
        num_layers=1, max_len=16,
    )


@pytest.fixture(scope="module")
def setup():
    """One model + one greedy (temperature 0) engine, plus the reference
    round: greedy decoding by the full forward (``model.apply`` on the
    whole sequence) — shared by the parity / transfer / retrace /
    fragmentation tests to keep compiles off the tier-1 clock."""
    m = _model()
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32))
    rng = np.random.default_rng(1)
    prompts = rng.integers(2, V, size=(5, P_MAX)).astype(np.int32)
    lengths = np.array([6, 4, 3, 2, 1], np.int32)
    ref = greedy_full_forward(m, params, prompts, lengths, P_MAX, R_MAX)
    cont = ContinuousEngine(
        m, params,
        ContinuousConfig(
            vocab_size=V, max_prompt_len=P_MAX, max_new_tokens=R_MAX,
            temperature=0.0, seed=7, lanes=4, page_size=4,
            steps_per_macro=3, steps_in_flight=1,  # legacy sync semantics
        ),
    )
    return dict(
        model=m, params=params, prompts=prompts, lengths=lengths,
        ref=ref, cont=cont,
    )


def _by_prompt(completions):
    return {tuple(c.prompt.tolist()): c for c in completions}


def test_engine_is_single_device_under_a_meshed_learner(setup):
    """Generation is single-chip: handed a meshed learner's model (its
    activations pinned to the learner mesh) and mesh-sharded params, the
    engine drops the pin and gathers every push onto its one device — a
    sharded tree would make decode SPMD, and the Mosaic paged-attention
    kernel cannot be partitioned."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from scalerl_tpu.parallel import activation_constraint, make_mesh

    mesh = make_mesh("dp=2,mp=2", jax.devices()[:4])
    spread = jax.device_put(setup["params"], NamedSharding(mesh, P()))
    engine = ContinuousEngine(
        setup["model"].clone(constrain=activation_constraint(mesh)),
        spread,
        ContinuousConfig(
            vocab_size=V, max_prompt_len=P_MAX, max_new_tokens=R_MAX,
            lanes=4, page_size=4,
        ),
    )
    assert engine.model.constrain is None

    def devices_of(tree):
        return {
            d for leaf in jax.tree_util.tree_leaves(tree)
            for d in leaf.devices()
        }

    assert devices_of(engine._snapshot_params()[0]) == {engine._device}
    engine.push_params(spread)
    assert devices_of(engine._snapshot_params()[0]) == {engine._device}
    assert devices_of(engine._pools) == {engine._device}


def test_greedy_parity_with_the_full_forward(setup):
    """The acceptance pin: at temperature 0 the engine's token-level
    outputs for any single sequence are IDENTICAL to greedy decoding by
    the full forward (exact tokens, 1e-5 behavior logprobs) — through a
    completely different layout (paged cache and compact prompts against
    no cache and left-padded rows)."""
    cont, ref = setup["cont"], setup["ref"]
    prompts, lengths = setup["prompts"], setup["lengths"]
    for i in range(5):
        cont.submit(prompts[i], lengths[i])
    done = _by_prompt(cont.run_until(5, max_macro_steps=60))
    for i in range(5):
        c = done[tuple(prompts[i][: lengths[i]].tolist())]
        n = int(ref.response_len[i])
        np.testing.assert_array_equal(
            c.response_tokens, ref.response_tokens[i, :n]
        )
        np.testing.assert_allclose(
            c.behavior_logp, ref.behavior_logp[i, :n], atol=1e-5
        )
        np.testing.assert_allclose(c.values, ref.values[i, :n], atol=1e-5)
        assert c.generation == 0
    # every reservation came back when the lanes drained; the only pages
    # still allocated are the prefix-cache's chains (refcount 1 each)
    assert cont.allocator.reserved == 0
    assert (
        cont.allocator.allocated_pages == cont._prefix_cache.cached_pages
    )
    assert all(
        cont.allocator.refcount(n.page) == 1
        for n in cont._prefix_cache._nodes.values()
    )


def test_one_batched_transfer_per_macro_step(setup, monkeypatch):
    """The macro-step discipline, counted at the module seams: a step
    with admission = one prefill upload + one table upload + ONE batched
    read; a steady step (no admission) = one upload + ONE read — all
    under the armed ``steady_state_guard`` (the engine is warm)."""
    import scalerl_tpu.genrl.continuous as cont_mod

    cont = setup["cont"]
    assert cont._warm  # the parity round armed the guard
    puts, gets = [], []
    real_put, real_get = cont_mod._device_put, cont_mod._device_get
    monkeypatch.setattr(
        cont_mod, "_device_put", lambda x: (puts.append(1), real_put(x))[1]
    )
    monkeypatch.setattr(
        cont_mod, "_device_get", lambda x: (gets.append(1), real_get(x))[1]
    )
    prompts, lengths = setup["prompts"], setup["lengths"]
    cont.submit(prompts[0], lengths[0])
    cont.submit(prompts[1], lengths[1])
    cont.step()  # admits both (one bucket group each) + decodes
    n_prefill_puts = len(puts) - 1  # the last put is the decode table
    assert len(gets) == 1
    assert n_prefill_puts in (1, 2)  # one per (prompt-bucket) group
    while cont.live_lanes or cont.pending:
        puts.clear()
        gets.clear()
        cont.step()  # steady: no admission pending
        assert (len(puts), len(gets)) == (1, 1)


def test_zero_retraces_after_warmup(setup):
    """The decode macro-step program traced exactly ONCE across every
    round so far (fixed lane count + static paged shapes), and re-running
    warm bucket admissions adds no prefill traces either."""
    cont = setup["cont"]
    assert cont._decode_traces == 1
    prefill_programs = len(cont._prefill_fns)
    assert cont._prefill_traces == prefill_programs
    prompts, lengths = setup["prompts"], setup["lengths"]
    for i in range(5):
        cont.submit(prompts[i], lengths[i])
    cont.run_until(5, max_macro_steps=60)
    assert cont._decode_traces == 1
    assert cont._prefill_traces == len(cont._prefill_fns)


def test_fragmentation_independence_of_results(setup):
    """After admit/finish churn has fragmented the page pool, the same
    prompt still decodes to the same greedy tokens as the full-forward
    reference — results never depend on the physical page layout."""
    cont, ref = setup["cont"], setup["ref"]
    prompts, lengths = setup["prompts"], setup["lengths"]
    rng = np.random.default_rng(9)
    # churn: interleaved ragged admissions fragment the LIFO free list
    for i in range(7):
        n = int(rng.integers(1, P_MAX + 1))
        cont.submit(rng.integers(2, V, size=n).astype(np.int32), n)
    cont.run_until(7, max_macro_steps=80)
    cont.submit(prompts[0], lengths[0])
    done = cont.run_until(1, max_macro_steps=40)
    n = int(ref.response_len[0])
    np.testing.assert_array_equal(
        done[0].response_tokens, ref.response_tokens[0, :n]
    )


def test_a_pool_with_no_two_free_pages_adjacent_decodes_the_same_tokens(setup):
    """The allocator prefers adjacent pages and never needs them (ISSUE
    50): with every other page of the pool held by someone else, a lane's
    table holds no run at all, and the prompt still decodes to the
    full-forward reference's greedy tokens."""
    cont, ref = setup["cont"], setup["ref"]
    prompts, lengths = setup["prompts"], setup["lengths"]
    a = cont.allocator
    cont._prefix_cache.flush()
    assert a.allocated_pages == 0
    held = a.alloc(a.free_pages, holder="someone-else")
    a.free(held[1::2], holder="someone-else")
    before = a.stats()
    cont.submit(prompts[0], lengths[0])
    done = cont.run_until(1, max_macro_steps=40)
    n = int(ref.response_len[0])
    np.testing.assert_array_equal(done[0].response_tokens, ref.response_tokens[0, :n])
    after = a.stats()
    assert after["allocated_total"] - before["allocated_total"] >= 2
    assert after["adjacent"] == before["adjacent"]  # not one page next to the last
    cont._prefix_cache.flush()
    a.free(held[0::2], holder="someone-else")
    assert a.free_pages == a.capacity


def _run_engine(lanes, **config):
    m = TransformerPolicy(
        num_actions=V, vocab_size=V, d_model=32, num_heads=2, num_layers=1, max_len=32,
    )
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32))
    return ContinuousEngine(
        m, params,
        ContinuousConfig(
            vocab_size=V, max_prompt_len=12, max_new_tokens=12, temperature=1.0, seed=3,
            lanes=lanes, page_size=2, steps_per_macro=2, steps_in_flight=1,
            prefix_cache=False, **config,
        ),
    )


def test_group_members_tables_are_a_shared_run_then_an_own_run():
    """After a group admission the leader's prompt pages are one run, every
    member's table is those shared pages then a run of its own that starts
    at its copy of the partial page, and growth continues each lane's own
    run (ISSUE 50)."""
    eng = _run_engine(4, num_pages=1025)  # sixty-four stretches of 16 pages: room for runs
    prompt = np.arange(2, 11).astype(np.int32)  # 9 tokens: 4 full pages and a partial one
    assert eng.submit_group(prompt, 4, len(prompt))
    for _ in range(3):
        eng.step()
    tables = [np.asarray(l.pages) for l in eng._lanes]
    assert all(len(t) >= 8 for t in tables)
    leader = tables[0]
    np.testing.assert_array_equal(np.diff(leader), 1)  # prompt and growth: one run
    own_starts = set()
    for member in tables[1:]:
        np.testing.assert_array_equal(member[:4], leader[:4])  # the shared run
        assert member[4] != leader[4]  # its own copy of the partial page
        np.testing.assert_array_equal(np.diff(member[4:]), 1)  # ... starts its own run
        own_starts.add(int(member[4]))
    assert len(own_starts) == 3
    np.testing.assert_array_equal(eng._table[1, : len(tables[1])], tables[1])


def test_stats_count_adjacent_pages_and_pages_a_copy():
    """``stats()``'s ``page_adjacent_share`` and ``pages_per_copy`` against
    a table counted by hand: one lane, a prompt of 9 tokens on pages of 2.
    Admission hands out pages 1-5 and the first macro-step's horizon (two
    more tokens) page 6: six pages, five of them next to the one before.
    The table as first uploaded has five live pages, 1 to 5, which the
    kernels' rule fetches as a copy of four and a copy of one."""
    eng = _run_engine(1)
    prompt = np.arange(2, 11).astype(np.int32)
    eng.submit(prompt, len(prompt))
    eng.step()
    np.testing.assert_array_equal(eng._table[0, :6], [1, 2, 3, 4, 5, 6])
    s = eng.stats()
    assert (s["table_pages"], s["table_copies"]) == (5, 2)
    assert s["pages_per_copy"] == 2.5
    assert s["page_adjacent_share"] == 5 / 6
    assert eng.allocator.stats()["adjacent"] == 5
    # a second macro-step: the context is 11 tokens, six live pages, 4 + 1 + 1
    eng.step()
    s = eng.stats()
    assert (s["table_pages"], s["table_copies"]) == (5 + 6, 2 + 3)


def test_decode_program_traces_the_kernel_body_once(monkeypatch):
    """Set-up's guard, with no clock (ISSUE 50): the four attention layers
    of a model call the paged decode kernel on the same shapes, and tracing
    the decode program traces the kernel's body ONCE (the call runs under
    a ``jax.jit`` of its own), not once a layer."""
    from scalerl_tpu.ops import pallas_paged_attention as ppa

    calls = []
    real = ppa._decode_kernel

    def counted(*args, **kwargs):
        calls.append(1)
        return real(*args, **kwargs)

    monkeypatch.setattr(ppa, "_decode_kernel", counted)
    # a geometry no other test has: the kernel's own cache starts cold
    m = TransformerPolicy(
        num_actions=V, vocab_size=V, d_model=48, num_heads=3, num_layers=4, max_len=24,
    )
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32))
    eng = ContinuousEngine(
        m, params,
        ContinuousConfig(
            vocab_size=V, max_prompt_len=8, max_new_tokens=8, lanes=3, page_size=4,
            paged_attn="pallas",
        ),
    )
    text = eng.lower_decode().as_text()
    assert len(calls) == 1
    assert text.count("paged_decode") >= 1


def test_a_narrow_pool_walks_long_blocks_and_decodes_the_references_tokens(monkeypatch):
    """ISSUE 53: a pool of 1 KiB a token (two heads of 128 float32) is
    walked 512 tokens a block, which ``stats()`` and one zero-length span a
    traced shape say; the allocator's stretch stays the 16 pages of one
    copy; and through the kernel the engine decodes the full forward's
    greedy tokens."""
    from scalerl_tpu.ops.pallas_paged_attention import pages_per_block
    from scalerl_tpu.runtime import tracing

    monkeypatch.setenv(tracing.ENV_SAMPLE, "1.0")
    tracing.reset()
    try:
        m = TransformerPolicy(
            num_actions=V, vocab_size=V, d_model=256, num_heads=2, num_layers=2, max_len=16,
        )
        params = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32))
        rng = np.random.default_rng(53)
        prompts = rng.integers(2, V, size=(3, P_MAX)).astype(np.int32)
        lengths = np.array([6, 3, 1], np.int32)
        ref = greedy_full_forward(m, params, prompts, lengths, P_MAX, R_MAX)
        eng = ContinuousEngine(
            m, params,
            ContinuousConfig(
                vocab_size=V, max_prompt_len=P_MAX, max_new_tokens=R_MAX, temperature=0.0,
                seed=7, lanes=3, page_size=8, num_pages=129, steps_per_macro=3,
                steps_in_flight=1, paged_attn="pallas",
            ),
        )
        assert eng.stats()["block_tokens"] == pages_per_block(8, 2 * 128, 4) * 8 == 512
        assert eng.allocator.stretch == 16
        for i in range(3):
            eng.submit(prompts[i], lengths[i])
        done = _by_prompt(eng.run_until(3, max_macro_steps=20))
        for i in range(3):
            c = done[tuple(prompts[i][: lengths[i]].tolist())]
            np.testing.assert_array_equal(c.response_tokens, ref.response_tokens[i])
            np.testing.assert_allclose(c.behavior_logp, ref.behavior_logp[i], atol=1e-5)
        spans = [
            s for s in tracing.get_tracer().finished() if s["name"] == "paged_decode.tiling"
        ]
        assert len(spans) == 1, spans  # two layers, one traced shape
        attrs = spans[0]["attrs"]
        assert attrs["shape"] == [3, 1, 2, 128] and attrs["pool"] == [129, 8, 256]
        assert (attrs["dtype"], attrs["pages_per_block"], attrs["block_tokens"]) == ("float32", 64, 512)
    finally:
        monkeypatch.delenv(tracing.ENV_SAMPLE)
        tracing.reset()


def test_quantized_push_params_logits_parity(setup):
    """push_params(quantize="int8") stores the compressed snapshot and
    dequantizes on read: greedy decode tokens are unchanged and behavior
    logprobs stay within the int8 tolerance; the dequant is cached per
    generation."""
    m, params = setup["model"], setup["params"]
    prompts, lengths = setup["prompts"], setup["lengths"]
    ref = setup["ref"]
    eng = ContinuousEngine(m, params, setup["cont"].config)

    def generate():
        for i in range(5):
            eng.submit(prompts[i], lengths[i], tag=i)
        return sorted(
            eng.run_until(5, max_macro_steps=60), key=lambda c: c.tag
        )

    gen = eng.push_params(params, quantize="int8")
    assert gen == 1
    snap1, _ = eng._snapshot_params()
    snap2, _ = eng._snapshot_params()
    assert snap1 is snap2  # dequant-on-read cached until the next push
    for i, c in enumerate(generate()):
        assert c.generation == 1
        np.testing.assert_array_equal(
            c.response_tokens, ref.response_tokens[i]
        )
        np.testing.assert_allclose(
            c.behavior_logp, ref.behavior_logp[i], atol=5e-2
        )
    # bf16 mode is tighter
    eng.push_params(params, quantize="bf16")
    for i, c in enumerate(generate()):
        np.testing.assert_allclose(
            c.behavior_logp, ref.behavior_logp[i], atol=5e-2
        )
    # the serving plane exposes the same knob (non-learner replicas)
    import inspect

    from scalerl_tpu.serving.server import InferenceServer

    assert "quantize" in inspect.signature(
        InferenceServer.push_params
    ).parameters


def test_eos_latch_variable_lengths_and_page_return():
    """With an EOS id and temperature 1, lanes finish at ragged lengths;
    harvested sequences end in EOS (when short of budget), pages return
    immediately, and more sequences than lanes flow through."""
    m = _model()
    params = m.init(jax.random.PRNGKey(1), jnp.zeros((1, 2), jnp.int32))
    eng = ContinuousEngine(
        m, params,
        ContinuousConfig(
            vocab_size=V, max_prompt_len=P_MAX, max_new_tokens=R_MAX,
            temperature=1.0, eos_token=1, seed=3, lanes=3, page_size=2,
            steps_per_macro=2,
        ),
    )
    rng = np.random.default_rng(5)
    for _ in range(8):
        n = int(rng.integers(1, P_MAX + 1))
        eng.submit(rng.integers(2, V, size=n).astype(np.int32), n)
    done = eng.run_until(8, max_macro_steps=200)
    assert len(done) == 8
    for c in done:
        r = len(c.response_tokens)
        assert 1 <= r <= R_MAX
        assert len(c.behavior_logp) == r and len(c.values) == r
        if r < R_MAX:
            assert c.response_tokens[-1] == 1  # latched on sampling EOS
        assert c.finish_time >= c.admit_time >= c.submit_time
    # reservations fully returned; only cache-held chains stay allocated
    assert eng.allocator.reserved == 0
    assert (
        eng.allocator.allocated_pages == eng._prefix_cache.cached_pages
    )
    assert eng.completed_total == 8
    assert 0.0 < eng.mean_occupancy <= 1.0


def test_page_exhaustion_backpressure_and_shedding():
    """A pool that fits ONE worst-case sequence serializes admission
    (backpressure through the queue, lanes idle), the queue bound sheds,
    and everything still completes without corruption."""
    m = _model()
    params = m.init(jax.random.PRNGKey(2), jnp.zeros((1, 2), jnp.int32))
    # worst case = ceil((6 + 4) / 4) = 3 pages; capacity 3 -> 1 sequence
    eng = ContinuousEngine(
        m, params,
        ContinuousConfig(
            vocab_size=V, max_prompt_len=P_MAX, max_new_tokens=R_MAX,
            temperature=0.0, seed=0, lanes=2, page_size=4, num_pages=4,
            steps_per_macro=2, max_pending=2,
        ),
    )
    rng = np.random.default_rng(6)
    p = rng.integers(2, V, size=(3, P_MAX)).astype(np.int32)
    assert eng.submit(p[0], P_MAX)
    assert eng.submit(p[1], P_MAX)
    assert not eng.submit(p[2], P_MAX)  # queue at max_pending: shed
    assert eng._batcher.shed_total == 1
    done = eng.run_until(2, max_macro_steps=100)
    assert len(done) == 2
    # the pool never over-committed: one sequence's pages at a time, and
    # any cache-held leftovers are reclaimable (refcount 1)
    assert eng.allocator.capacity == 3
    assert eng.allocator.reserved == 0
    assert (
        eng.allocator.allocated_pages == eng._prefix_cache.cached_pages
    )


def test_pack_completions_layout_and_fields():
    c0 = CompletedSequence(
        prompt=np.array([5, 6, 7], np.int32), prompt_len=3,
        response_tokens=np.array([8, 9], np.int32),
        behavior_logp=np.array([-0.5, -0.7], np.float32),
        values=np.array([0.1, 0.2], np.float32),
        generation=2, submit_time=0.0, admit_time=1.0, finish_time=2.0,
    )
    c1 = CompletedSequence(
        prompt=np.array([4], np.int32), prompt_len=1,
        response_tokens=np.array([3, 3, 3, 3], np.int32),
        behavior_logp=np.full(4, -1.0, np.float32),
        values=np.zeros(4, np.float32),
        generation=5, submit_time=0.0, admit_time=0.0, finish_time=0.0,
    )
    packed = pack_completions([c0, c1], prompt_pad=4, response_pad=4)
    # task layout: right-padded prompts; learner layout: left-padded seqs
    np.testing.assert_array_equal(packed.prompts[0], [5, 6, 7, 0])
    np.testing.assert_array_equal(packed.sequences[0], [0, 5, 6, 7, 8, 9, 0, 0])
    np.testing.assert_array_equal(packed.mask[0], [1, 1, 0, 0])
    np.testing.assert_array_equal(packed.response_len, [2, 4])
    np.testing.assert_array_equal(packed.generations, [2, 5])
    assert packed.decode_tokens == 6
    fields, prios = packed.fields(np.array([0.5, 1.0], np.float32))
    assert set(fields) == set(sequence_field_shapes(4, 4))
    np.testing.assert_array_equal(fields["generation"], [2, 5])
    np.testing.assert_array_equal(prios, [1.0, 1.0])
    with pytest.raises(ValueError):
        packed.fields(np.zeros(3, np.float32))  # wrong reward batch


def test_pack_completions_zero_round_packs_empty():
    """A zero-completion round is a legitimate continuous/disagg outcome
    (every lane mid-decode): the pack is empty but shape-correct, and
    fields() still produces the replay schema at B=0."""
    packed = pack_completions([], prompt_pad=4, response_pad=4)
    assert packed.sequences.shape == (0, 8)
    assert packed.prompts.shape == (0, 4)
    assert packed.decode_tokens == 0
    fields, prios = packed.fields(np.zeros(0, np.float32))
    assert set(fields) == set(sequence_field_shapes(4, 4))
    assert all(v.shape[0] == 0 for v in fields.values())
    assert prios.shape == (0,)
    # the packed-learner layout (ISSUE 15) handles the same edge: zero
    # completions pack to zero ROWS with intact trailing geometry
    from scalerl_tpu.genrl.rollout import (
        packed_field_shapes,
        packed_rows_from_completions,
    )

    pk = packed_rows_from_completions(
        packed, np.zeros(0, np.float32), pack_len=8
    )
    assert pk.rows == 0 and pk.tokens.shape == (0, 8)
    pfields, pprios = pk.fields()
    assert set(pfields) == set(packed_field_shapes(8))
    assert all(v.shape[0] == 0 for v in pfields.values())
    assert pprios.shape == (0,)


def _completion(prompt_len, resp_len, generation, token=3):
    return CompletedSequence(
        prompt=np.full(prompt_len, token, np.int32), prompt_len=prompt_len,
        response_tokens=np.full(resp_len, token, np.int32),
        behavior_logp=np.full(resp_len, -1.0, np.float32),
        values=np.zeros(resp_len, np.float32),
        generation=generation, submit_time=0.0, admit_time=0.0,
        finish_time=0.0,
    )


def test_pack_completions_backlog_straddles_three_generations():
    """A backlog batch whose members were admitted under three different
    param generations keeps the per-sequence tags — the learner's
    importance ratios see each sequence's true behavior generation."""
    batch = [_completion(2, 2, g) for g in (3, 4, 5)]
    packed = pack_completions(batch, prompt_pad=4, response_pad=4)
    np.testing.assert_array_equal(packed.generations, [3, 4, 5])
    fields, _ = packed.fields(np.zeros(3, np.float32))
    np.testing.assert_array_equal(fields["generation"], [3, 4, 5])


def test_pack_completions_oversize_sheds_with_counter():
    """An oversize completion (prompt or response past the bucket pair —
    a foreign host shipping against a different ladder) is shed with a
    counter, never a crash; survivors pack normally."""
    from scalerl_tpu.runtime import telemetry

    before = telemetry.get_registry().counter("genrl.oversize_shed").value
    batch = [
        _completion(2, 2, 1),
        _completion(6, 2, 1),   # prompt overflows prompt_pad=4
        _completion(2, 9, 1),   # response overflows response_pad=4
    ]
    packed = pack_completions(batch, prompt_pad=4, response_pad=4)
    assert packed.sequences.shape[0] == 1
    np.testing.assert_array_equal(packed.generations, [1])
    after = telemetry.get_registry().counter("genrl.oversize_shed").value
    assert after - before == 2
    # the survivor re-packs into the learner-row layout cleanly too: the
    # shed already happened upstream, so no pack_oversize_shed fires
    from scalerl_tpu.genrl.rollout import packed_rows_from_completions

    pk = packed_rows_from_completions(
        packed, np.zeros(1, np.float32), pack_len=8
    )
    assert pk.rows == 1 and pk.sequences_shed == 0
    assert pk.decode_tokens == 2
    # an all-oversize batch degrades to the empty pack, still no crash
    packed = pack_completions([_completion(6, 9, 1)], 4, 4)
    assert packed.sequences.shape[0] == 0


def test_submit_tag_rides_to_completion():
    """submit(tag=...) comes back on the CompletedSequence — the disagg
    shell's lease routing — even when lanes complete out of order."""
    m = _model()
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32))
    eng = ContinuousEngine(
        m, params,
        ContinuousConfig(
            vocab_size=V, max_prompt_len=P_MAX, max_new_tokens=R_MAX,
            temperature=1.0, eos_token=1, seed=7, lanes=2,
            page_size=2, steps_per_macro=2,
        ),
    )
    rng = np.random.default_rng(11)
    tags = [f"lease-{i}" for i in range(5)]
    prompts = {}
    for t in tags:
        n = int(rng.integers(1, P_MAX + 1))
        p = rng.integers(2, V, size=n).astype(np.int32)
        prompts[t] = p
        eng.submit(p, n, tag=t)
    done = eng.run_until(5, max_macro_steps=200)
    assert sorted(c.tag for c in done) == sorted(tags)
    for c in done:
        np.testing.assert_array_equal(c.prompt, prompts[c.tag])


def test_trainer_rides_continuous_engine():
    """The trainer loop over the engine: rounds train, insert batches stay shape-stable via the
    completion backlog, and staleness/decode metrics flow."""
    args = GenRLArguments(
        seed=3, vocab_size=8, prompt_len=4, max_new_tokens=4,
        d_model=32, n_layers=1, n_heads=2,
        genrl_batch=8, genrl_sample_batch=8, genrl_buffer_sequences=16,
        telemetry_interval_s=0.0, logger_backend="none",
        genrl_engine="continuous", genrl_lanes=4, genrl_page_size=4,
        genrl_macro_steps=2,
    )
    trainer = SequenceRLTrainer(args)
    m1 = trainer.train_round()
    m2 = trainer.train_round()
    assert np.isfinite(m1["total_loss"]) and np.isfinite(m2["total_loss"])
    assert m2["decode_tokens"] > 0
    assert m2["staleness"] >= 0.0
    assert trainer.engine._decode_traces == 1  # one macro program, ever


# ---------------------------------------------------------------------------
# shared-prefix KV reuse + CoW group sampling + pipelining (ISSUE 14)


def test_submit_group_cow_parity_and_prefill_savings(setup):
    """The acceptance pin for group sampling: submit_group(prompt, 8) at
    temperature 0 produces 8 completions TOKEN-IDENTICAL to the
    full-forward reference — 7 of them riding the leader's prompt pages
    copy-on-write — and the prefill-savings ratio hits the bench
    acceptance bar ((n-1)/n of full-page prefix tokens >= 0.8)."""
    m, params = setup["model"], setup["params"]
    ref = setup["ref"]
    prompts, lengths = setup["prompts"], setup["lengths"]
    eng = ContinuousEngine(
        m, params,
        ContinuousConfig(
            vocab_size=V, max_prompt_len=P_MAX, max_new_tokens=R_MAX,
            temperature=0.0, seed=7, lanes=8, page_size=4,
            steps_per_macro=3,
        ),
    )
    shared_before = (
        telemetry.get_registry().counter("genrl.pages_shared").value
    )
    assert eng.submit_group(prompts[0], 8, lengths[0], tag="grp")
    done = eng.run_until(8, max_macro_steps=80)
    n = int(ref.response_len[0])
    for c in done:
        assert c.tag == "grp"
        np.testing.assert_array_equal(
            c.response_tokens, ref.response_tokens[0, :n]
        )
        np.testing.assert_allclose(
            c.behavior_logp, ref.behavior_logp[0, :n], atol=1e-5
        )
    # prompt len 6 @ page_size 4 -> 4 full-page tokens per lane; the
    # leader prefilled them, the 7 members shared them CoW
    assert eng.prefix_tokens_total == 8 * 4
    assert eng.prefix_tokens_saved == 7 * 4
    assert eng.prefix_saved_ratio >= 0.8
    assert eng._fork_traces == 1  # one jitted fork program, one dispatch
    after = telemetry.get_registry().counter("genrl.pages_shared").value
    assert after - shared_before >= 7
    assert eng.allocator.reserved == 0


def test_prefix_cache_hit_skips_prefill_token_identical(setup):
    """Single-prompt submits take the same cache-lookup path: the second
    admission of a prompt shares its cached full-page prefix (saved
    tokens grow, prefilled tokens shrink) and decodes to IDENTICAL
    tokens/logps through the shared-table tail-prefill program."""
    m, params = setup["model"], setup["params"]
    ref = setup["ref"]
    prompts, lengths = setup["prompts"], setup["lengths"]
    eng = ContinuousEngine(
        m, params,
        ContinuousConfig(
            vocab_size=V, max_prompt_len=P_MAX, max_new_tokens=R_MAX,
            temperature=0.0, seed=7, lanes=2, page_size=2,
            steps_per_macro=3, steps_in_flight=1,
        ),
    )
    eng.submit(prompts[0], lengths[0])
    first = eng.run_until(1, max_macro_steps=40)[0]
    assert eng.prefix_tokens_saved == 0
    prefilled_cold = eng.prefill_tokens
    assert prefilled_cold == int(lengths[0])
    eng.submit(prompts[0], lengths[0])
    second = eng.run_until(1, max_macro_steps=40)[0]
    # lookup caps at prompt_len - 1 = 5 tokens -> 2 full pages = 4 tokens
    assert eng.prefix_tokens_saved == 4
    assert eng.prefill_tokens == prefilled_cold + int(lengths[0]) - 4
    assert eng._prefix_cache.hits >= 1
    n = int(ref.response_len[0])
    for c in (first, second):
        np.testing.assert_array_equal(
            c.response_tokens, ref.response_tokens[0, :n]
        )
        np.testing.assert_allclose(
            c.behavior_logp, ref.behavior_logp[0, :n], atol=1e-5
        )
        np.testing.assert_allclose(c.values, ref.values[0, :n], atol=1e-5)


def test_pipelined_steps_in_flight_parity_and_lagged_reads(setup, monkeypatch):
    """K=3 macro-steps in flight: reads lag dispatch by K-1 (the first
    K-1 steps dispatch without reading), steady steps still do exactly
    ONE upload + ONE batched read under the armed guard, and the
    completions stay token-identical to the full-forward reference."""
    import scalerl_tpu.genrl.continuous as cont_mod

    m, params = setup["model"], setup["params"]
    ref = setup["ref"]
    prompts, lengths = setup["prompts"], setup["lengths"]
    eng = ContinuousEngine(
        m, params,
        ContinuousConfig(
            vocab_size=V, max_prompt_len=P_MAX, max_new_tokens=R_MAX,
            temperature=0.0, seed=7, lanes=4, page_size=4,
            steps_per_macro=1, steps_in_flight=3,
        ),
    )
    # warm: compile decode + prefill off the counting clock, then drain
    # the warmup's leftover in-flight macros so the counted window starts
    # from an empty pipeline
    eng.submit(prompts[4], lengths[4])
    eng.run_until(1, max_macro_steps=40)
    while eng._inflight:
        eng.step()
    puts, gets = [], []
    real_put, real_get = cont_mod._device_put, cont_mod._device_get
    monkeypatch.setattr(
        cont_mod, "_device_put", lambda x: (puts.append(1), real_put(x))[1]
    )
    monkeypatch.setattr(
        cont_mod, "_device_get", lambda x: (gets.append(1), real_get(x))[1]
    )
    for i in range(4):
        eng.submit(prompts[i], lengths[i])
    done = []
    # warmup never leaves more than K-1 macros in flight
    assert len(eng._inflight) <= 2
    steps = 0
    lagged = 0
    steady = 0
    while len(done) < 4 and steps < 100:
        depth_before = len(eng._inflight)
        was_steady = (
            depth_before == 2 and eng.pending == 0 and eng.live_lanes > 0
        )
        puts.clear()
        gets.clear()
        got = eng.step()
        done.extend(got)
        steps += 1
        if not gets and eng.live_lanes:
            lagged += 1  # a dispatch whose read is still in flight
        if was_steady:
            # pipeline full, no admission: exactly ONE upload (the
            # table) + ONE batched read per macro-step, K-1 behind
            assert (len(puts), len(gets)) == (1, 1)
            steady += 1
    assert lagged >= 1  # reads genuinely lag dispatch
    assert steady >= 1  # the (1, 1) steady state was actually exercised
    by_prompt = _by_prompt(done)
    for i in range(4):
        c = by_prompt[tuple(prompts[i][: lengths[i]].tolist())]
        n = int(ref.response_len[i])
        np.testing.assert_array_equal(
            c.response_tokens, ref.response_tokens[i, :n]
        )
        np.testing.assert_allclose(
            c.behavior_logp, ref.behavior_logp[i, :n], atol=1e-5
        )


def test_push_params_flushes_prefix_cache(setup):
    """A param push invalidates the whole prefix index (cached K/V
    belongs to the old generation); re-admission recomputes and stays
    token-identical when the pushed params are unchanged."""
    m, params = setup["model"], setup["params"]
    ref = setup["ref"]
    prompts, lengths = setup["prompts"], setup["lengths"]
    eng = ContinuousEngine(
        m, params,
        ContinuousConfig(
            vocab_size=V, max_prompt_len=P_MAX, max_new_tokens=R_MAX,
            temperature=0.0, seed=7, lanes=2, page_size=2,
            steps_per_macro=3, steps_in_flight=1,
        ),
    )
    eng.submit(prompts[0], lengths[0])
    eng.run_until(1, max_macro_steps=40)
    assert eng._prefix_cache.cached_pages > 0
    gen = eng.push_params(params)
    assert eng._prefix_cache.cached_pages == 0
    assert eng.allocator.allocated_pages == 0  # cache refs released
    saved_before = eng.prefix_tokens_saved
    eng.submit(prompts[0], lengths[0])
    c = eng.run_until(1, max_macro_steps=40)[0]
    assert eng.prefix_tokens_saved == saved_before  # recomputed, no hit
    assert c.generation == gen
    n = int(ref.response_len[0])
    np.testing.assert_array_equal(
        c.response_tokens, ref.response_tokens[0, :n]
    )


@pytest.mark.slow  # ~12 s churn soak; aliasing/identity mechanics stay tier-1-covered by
# the paging churn invariant + group-submit identity tests (ISSUE 19 buy-back)
def test_churn_grouped_admits_evictions_no_aliasing_token_identity():
    """Satellite: 300 churn steps mixing grouped admits, prefix hits,
    mid-group EOS, param-push flushes, and LRU evictions over a tight
    pool — the NO-ALIASING invariant (a page mapped by two live lanes is
    a shared full-page prompt prefix whose token span AGREES between the
    lanes, and the allocator's live/free sets always partition the pool)
    checked at every step, and temperature-0 token-identity vs the
    CACHE-OFF engine asserted for every completion after every phase."""
    m = _model()
    # init/pool seeds chosen so several pool prompts greedy-decode into an
    # early EOS (mid-group EOS is part of the churn mix, not an accident)
    params = m.init(jax.random.PRNGKey(7), jnp.zeros((1, 2), jnp.int32))
    base = dict(
        vocab_size=V, max_prompt_len=P_MAX, max_new_tokens=R_MAX,
        temperature=0.0, eos_token=1, seed=5, page_size=2,
        steps_per_macro=2,
    )
    lanes = 6
    worst = -(-(P_MAX + R_MAX) // 2)  # pages per worst-case sequence
    eng = ContinuousEngine(
        m, params,
        ContinuousConfig(
            lanes=lanes, num_pages=lanes * worst + 1, **base
        ),
    )
    twin = ContinuousEngine(  # the cache-off oracle
        m, params,
        ContinuousConfig(
            lanes=2, prefix_cache=False, steps_in_flight=1, **base
        ),
    )
    rng = np.random.default_rng(15)
    pool = []
    for _ in range(6):
        n = int(rng.integers(2, P_MAX + 1))
        pool.append(rng.integers(2, V, size=n).astype(np.int32))
    expected = {}

    def oracle(prompt):
        key = tuple(prompt.tolist())
        if key not in expected:
            twin.submit(prompt, len(prompt))
            expected[key] = twin.run_until(1, max_macro_steps=60)[0]
        return expected[key]

    def check_no_aliasing():
        a = eng.allocator
        assert not set(a._refs) & set(a._free)
        assert len(a._refs) + a.free_pages == a.capacity
        live = [
            (l.pages, l.prompt, l.prompt_len)
            for l in eng._lanes
            if l.busy
        ]
        for i in range(len(live)):
            for j in range(i + 1, len(live)):
                pi, pri, ni = live[i]
                pj, prj, nj = live[j]
                for p in set(pi) & set(pj):
                    assert a.refcount(p) >= 2
                    ki, kj = pi.index(p), pj.index(p)
                    assert ki == kj  # same chain depth
                    span_i = pri[ki * 2 : (ki + 1) * 2]
                    span_j = prj[kj * 2 : (kj + 1) * 2]
                    np.testing.assert_array_equal(span_i, span_j)
                    # shared pages are FULL prompt pages: never in either
                    # lane's writable region
                    assert (ki + 1) * 2 <= ni and (kj + 1) * 2 <= nj

    completions = []
    short = 0
    for phase in range(10):
        for _ in range(30):
            if eng.pending < 4:
                prompt = pool[int(rng.integers(len(pool)))]
                n = int(rng.integers(1, 4))
                eng.submit_group(prompt, n, len(prompt))
            completions.extend(eng.step())
            check_no_aliasing()
        # identity vs the cache-off oracle after every churn phase
        for c in completions:
            e = oracle(np.asarray(c.prompt))
            np.testing.assert_array_equal(
                c.response_tokens, e.response_tokens
            )
            np.testing.assert_allclose(
                c.behavior_logp, e.behavior_logp, atol=1e-5
            )
            np.testing.assert_allclose(c.values, e.values, atol=1e-5)
            if len(c.response_tokens) < R_MAX:
                short += 1
        completions = []
        if phase == 4:
            # same-weights push: flushes the cache mid-churn without
            # changing the greedy trajectory — post-flush re-admits must
            # recompute to the same tokens
            eng.push_params(params)
            assert eng._prefix_cache.cached_pages == 0
    assert eng._decode_traces == 1  # zero retraces across all churn
    assert eng._prefix_cache.hits > 0  # prefix hits genuinely occurred
    assert short > 0  # some sequences latched EOS short of the budget
    stats = eng._prefix_cache.stats()
    assert stats["evictions"] > 0  # flush/LRU reclaim genuinely fired


def test_trainer_group_sampling():
    """samples_per_prompt on the trainer: the engine admits via
    submit_group (prefill savings accrue) and the round trains."""
    base = dict(
        seed=3, vocab_size=8, prompt_len=4, max_new_tokens=4,
        d_model=32, n_layers=1, n_heads=2,
        genrl_batch=8, genrl_sample_batch=8, genrl_buffer_sequences=16,
        telemetry_interval_s=0.0, logger_backend="none",
        samples_per_prompt=4,
    )
    args = GenRLArguments(
        genrl_engine="continuous", genrl_lanes=8, genrl_page_size=2,
        genrl_macro_steps=2, **base,
    )
    trainer = SequenceRLTrainer(args)
    metrics = trainer.train_round()
    assert np.isfinite(metrics["total_loss"])
    # 2 groups of 4: each group's 3 followers shared the leader's full
    # prompt pages
    assert trainer.engine.prefix_tokens_saved > 0
    assert trainer.engine.prefix_saved_ratio >= 0.5


def test_continuous_config_and_args_validation():
    base = dict(vocab_size=8, max_prompt_len=4, max_new_tokens=4)
    with pytest.raises(ValueError):
        ContinuousConfig(lanes=0, **base).validate()
    with pytest.raises(ValueError):
        ContinuousConfig(page_size=0, **base).validate()
    with pytest.raises(ValueError):
        ContinuousConfig(steps_per_macro=0, **base).validate()
    with pytest.raises(ValueError):
        ContinuousConfig(min_free_lanes=0, **base).validate()
    with pytest.raises(ValueError):
        ContinuousConfig(temperature=-0.1, **base).validate()
    with pytest.raises(ValueError):
        ContinuousConfig(steps_in_flight=0, **base).validate()
    ContinuousConfig(temperature=0.0, **base).validate()  # greedy is legal
    argbase = dict(
        vocab_size=8, prompt_len=4, max_new_tokens=4,
        telemetry_interval_s=0.0, logger_backend="none",
    )
    with pytest.raises(ValueError):
        GenRLArguments(genrl_engine="paged", **argbase).validate()
    with pytest.raises(ValueError, match="cohort engine is gone"):
        GenRLArguments(genrl_engine="cohort", **argbase).validate()
    assert GenRLArguments(**argbase).genrl_engine == "continuous"
    with pytest.raises(ValueError):
        GenRLArguments(genrl_page_size=0, **argbase).validate()
    with pytest.raises(ValueError):
        GenRLArguments(genrl_macro_steps=0, **argbase).validate()
    with pytest.raises(ValueError):
        GenRLArguments(genrl_paged_attn="cuda", **argbase).validate()
    with pytest.raises(ValueError):
        GenRLArguments(samples_per_prompt=0, **argbase).validate()
    with pytest.raises(ValueError):
        # genrl_batch (default 32) must hold whole groups
        GenRLArguments(samples_per_prompt=3, **argbase).validate()
    with pytest.raises(ValueError):
        GenRLArguments(genrl_steps_in_flight=0, **argbase).validate()
    GenRLArguments(samples_per_prompt=4, **argbase).validate()
    GenRLArguments(genrl_engine="continuous", **argbase).validate()
    # submit_group rejects groups wider than the lane pool
    m = _model()
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32))
    eng = ContinuousEngine(
        m, params,
        ContinuousConfig(
            vocab_size=V, max_prompt_len=P_MAX, max_new_tokens=R_MAX,
            lanes=2, temperature=0.0,
        ),
    )
    with pytest.raises(ValueError):
        eng.submit_group(np.asarray([3, 4], np.int32), 3)
    # speculative-decode knobs (ISSUE 16)
    with pytest.raises(ValueError):
        ContinuousConfig(spec_k=-1, **base).validate()
    with pytest.raises(ValueError):
        ContinuousConfig(spec_ngram=0, **base).validate()
    ContinuousConfig(spec_k=0, **base).validate()  # 0 = compiled out
    with pytest.raises(ValueError):
        GenRLArguments(
            genrl_engine="continuous", spec_enable=True, spec_k=0, **argbase
        ).validate()
    with pytest.raises(ValueError):
        GenRLArguments(spec_ngram=0, **argbase).validate()
    GenRLArguments(
        genrl_engine="continuous", spec_enable=True, **argbase
    ).validate()


# ---------------------------------------------------------------------------
# speculative decoding (ISSUE 16): draft-and-verify vs plain decode


@pytest.fixture(scope="module")
def spec_setup():
    """One model + a plain engine and a speculating engine at the SAME
    config otherwise — module-scoped so the verify-ladder compiles land
    on the tier-1 clock once."""
    m = TransformerPolicy(
        num_actions=V, vocab_size=V, d_model=32, num_heads=2,
        num_layers=1, max_len=40,
    )
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32))
    base = dict(
        vocab_size=V, max_prompt_len=P_MAX, max_new_tokens=12,
        temperature=0.0, seed=7, lanes=4, page_size=4,
        steps_per_macro=4, prompt_buckets=(P_MAX,),
    )
    plain = ContinuousEngine(m, params, ContinuousConfig(**base))
    spec = ContinuousEngine(
        m, params, ContinuousConfig(spec_k=4, spec_ngram=2, **base)
    )
    rng = np.random.default_rng(2)
    prompts = rng.integers(2, V, size=(5, P_MAX)).astype(np.int32)
    lengths = np.array([6, 5, 3, 2, 4], np.int32)
    return dict(
        model=m, params=params, base=base, plain=plain, spec=spec,
        prompts=prompts, lengths=lengths,
    )


def _drain(eng, want, prompts, lengths):
    for i in range(want):
        eng.submit(prompts[i], lengths[i])
    return _by_prompt(eng.run_until(want, max_macro_steps=200))


def test_spec_greedy_token_identity_vs_plain(spec_setup):
    """The acceptance pin: at temperature 0, speculation changes WHAT is
    computed per pass but not what is emitted — tokens exactly equal,
    behavior logps/values to float tolerance, per prompt."""
    s = spec_setup
    a = _drain(s["plain"], 5, s["prompts"], s["lengths"])
    b = _drain(s["spec"], 5, s["prompts"], s["lengths"])
    assert set(a) == set(b)
    for key in a:
        np.testing.assert_array_equal(
            a[key].response_tokens, b[key].response_tokens
        )
        np.testing.assert_allclose(
            a[key].behavior_logp, b[key].behavior_logp, atol=1e-5
        )
        np.testing.assert_allclose(a[key].values, b[key].values, atol=1e-5)
    # speculation actually engaged (this is not a vacuous parity)
    assert s["spec"].spec_proposed_total > 0
    assert s["spec"].spec_accepted_total > 0
    st = s["spec"].stats()
    assert 0.0 <= st["spec_acceptance_rate"] <= 1.0
    assert st["spec_rollback_pages"] >= 0
    assert st["spec_k"] == 4


def test_spec_verify_ladder_never_retraces_after_warmup(spec_setup):
    """Each pow2 draft-length bucket compiles at most once, the total is
    pinned by the finite ladder, and further rounds add NO traces — the
    spec twin of the decode-macro retrace pin."""
    s = spec_setup
    eng = s["spec"]
    buckets = eng._spec_buckets
    assert buckets == (0, 1, 2, 4)
    assert 1 <= eng._verify_traces <= len(buckets)
    traces = eng._verify_traces
    warm = set(eng._spec_warm)
    _drain(eng, 3, s["prompts"], s["lengths"])
    assert eng._verify_traces == traces + len(set(eng._spec_warm) - warm)
    assert eng._verify_traces <= len(buckets)


def test_spec_one_batched_transfer_per_pass(spec_setup, monkeypatch):
    """The draft loop is host-side: a steady spec pass is ONE batched
    upload + ONE batched read, same discipline as the plain macro-step
    (graftlint's JG001 contract, counted at the module seams)."""
    import scalerl_tpu.genrl.continuous as cont_mod

    s = spec_setup
    eng = s["spec"]
    puts, gets = [], []
    real_put, real_get = cont_mod._device_put, cont_mod._device_get
    monkeypatch.setattr(
        cont_mod, "_device_put", lambda x: (puts.append(1), real_put(x))[1]
    )
    monkeypatch.setattr(
        cont_mod, "_device_get", lambda x: (gets.append(1), real_get(x))[1]
    )
    eng.submit(s["prompts"][0], s["lengths"][0])
    eng.step()  # admission pass: prefill upload(s) + the verify pair
    while eng.live_lanes or eng.pending:
        puts.clear()
        gets.clear()
        eng.step()  # steady: no admission pending
        assert (len(puts), len(gets)) == (1, 1)


def test_spec_group_submit_cow_identity(spec_setup):
    """submit_group fans one prompt into CoW lanes sharing prefix pages;
    at temperature 0 the speculating engine's group responses match the
    plain engine's exactly (as multisets per prompt — lane order is a
    scheduling detail)."""
    s = spec_setup

    def group_run(eng):
        for i in range(2):
            eng.submit_group(
                s["prompts"][i][: s["lengths"][i]], 2, tag=i
            )
        done = eng.run_until(4, max_macro_steps=200)
        out = {}
        for c in done:
            out.setdefault(c.tag, []).append(
                c.response_tokens.tobytes()
            )
        return {t: sorted(v) for t, v in out.items()}

    assert group_run(s["plain"]) == group_run(s["spec"])


def test_spec_telemetry_counters_registered(spec_setup):
    """The spec counters ride the shared registry under the genrl prefix
    and the acceptance-rate gauge tracks the engine property."""
    s = spec_setup
    eng = s["spec"]
    reg = telemetry.get_registry()
    assert reg.counter("genrl.spec_proposed").value >= (
        eng.spec_proposed_total
    )
    assert reg.counter("genrl.spec_accepted").value >= (
        eng.spec_accepted_total
    )
    assert reg.counter("genrl.spec_rollback_pages").value >= 0
    assert reg.gauge("genrl.spec_acceptance_rate").value == pytest.approx(
        eng.spec_acceptance_rate
    )
