"""The hot programs' phases that no module names carry a ``jax.named_scope``
(ISSUE 54), and a scope is metadata alone.

The decode macro-step, the token learner's learn step and the fused classic
loop's iteration are compiled at test size on the CPU, once as they are and
once with ``jax.named_scope`` patched to do nothing: every new scope and every
older one is in some instruction's ``op_name``, and the two texts hold the
same instructions.  ``benchmark/op_scopes.py`` reads these names out of a
device trace (PERF.md section 3 lists which metric reads which)."""

import collections
import contextlib
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from scalerl_tpu.genrl.continuous import ContinuousConfig, ContinuousEngine  # noqa: E402
from scalerl_tpu.models.transformer import TransformerPolicy  # noqa: E402

_OPCODE = re.compile(r" = (?:\(.*?\)|\S+) ([a-z][a-z0-9\-]*)\(")


def _decode_text():
    vocab = 64
    model = TransformerPolicy(
        num_actions=vocab, vocab_size=vocab, d_model=32, num_heads=4, num_layers=2, max_len=64
    )
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32))
    eng = ContinuousEngine(
        model, params,
        ContinuousConfig(
            vocab_size=vocab, max_prompt_len=16, max_new_tokens=16, lanes=4, page_size=8,
            num_pages=64, steps_per_macro=2,
        ),
        iter_mode="scan",
    )
    state = (eng._pools, eng._logits_st, eng._value_st, eng._cl, eng._done, eng._resp)
    table = jnp.zeros(eng._table.shape, jnp.int32)
    return eng._decode_fn.lower(
        eng._snapshot_params()[0], *state, table, jax.random.PRNGKey(0)
    ).compile().as_text()


def _learn_text():
    import tiny_token_learner as ttl

    agent = ttl.program_agent()
    return jax.jit(agent._learn_fn).lower(agent.state, ttl.packed_batch()).compile().as_text()


def _fused_text():
    from scalerl_tpu.agents.impala import ImpalaAgent, make_impala_learn_fn
    from scalerl_tpu.config import ImpalaArguments
    from scalerl_tpu.envs import make_jax_vec_env
    from scalerl_tpu.runtime.device_loop import DeviceActorLearnerLoop

    args = ImpalaArguments(
        env_id="CartPole-v1", hidden_size=32, rollout_length=3, batch_size=8,
        max_timesteps=0, logger_backend="none",
    )
    venv = make_jax_vec_env("CartPole-v1", num_envs=8)
    agent = ImpalaAgent(
        args, obs_shape=venv.observation_shape, num_actions=venv.num_actions,
        obs_dtype=venv.env.observation_dtype,
    )
    loop = DeviceActorLearnerLoop(
        agent.model, venv, make_impala_learn_fn(agent.model, agent.optimizer, args), 3,
        iters_per_call=2, iter_mode="scan",
    )
    key = jax.random.PRNGKey(0)
    carry = loop.init_carry(key)
    return jax.jit(loop._train_many_impl).lower(agent.state, carry, key).compile().as_text()


# program -> (its text, the scopes this PR opened in it, names a metric or a
# tool already read: PERF.md section 3)
PROGRAMS = {
    "decode": (
        _decode_text, ("sample", "kv_write", "attend"),
        ("qkv", "proj", "mlp_in", "mlp_out", "LayerNorm_0", "LayerNorm_1", "final_norm",
         "policy_head", "value_head", "token_embed"),
    ),
    "learn": (
        _learn_text, ("loss", "update", "guard", "attend"),
        ("qkv", "proj", "mlp_in", "mlp_out", "final_norm", "policy_head", "value_head"),
    ),
    "fused": (_fused_text, ("act", "env_step", "store", "learn"), ()),
}


def _scopes(text):
    names = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        names.update(op_name.split("/"))
    return names


@pytest.fixture(scope="module", params=sorted(PROGRAMS))
def texts(request):
    build, new, old = PROGRAMS[request.param]
    jax.clear_caches()
    named = build()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        jax.clear_caches()
        bare = build()
    jax.clear_caches()
    return request.param, named, bare, new, old


def test_every_scope_is_in_an_op_name(texts):
    program, named, bare, new, old = texts
    have = _scopes(named)
    assert set(new) <= have, (program, sorted(set(new) - have))
    assert set(old) <= have, (program, sorted(set(old) - have))
    # and with the scopes patched out none of the new names is left
    assert not set(new) & _scopes(bare), program


def test_a_scope_changes_no_instruction(texts):
    program, named, bare, _new, _old = texts
    count = lambda text: collections.Counter(_OPCODE.findall(text))  # noqa: E731
    assert sum(count(named).values()) > 100
    assert count(named) == count(bare), program


def test_the_two_prefills_are_two_program_names():
    vocab = 64
    model = TransformerPolicy(
        num_actions=vocab, vocab_size=vocab, d_model=32, num_heads=4, num_layers=1, max_len=64
    )
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32))
    eng = ContinuousEngine(
        model, params,
        ContinuousConfig(
            vocab_size=vocab, max_prompt_len=16, max_new_tokens=16, lanes=4, page_size=8,
            num_pages=64, steps_per_macro=2,
        ),
    )
    names = {
        eng._build_prefill(8, 1).__name__, eng._build_prefix_prefill(8, 1).__name__,
        eng._build_fork(2).__name__, eng._decode_fn.__name__,
    }
    assert names == {"prefill", "prefix_prefill", "fork", "decode"}
