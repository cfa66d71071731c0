"""Ring attention + sequence-parallel transformer tests (8-device CPU mesh)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from scalerl_tpu.models.transformer import TransformerPolicy
from scalerl_tpu.ops.ring_attention import (
    full_attention,
    make_ring_attention_fn,
    ring_attention,
)
from scalerl_tpu.parallel import make_mesh
from scalerl_tpu.parallel.sequence import make_sequence_parallel_apply

B, T, H, D = 2, 32, 2, 8  # T divides the 8-way sp axis


@pytest.fixture(scope="module")
def sp_mesh():
    return make_mesh("sp=8")


def _qkv(seed=0):
    key = jax.random.PRNGKey(seed)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, T, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, T, H, D), jnp.float32)
    v = jax.random.normal(kv, (B, T, H, D), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_full_attention(sp_mesh, causal):
    q, k, v = _qkv()
    want = full_attention(q, k, v, causal=causal)
    got = make_ring_attention_fn(sp_mesh, causal=causal)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_ring_attention_gradients_match(sp_mesh):
    q, k, v = _qkv(seed=1)
    ring_fn = make_ring_attention_fn(sp_mesh, causal=True)

    def loss_ring(q, k, v):
        return (ring_fn(q, k, v) ** 2).sum()

    def loss_full(q, k, v):
        return (full_attention(q, k, v, causal=True) ** 2).sum()

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    for gr, gf in zip(g_ring, g_full):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gf),
                                   rtol=5e-5, atol=5e-5)


def test_ring_attention_jit_under_shard_map(sp_mesh):
    q, k, v = _qkv(seed=2)
    fn = jax.jit(make_ring_attention_fn(sp_mesh, causal=True))
    out = fn(q, k, v)
    assert out.shape == (B, T, H, D)
    assert bool(jnp.isfinite(out).all())


def test_ring_attention_bfloat16(sp_mesh):
    q, k, v = _qkv(seed=4)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    got = make_ring_attention_fn(sp_mesh, causal=True)(qb, kb, vb)
    assert got.dtype == jnp.bfloat16
    want = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), rtol=0.06, atol=0.06
    )


def test_transformer_rejects_overlong_sequence():
    model = TransformerPolicy(num_actions=3, d_model=16, num_heads=2,
                              num_layers=1, max_len=8)
    obs = jnp.ones((1, 16, 4))
    with pytest.raises(ValueError, match="exceeds max_len"):
        model.init(jax.random.PRNGKey(0), obs)


def test_ring_handles_uneven_value_scale(sp_mesh):
    # large score magnitudes exercise the online-softmax max tracking
    q, k, v = _qkv(seed=3)
    got = make_ring_attention_fn(sp_mesh, causal=False)(q * 30, k * 30, v)
    want = full_attention(q * 30, k * 30, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# transformer policy


def test_transformer_policy_shapes():
    model = TransformerPolicy(num_actions=5, d_model=32, num_heads=2,
                              num_layers=2, max_len=64)
    obs = jnp.ones((3, 16, 7))
    params = model.init(jax.random.PRNGKey(0), obs)
    out = jax.jit(model.apply)(params, obs)
    assert out.policy_logits.shape == (3, 16, 5)
    assert out.baseline.shape == (3, 16)


def test_transformer_is_causal():
    # future-obs perturbation must not change past logits
    model = TransformerPolicy(num_actions=3, d_model=32, num_heads=2,
                              num_layers=1, max_len=64)
    obs = jnp.ones((1, 8, 4))
    params = model.init(jax.random.PRNGKey(0), obs)
    base = model.apply(params, obs).policy_logits
    perturbed = obs.at[0, 6].set(100.0)
    out = model.apply(params, perturbed).policy_logits
    np.testing.assert_allclose(base[0, :6], out[0, :6], atol=1e-5)
    assert not np.allclose(base[0, 6:], out[0, 6:])


def test_sequence_parallel_transformer_matches_single_device(sp_mesh):
    model = TransformerPolicy(num_actions=4, d_model=32, num_heads=2,
                              num_layers=2, max_len=T)
    obs = jax.random.normal(jax.random.PRNGKey(7), (B, T, 6))
    params = model.init(jax.random.PRNGKey(0), obs)
    want = model.apply(params, obs)
    sp_apply = jax.jit(make_sequence_parallel_apply(model, sp_mesh))
    got = sp_apply(params, obs)
    np.testing.assert_allclose(np.asarray(got.policy_logits),
                               np.asarray(want.policy_logits),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(got.baseline),
                               np.asarray(want.baseline),
                               rtol=3e-5, atol=3e-5)


def test_sequence_parallel_gradients_flow(sp_mesh):
    model = TransformerPolicy(num_actions=4, d_model=32, num_heads=2,
                              num_layers=1, max_len=T)
    obs = jax.random.normal(jax.random.PRNGKey(8), (B, T, 6))
    params = model.init(jax.random.PRNGKey(0), obs)
    sp_apply = make_sequence_parallel_apply(model, sp_mesh)

    def loss(params):
        out = sp_apply(params, obs)
        return (out.baseline ** 2).mean()

    grads = jax.jit(jax.grad(loss))(params)
    norms = [float(jnp.abs(g).sum()) for g in jax.tree_util.tree_leaves(grads)]
    assert all(np.isfinite(norms))
    assert sum(norms) > 0
