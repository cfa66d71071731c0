"""Every block family's traced programs and seeded weights, held to the
commit that last meant to change them.

One tiny model a family, built once (``tests/tiny_families.py``); a case is
``family.program``:

- ``forward`` / ``packed`` / ``prefill`` / ``decode`` / ``tail_prefill``:
  sha256 (first 16 hex) of ``str(jax.make_jaxpr(...))`` of the model
  called in that form.  A jaxpr's text has no source location in it, and a
  ``pallas_call``'s holds the kernel's body, so a change to
  ``models/transformer.py``, ``models/routed_ffn.py`` or a kernel that
  adds, drops or reorders one operation of a program changes its digest.
- ``values``: of the bytes of the parameters seeded from key 0 and of a
  forward's outputs: a weight that moved in the tree, or took another
  key, changes it.
- ``paged_decode.*``: the paged decode kernel alone, at as many key/value
  heads as query heads and at grouped heads.

The digests were taken on the parent of the PR that added each family's
successor (gpt2 and olmoe ``forward`` / ``decode`` / ``tail_prefill`` at
fed845a; gpt2, olmoe ``packed`` / ``values`` and longcat at e6c85c7; joyai
and ``paged_decode.kernel`` at 687c51e; nemotron and
``paged_decode.grouped_kernel`` at 5a380d0; qwen3next at 2f98510; zaya on
the tree of the PR that added it, ISSUE 46: the next PR's parent) with this
environment's JAX, and have passed unchanged on every commit since; both
``paged_decode.*`` again on the tree of ISSUE 50, which meant to change the
kernel (a run of adjacent pages in one copy, the call under its own
``jax.jit``) and changed no family's program, and once more on the tree of
ISSUE 53, which sized a block of the walk by its bytes (these tiny pools'
rows are 64 and 128 bytes: their block went from 128 tokens to 512) and
changed no family's program either; xing4 on the tree of the PR
that added it, ISSUE 51, which changed no other family's.  After
a JAX upgrade, take them again from a commit known to be unchanged.
"""

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scalerl_tpu.ops.pallas_paged_attention import paged_decode_attention
from tests.tiny_families import MODELS, V

PARENT = {
    "gpt2.forward": "9293ebb1f7ffd327",
    "gpt2.decode": "6684c4a6ae24edad",
    "gpt2.tail_prefill": "6619e9ad7001af24",
    "gpt2.packed": "90fb8eec895a66a7",
    "gpt2.values": "79b839c5b749355e",
    "olmoe.forward": "82344cf57c7911f1",
    "olmoe.decode": "7659d85004829743",
    "olmoe.tail_prefill": "664d411e9b43a686",
    "olmoe.packed": "4981fc3ee9949241",
    "olmoe.values": "c9d1ee354f83f5d6",
    "longcat.forward": "d2571fb6571faadd",
    "longcat.packed": "bb5c5dc838adec33",
    "longcat.decode": "eb657760ceb277ba",
    "longcat.tail_prefill": "75daf35c5fe7a530",
    "longcat.values": "de33b4ae4e2f31bb",
    "joyai.packed": "63d1856f31853497",
    "joyai.decode": "20200891d4e5526b",
    "joyai.values": "249dd1bdf2771f16",
    "nemotron.packed": "d1b68cc203615fd5",
    "nemotron.prefill": "4395f923a31815fd",
    "nemotron.decode": "67aa4a3e99004e1b",
    "nemotron.values": "228403cc1d471ca7",
    "qwen3next.packed": "9d1c1d7b695118ad",
    "qwen3next.prefill": "ba95ed35f45d7a32",
    "qwen3next.decode": "acc15eb4bd6b44ab",
    "qwen3next.values": "1807276d17a77dd9",
    "zaya.packed": "3998a795aa8babb3",
    "zaya.prefill": "089f5d1a6a55e63b",
    "zaya.decode": "ac11e3213a475442",
    "zaya.values": "c3b4e6916bc10194",
    "xing4.packed": "dcff4dd0d4e197f3",
    "xing4.decode": "a28da17b6ab9895d",
    "xing4.tail_prefill": "9147d7beb59a21d4",
    "xing4.values": "c8159e7ccb0cb400",
    "paged_decode.kernel": "de191285954da127",
    "paged_decode.grouped_kernel": "fd3482696fe7ed0d",
}
# [q heads, kv heads x head size] of the kernel-alone cases
_KERNEL = {"kernel": (4, 32), "grouped_kernel": (8, 16)}


def _sha(data):
    return hashlib.sha256(data).hexdigest()[:16]


@functools.lru_cache(maxsize=None)
def _shapes(family):
    """The family's parameters and cache as shapes (nothing is computed)."""
    model = MODELS[family]
    tokens = jnp.zeros((2, 24), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), tokens))
    return params, jax.eval_shape(lambda: model.init_paged_cache(9, 4, lanes=3))


def digest(name):
    family, program = name.split(".")
    if family == "paged_decode":
        sd = jax.ShapeDtypeStruct
        heads, width = _KERNEL[program]
        jaxpr = jax.make_jaxpr(
            lambda q, k, v, t, l: paged_decode_attention(q, k, v, t, l, interpret=True)
        )(
            sd((3, 1, heads, 8), jnp.float32), sd((12, 4, width), jnp.float32),
            sd((12, 4, width), jnp.float32), sd((3, 3), jnp.int32), sd((3,), jnp.int32),
        )
        return _sha(str(jaxpr).encode())
    model = MODELS[family]
    mtp = bool(model.mtp_layers)
    tokens = jnp.zeros((2, 24), jnp.int32)
    if program == "values":
        real = model.init(jax.random.PRNGKey(0), tokens)
        out = model.apply(real, jnp.arange(48).reshape(2, 24) % V, mtp=mtp)
        return _sha(b"".join(np.asarray(x).tobytes() for x in jax.tree_util.tree_leaves((real, out))))
    params, pools = _shapes(family)
    if program == "forward":
        jaxpr = jax.make_jaxpr(lambda p, t: model.apply(p, t))(params, tokens)
    elif program == "packed":
        jaxpr = jax.make_jaxpr(
            lambda p, t, s: model.apply(p, t, positions=t, segment_ids=s, mtp=mtp)
        )(params, tokens, jnp.ones((2, 24), jnp.int32))
    elif program == "prefill":
        z = jnp.zeros((2, 8), jnp.int32)
        jaxpr = jax.make_jaxpr(
            lambda p, c, t, m, ids, lanes: model.apply(
                p, t, positions=t, attn_mask=m, paged_cache=c, page_ids=ids, page_offsets=ids,
                state_lanes=lanes,
            )
        )(params, pools, z, jnp.ones((2, 8, 8), bool), z, jnp.zeros((2,), jnp.int32))
    else:
        z = jnp.zeros((3, 1 if program == "decode" else 4), jnp.int32)
        key, value = (
            ("attn_lengths", jnp.ones((3,), jnp.int32)) if program == "decode"
            else ("prefix_starts", jnp.zeros((3,), jnp.int32))
        )
        jaxpr = jax.make_jaxpr(
            lambda p, c, t, pos, ids, offs, tab, x: model.apply(
                p, t, positions=pos, paged_cache=c, page_ids=ids, page_offsets=offs,
                page_table=tab, **{key: x},
            )
        )(params, pools, z, z, z, z, jnp.zeros((3, 6), jnp.int32), value)
    return _sha(str(jaxpr).encode())


@pytest.mark.parametrize("name", sorted(PARENT))
def test_a_familys_programs_and_weights_are_the_parents(name):
    """Operation for operation the pinned commit's traced program, bit for
    bit its seeded tree and outputs: what a later family or a clean-up
    added to ``BlockSpec``, the call, the cache and the routed FFN is
    invisible to the families that were there."""
    assert digest(name) == PARENT[name]


if __name__ == "__main__":  # print the table, to take digests on a known commit
    for name in sorted(PARENT):
        print(f'    "{name}": "{digest(name)}",')
