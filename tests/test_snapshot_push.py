"""How a parameter snapshot copy is made (ISSUE 38).

The plane makes its copy in ONE place (``param_server.snapshot_copy``): one
compiled program over a whole tree of device arrays, the leaf-by-leaf walk
for anything else.  ``ContinuousEngine`` alone writes a push's copy OVER the
snapshot it retires (the old snapshot is a donated operand of the program),
because one thread both steps and pushes it; the servers, read by other
threads while a push arrives, build the new snapshot beside the old one.
The CPU backend honours donation, so buffer pointers say here what they
say on the chip.
"""

import subprocess
import sys
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from genrl_reference import greedy_full_forward
from scalerl_tpu.genrl.continuous import ContinuousConfig, ContinuousEngine
from scalerl_tpu.models.transformer import TransformerPolicy
from scalerl_tpu.runtime import param_server
from scalerl_tpu.runtime.param_server import ParameterServer, ParamSnapshotPlane

V = 11
P_MAX, R_MAX = 6, 4

leaves_of = jax.tree_util.tree_leaves


def pointers(tree):
    return [x.unsafe_buffer_pointer() for x in leaves_of(tree)]


def scaled(tree, factor):
    """A fresh device tree: what a learn step hands the push."""
    return jax.tree_util.tree_map(lambda x: x * factor, tree)


def assert_trees_equal(a, b):
    assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b)
    for x, y in zip(leaves_of(a), leaves_of(b)):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class Plane(ParamSnapshotPlane):
    """The plane as its server users hold it: no placement of its own."""

    def __init__(self, params=None):
        self._init_param_plane(params)


@pytest.fixture(scope="module")
def setup():
    model = TransformerPolicy(
        num_actions=V, vocab_size=V, d_model=32, num_heads=2,
        num_layers=1, max_len=16,
    )
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32))
    rng = np.random.default_rng(1)
    prompts = rng.integers(2, V, size=(3, P_MAX)).astype(np.int32)
    lengths = np.array([6, 4, 2], np.int32)
    return dict(model=model, params=params, prompts=prompts, lengths=lengths)


def _engine(setup, params=None, **kw):
    config = dict(
        vocab_size=V, max_prompt_len=P_MAX, max_new_tokens=R_MAX,
        temperature=0.0, seed=7, lanes=4, page_size=4,
        steps_per_macro=R_MAX, steps_in_flight=2,
    )
    config.update(kw)
    return ContinuousEngine(
        setup["model"],
        setup["params"] if params is None else params,
        ContinuousConfig(**config),
    )


def _greedy(setup, params):
    return greedy_full_forward(
        setup["model"], params, setup["prompts"], setup["lengths"], P_MAX, R_MAX
    )


def _decode_all(setup, eng):
    for i in range(len(setup["lengths"])):
        eng.submit(setup["prompts"][i], setup["lengths"][i], tag=i)
    return sorted(
        eng.run_until(len(setup["lengths"]), max_macro_steps=20),
        key=lambda c: c.tag,
    )


def test_importing_the_plane_does_not_import_jax():
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; import scalerl_tpu.runtime.param_server as ps; "
         "import numpy as np; "
         "tree = {'w': np.ones((2, 2), np.float32), 'b': [np.zeros(2)]}; "
         "copy, programs = ps.snapshot_copy(tree); "
         "assert programs == 0 and copy['w'].shape == (2, 2); "
         "print('jax' in sys.modules)"],
        capture_output=True, text=True, timeout=120, check=True,
    )
    assert out.stdout.strip() == "False", out.stdout + out.stderr


def test_a_device_tree_is_copied_by_one_program_traced_once(setup):
    """Three pushes of a device tree: one program each, the copy traced
    once (jit's cache keys it by structure, shapes and shardings)."""
    # a shape no other test pushes, so the delta below is this test's own
    tree = {"w": jnp.ones((5, 7)), "inner": {"b": jnp.arange(13.0)}}
    program = param_server._copy_program()
    traced = program._cache_size()
    plane = Plane()
    for step in range(1, 4):
        source = scaled(tree, float(step))
        gen = plane.push_params(source, learner_step=step)
        assert gen == step
        assert plane.last_push == {
            "bytes": 4 * (35 + 13), "leaves": 2, "generation": step,
            "programs": 1, "in_place": False,
        }
        assert_trees_equal(plane._snapshot_params()[0], source)
    assert program._cache_size() == traced + 1


def test_a_server_never_overwrites_a_snapshot(setup):
    """The plane's pushes build beside: a tree fetched before two more
    pushes is whole, and no push lands in a retired snapshot's buffers."""
    plane = Plane(setup["params"])
    fetched, _ = plane._snapshot_params()
    before = pointers(fetched)
    for factor in (0.5, 0.25):
        plane.push_params(scaled(setup["params"], factor))
        assert plane.last_push["in_place"] is False
    assert not any(x.is_deleted() for x in leaves_of(fetched))
    assert_trees_equal(fetched, setup["params"])
    # (a freed buffer may be handed out again; these are all still held)
    assert not set(before) & set(pointers(plane._snapshot_params()[0]))


@pytest.mark.parametrize("route", ["plane", "constructor", "in_place"])
def test_snapshot_is_fresh_bit_equal_and_survives_its_source(setup, route):
    """Every route detaches the snapshot from the learner's buffers: equal
    bit for bit, no buffer shared, and whole after the source is deleted
    (what the learn step's donation does to it)."""
    source = scaled(setup["params"], 0.5)
    expect = jax.device_get(source)
    if route == "plane":
        holder = Plane()
        holder.push_params(source)
    elif route == "constructor":
        holder = _engine(setup, params=source)
    else:
        holder = _engine(setup)
        holder.push_params(source)
        assert holder.last_push["in_place"] is True
    snapshot, _gen = holder._snapshot_params()
    assert not set(pointers(snapshot)) & set(pointers(source))
    for leaf in leaves_of(source):
        leaf.delete()
    assert_trees_equal(snapshot, expect)
    if route != "plane":
        # and the engine decodes with it: one whole response a macro-step
        ref = _greedy(setup, jax.device_put(expect))
        for i, c in enumerate(_decode_all(setup, holder)):
            np.testing.assert_array_equal(c.response_tokens, ref.response_tokens[i])


def _numpy_tree(setup):
    return jax.device_get(setup["params"]), 0


def _spread_tree(setup):
    """A mesh learner's tree: every leaf on four devices."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "mp"))
    # one program over the mesh, then _place gathers onto the engine's chip
    return jax.device_put(setup["params"], NamedSharding(mesh, PartitionSpec())), 1


def _scattered_tree(setup):
    """Leaves committed to different devices: no one program takes them."""
    devices = jax.devices()
    leaves, treedef = jax.tree_util.tree_flatten(setup["params"])
    placed = [jax.device_put(x, devices[i % 2]) for i, x in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, placed), len(leaves)


def _foreign_tree(setup):
    """Whole on one device, but not the engine's."""
    return jax.device_put(setup["params"], jax.devices()[1]), 1


@pytest.mark.parametrize(
    "make", [_numpy_tree, _spread_tree, _scattered_tree, _foreign_tree],
    ids=lambda f: f.__name__.strip("_"),
)
def test_other_trees_take_the_plane_s_route(setup, make):
    """What the engine cannot overwrite in place goes the way it went:
    copied (leaf by leaf where no one program takes it), then placed on
    the engine's device, with the old snapshot left whole."""
    eng = _engine(setup, params=scaled(setup["params"], 2.0))
    old, _ = eng._snapshot_params()
    tree, programs = make(setup)
    eng.push_params(tree)
    assert eng.last_push["programs"] == programs
    assert eng.last_push["in_place"] is False
    assert not any(x.is_deleted() for x in leaves_of(old))
    snapshot, gen = eng._snapshot_params()
    assert gen == 1
    assert {d for x in leaves_of(snapshot) for d in x.devices()} == {eng._device}
    assert_trees_equal(snapshot, setup["params"])
    ref = _greedy(setup, setup["params"])
    for i, c in enumerate(_decode_all(setup, eng)):
        np.testing.assert_array_equal(c.response_tokens, ref.response_tokens[i])


@pytest.mark.parametrize("mode", ["int8", "bf16"])
def test_a_quantized_push_is_not_a_copy(setup, mode):
    eng = _engine(setup)
    old, _ = eng._snapshot_params()
    eng.push_params(scaled(setup["params"], 1.0), quantize=mode)
    assert eng.last_push["programs"] == 0 and eng.last_push["in_place"] is False
    assert not any(x.is_deleted() for x in leaves_of(old))
    snapshot, _gen = eng._snapshot_params()  # dequantized on read, and placed
    assert [x.dtype for x in leaves_of(snapshot)] == [x.dtype for x in leaves_of(old)]
    # a full-precision push after it may take the dequantized cache's place
    eng.push_params(scaled(setup["params"], 0.5))
    assert eng.last_push["programs"] == 1
    assert_trees_equal(eng._snapshot_params()[0], scaled(setup["params"], 0.5))


def test_a_changed_tree_and_the_engine_s_own_snapshot_are_not_overwritten(setup):
    eng = _engine(setup)
    snapshot, _ = eng._snapshot_params()
    # the engine's own tree pushed back: source and target are one buffer
    eng.push_params(snapshot)
    assert eng.last_push["in_place"] is False and eng.last_push["programs"] == 1
    assert_trees_equal(eng._snapshot_params()[0], setup["params"])
    # another dtype: nothing to write over
    half = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), setup["params"])
    eng.push_params(half)
    assert eng.last_push["in_place"] is False
    assert {x.dtype for x in leaves_of(eng._snapshot_params()[0])} == {jnp.dtype(jnp.bfloat16)}


def test_the_engine_s_pushes_land_in_the_first_snapshot_s_buffers(setup):
    """After the second push (and the third) every snapshot leaf sits where
    the first snapshot's did, the retired arrays are deleted, the copy was
    traced once, and the values are the source's."""
    eng = _engine(setup)
    # jit caches by the wrapped function: another engine of this process
    # (any test file that shared the worker) may have traced it already
    eng._copy_over.clear_cache()
    first, _ = eng._snapshot_params()
    where = pointers(first)
    retired = first
    for step, factor in enumerate((0.5, 0.25, 0.125), start=1):
        source = scaled(setup["params"], factor)
        gen = eng.push_params(source, learner_step=10 * step)
        n_leaves, n_bytes = param_server.tree_size(source)
        assert eng.last_push == {
            "bytes": n_bytes, "leaves": n_leaves, "generation": gen,
            "programs": 1, "in_place": True,
        }
        assert all(x.is_deleted() for x in leaves_of(retired))
        snapshot, got = eng._snapshot_params()
        assert got == gen == step
        assert pointers(snapshot) == where
        assert_trees_equal(snapshot, source)
        assert not any(x.is_deleted() for x in leaves_of(source))
        retired = snapshot
    assert eng._copy_over._cache_size() == 1
    assert eng.staleness_steps(1) == 20.0  # the gen -> step map is the plane's


def test_only_the_engine_s_program_aliases_the_old_snapshot(setup):
    """The lowered text says which program may write over its operand: the
    engine's carries ``tf.aliasing_output`` on every leaf of the old
    snapshot (a donated argument the function does not read is pruned
    before donation unless ``keep_unused``), the plane's on none."""
    eng = _engine(setup)
    snapshot, _ = eng._snapshot_params()
    source = scaled(setup["params"], 0.5)
    n_leaves = len(leaves_of(snapshot))
    text = eng._copy_over.lower(source, snapshot).as_text()
    assert text.count("tf.aliasing_output") == n_leaves
    assert "tf.aliasing_output" not in param_server._copy_program().lower(source).as_text()
    assert not any(x.is_deleted() for x in leaves_of(snapshot))  # lowering donates nothing


def test_a_macro_step_in_flight_reads_the_weights_it_was_enqueued_with(setup):
    """A macro-step enqueued before a push and read after it returns the
    old generation's tokens, though the push donated its operands'
    buffers meanwhile: the runtime orders the overwrite behind the read."""
    old, new = setup["params"], scaled(setup["params"], -3.0)
    ref_old, ref_new = _greedy(setup, old), _greedy(setup, new)
    assert not np.array_equal(ref_old.response_tokens, ref_new.response_tokens)
    eng = _engine(setup)
    eng.push_params(scaled(old, 1.0))  # the overwrite program is built
    for i in range(len(setup["lengths"])):
        eng.submit(setup["prompts"][i], setup["lengths"][i], tag=i)
    assert eng.step() == []  # admitted and decoded whole, enqueued, not read
    eng.push_params(new)
    assert eng.last_push["in_place"] is True
    done = sorted(eng.run_until(3, max_macro_steps=10), key=lambda c: c.tag)
    for i, c in enumerate(done):
        assert c.generation == 1
        np.testing.assert_array_equal(c.response_tokens, ref_old.response_tokens[i])
    for i, c in enumerate(_decode_all(setup, eng)):
        assert c.generation == 2
        np.testing.assert_array_equal(c.response_tokens, ref_new.response_tokens[i])


def test_the_pull_server_s_device_push_is_the_same_copy(setup):
    server = ParameterServer()
    source = scaled(setup["params"], 0.5)
    expect = jax.device_get(source)
    assert server.push(source, to_host=False) == 1
    for leaf in leaves_of(source):
        leaf.delete()
    weights, version = server.pull()
    assert version == 1
    assert_trees_equal(weights, expect)


def test_inference_server_serves_a_tree_fetched_before_a_concurrent_push():
    """A flush holds the tree it fetched while pushes arrive from another
    thread: the tree stays whole and the reply carries its generation."""
    import test_serving as serving

    agent = serving._agent()
    server = serving.InferenceServer(
        agent, serving.ServingConfig(max_batch=8, max_wait_s=0.002)
    )
    c_end, s_end = serving.local_pair()
    server.hub.add_connection(s_end)
    try:
        fetched, gen = server._snapshot_params()
        expect = jax.device_get(fetched)
        pusher = threading.Thread(
            target=lambda: [
                server.push_params(scaled(agent.get_weights(), f)) for f in (0.5, 0.25)
            ]
        )
        pusher.start()
        pusher.join(timeout=60.0)
        assert not pusher.is_alive() and server.generation == gen + 2
        assert server.last_push["programs"] == 1 and server.last_push["in_place"] is False
        assert not any(x.is_deleted() for x in leaves_of(fetched))
        assert_trees_equal(fetched, expect)
        server._flush([serving._req(conn=s_end, req_id=3)])
        reply = c_end.recv(timeout=10.0)
        assert reply["kind"] == "act_result" and reply["gen"] == gen + 2
    finally:
        server.hub.close()
