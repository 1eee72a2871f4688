"""Checkpoint IO: bit-exact round-trip properties over mixed-dtype pytrees
(bf16 leaves, list/tuple containers, optimizer state), sharded restore
placement, and the versioned TrainState layer that backs deterministic
resume (controller schedule state, membership, loss trace)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.checkpoint.io import (TRAIN_STATE_VERSION, TrainState,
                                 load_checkpoint, load_train_state,
                                 save_checkpoint, save_train_state)
from repro.core.daso import DasoConfig
from repro.core.schedule import DasoController
from repro.optim.optimizers import adamw, sgd

_LEAF_SPECS = [
    ("float32", (3, 4)), ("float32", (7,)), ("bfloat16", (5, 3)),
    ("bfloat16", (2,)), ("float16", (4,)), ("int32", (6,)),
    ("int8", (3, 3)), ("uint32", (2, 2)),
]


def _leaf(rng, dt, shape):
    if dt.startswith(("int", "uint")):
        x = rng.randint(0 if dt.startswith("u") else -100, 100, size=shape)
    else:
        x = rng.randn(*shape) * 3
    return jnp.asarray(x).astype(dt)


def _assert_trees_identical(a, b):
    """Same treedef (tuple vs list distinguished), same dtypes, same bits."""
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    assert ta == tb, (ta, tb)
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x, np.float32)
                                      if x.dtype == jnp.bfloat16
                                      else np.asarray(x),
                                      np.asarray(y, np.float32)
                                      if y.dtype == jnp.bfloat16
                                      else np.asarray(y))


# -------------------------------------------------------- round-trips --

@given(st.lists(st.sampled_from(_LEAF_SPECS), min_size=1, max_size=6),
       st.sampled_from(["dict", "list", "tuple", "nested"]))
@settings(max_examples=20, deadline=None)
def test_roundtrip_mixed_dtype_property(specs, container):
    """save -> load is bit-identical (bf16 via the exact f32 widening) and
    structure-exact: lists come back lists, tuples come back tuples."""
    import tempfile

    rng = np.random.RandomState(len(specs) + len(container))
    leaves = [_leaf(rng, dt, shape) for dt, shape in specs]
    if container == "dict":
        tree = {f"k{i}": x for i, x in enumerate(leaves)}
    elif container == "list":
        tree = list(leaves)
    elif container == "tuple":
        tree = tuple(leaves)
    else:
        tree = {"a": (leaves[0], list(leaves)), "b": {"c": tuple(leaves)}}
    with tempfile.TemporaryDirectory() as path:
        save_checkpoint(path, tree, step=3)
        loaded, manifest = load_checkpoint(path)
    assert manifest["step"] == 3
    _assert_trees_identical(tree, loaded)


@pytest.mark.parametrize("opt_factory", [lambda: sgd(momentum=0.9),
                                         lambda: adamw()])
def test_optimizer_state_roundtrip(opt_factory, tmp_path):
    """Optimizer states (momentum trees, adamw's scalar step counter)
    survive the checkpoint layer exactly."""
    opt = opt_factory()
    params = {"w": jnp.ones((3, 2)), "b": jnp.zeros((2,), jnp.bfloat16)}
    state = opt.init(params)
    # advance once so the state is non-trivial
    grads = jax.tree.map(jnp.ones_like, params)
    _, state = opt.update(grads, state, params, 0.1)
    save_checkpoint(str(tmp_path), {"opt": state})
    loaded, _ = load_checkpoint(str(tmp_path))
    _assert_trees_identical(state, loaded["opt"])


def test_sharded_restore_placement(tmp_path):
    """Restore with a shardings pytree places every leaf with the
    requested NamedSharding."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("pod",))
    tree = {"w": jnp.arange(8, dtype=jnp.float32).reshape(2, 4),
            "b": jnp.ones((4,))}
    save_checkpoint(str(tmp_path), tree)
    sh = {"w": NamedSharding(mesh, P("pod")),
          "b": NamedSharding(mesh, P())}
    loaded, _ = load_checkpoint(str(tmp_path), shardings=sh)
    for k in tree:
        assert loaded[k].sharding.is_equivalent_to(sh[k], loaded[k].ndim)
        np.testing.assert_array_equal(np.asarray(loaded[k]),
                                      np.asarray(tree[k]))


# --------------------------------------------------------- TrainState --

def _controller_with_history():
    cfg = DasoConfig(n_replicas=2, global_world=8, b_max=4,
                     warmup_steps=2, cooldown_steps=2, total_steps=30)
    c = DasoController(cfg, loss_window=5)
    for t in range(12):
        c.mode_for_step(t)
        c.observe_loss(1.0 / (t + 1))
    c.notify_membership_change(12, 1)
    c.notify_dcn_scale(0.5, step=12)
    return cfg, c


def test_train_state_roundtrip(tmp_path):
    """Full TrainState: carry (tuple of trees incl. bf16), controller
    schedule state (window, history, events), membership, losses."""
    cfg, c = _controller_with_history()
    carry = ({"w": jnp.ones((2, 3, 3)), "b": jnp.zeros((2, 4), jnp.bfloat16)},
             {"mu": {"w": jnp.full((2, 3, 3), 0.5)}},
             {"w": jnp.ones((2, 3, 3)) * 2})
    state = TrainState(step=12, carry=carry, controller=c.state_dict(),
                       membership=[1.0, 0.0],
                       rng=jax.random.PRNGKey(7), strategy="daso",
                       losses=[1.0, 0.5, 0.25])
    save_train_state(str(tmp_path), state)
    loaded = load_train_state(str(tmp_path))
    assert loaded.version == TRAIN_STATE_VERSION
    assert loaded.step == 12
    assert loaded.strategy == "daso"
    assert loaded.membership == [1.0, 0.0]
    assert loaded.losses == [1.0, 0.5, 0.25]
    _assert_trees_identical(carry, loaded.carry)
    np.testing.assert_array_equal(np.asarray(loaded.rng),
                                  np.asarray(jax.random.PRNGKey(7)))
    # a controller restored from the loaded dict behaves identically
    c2 = DasoController(cfg, loss_window=5)
    c2.load_state_dict(loaded.controller)
    assert c2.state_dict() == c.state_dict()
    assert c2.history == c.history and c2.events == c.events
    assert (c2.b, c2.w) == (c.b, c.w)
    for t in range(12, 20):
        assert c2.mode_for_step(t) == c.mode_for_step(t)


def test_train_state_version_guard(tmp_path):
    """A checkpoint from a newer TrainState version is refused, and a bare
    parameter checkpoint is not mistaken for a TrainState."""
    state = TrainState(step=1, carry=({"w": jnp.ones(2)},),
                       version=TRAIN_STATE_VERSION + 1)
    save_train_state(str(tmp_path / "new"), state)
    with pytest.raises(ValueError, match="newer"):
        load_train_state(str(tmp_path / "new"))
    save_checkpoint(str(tmp_path / "bare"), {"w": jnp.ones(2)})
    with pytest.raises(ValueError, match="not a TrainState"):
        load_train_state(str(tmp_path / "bare"))
