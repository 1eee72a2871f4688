"""Fused flat-buffer exchange: pack/unpack roundtrip properties over
mixed-dtype/mixed-shape pytrees, wire-codec tiers (bf16 / int8 error
bounds), Pallas comm kernels vs the jnp oracles, dtype/wire-aware
transfer_bytes, and the HLO-level guarantee that one global exchange is
exactly ONE cross-replica all-reduce independent of leaf count."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import flatbuf
from repro.core.compression import (compress_bf16_roundtrip, transfer_bytes,
                                    wire_itemsize)
from repro.kernels import ops, ref

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# (dtype, shape) menu for the mixed-tree property; the shim's sampled_from
# handles arbitrary items
_LEAF_SPECS = [
    ("float32", (3, 4)), ("float32", (7,)), ("float32", (2, 2, 2)),
    ("bfloat16", (5, 3)), ("bfloat16", (8,)),
    ("float16", (4, 4)), ("int32", (6,)), ("int8", (3, 3)),
]


def _make_tree(specs, batch_shape=()):
    rng = np.random.RandomState(len(specs))
    tree = {}
    for i, (dt, shape) in enumerate(specs):
        full = batch_shape + shape
        if dt.startswith("int"):
            x = rng.randint(-100, 100, size=full)
        else:
            x = rng.randn(*full) * 3
        tree[f"leaf{i}"] = jnp.asarray(x).astype(dt)
    return tree


# ------------------------------------------------------- pack/unpack ----

@given(st.lists(st.sampled_from(_LEAF_SPECS), min_size=1, max_size=8),
       st.sampled_from([0, 1]))
@settings(max_examples=25, deadline=None)
def test_pack_unpack_roundtrip_property(specs, batch_dims):
    """pack -> unpack is bit-identical for every dtype (no casts ever
    happen during packing), for flat and replica-batched trees."""
    tree = _make_tree(specs, batch_shape=(3,) * batch_dims)
    layout = flatbuf.build_layout(tree, batch_dims=batch_dims)
    arenas = flatbuf.pack(tree, layout)
    # one arena per distinct dtype, each 1-D past the batch dims
    assert set(arenas) == {jnp.dtype(dt).name for dt, _ in specs}
    for key, arena in arenas.items():
        assert arena.shape == (3,) * batch_dims + (layout.arena_sizes[key],)
    out = flatbuf.unpack(arenas, layout)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


_MEAN_LEAF_SPECS = [("float32", (3, 4)), ("float32", (7,)),
                    ("bfloat16", (5, 3)), ("bfloat16", (8,))]


@given(st.lists(st.sampled_from(_MEAN_LEAF_SPECS), min_size=1, max_size=5),
       st.sampled_from([2, 3, 4]), st.sampled_from(["f32", "bf16"]),
       st.integers(0, 14), st.booleans())
@settings(max_examples=20, deadline=None)
def test_leafwise_mean_is_bit_identical_to_arena_property(
        specs, r, wire_format, mask_bits, deterministic):
    """The replica mean taken leaf by leaf (`device_local`, the one-device
    program) gives the same bits as the packed-arena mean: f32 and bf16
    leaves plus an int32 leaf, f32 and bf16 wire, with and without a
    membership mask, both reduction tiers."""
    from repro.core.daso import replica_mean
    tree = _make_tree(list(specs) + [("int32", (6,))], batch_shape=(r,))
    mask = tuple(float(mask_bits >> i & 1) for i in range(r))
    mask = mask if 0 < sum(mask) < r else None

    def mean(device_local):
        return jax.jit(lambda t: replica_mean(
            t, wire_format=wire_format, mask=mask,
            deterministic=deterministic, device_local=device_local))(tree)

    arena, leafwise = mean(False), mean(True)
    for k, x in tree.items():
        a, b = np.asarray(arena[k]), np.asarray(leafwise[k])
        assert a.dtype == b.dtype == x.dtype and a.shape == b.shape, k
        assert a.tobytes() == b.tobytes(), (k, wire_format, mask)


def test_layout_static_offsets():
    tree = {"a": jnp.zeros((2, 3)), "b": jnp.zeros((5,)),
            "c": jnp.zeros((4,), jnp.int32)}
    layout = flatbuf.build_layout(tree)
    assert layout.n_leaves == 3
    assert layout.arena_sizes == {"float32": 11, "int32": 4}
    slots = {s.offset: s.size for s in layout.slots if s.arena == "float32"}
    assert slots == {0: 6, 6: 5}


def test_layout_rejects_mismatched_batch_dims():
    tree = {"a": jnp.zeros((2, 3)), "b": jnp.zeros((4, 3))}
    with pytest.raises(ValueError):
        flatbuf.build_layout(tree, batch_dims=1)


# ------------------------------------------------------- wire codecs ----

def test_bf16_wire_roundtrip_matches_per_leaf_cast():
    tree = _make_tree([("float32", (9, 5)), ("float32", (17,)),
                       ("int32", (4,))])
    out = flatbuf.tree_wire_roundtrip(tree, "bf16")
    for k in ("leaf0", "leaf1"):
        expect = tree[k].astype(jnp.bfloat16).astype(jnp.float32)
        np.testing.assert_array_equal(np.asarray(out[k]),
                                      np.asarray(expect))
    # non-floating leaves pass through untouched
    np.testing.assert_array_equal(np.asarray(out["leaf2"]),
                                  np.asarray(tree["leaf2"]))
    # compression.py back-compat wrapper rides the same codec
    out2 = compress_bf16_roundtrip(tree)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(out2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@given(st.sampled_from([64, 128, 256]), st.integers(1, 2000),
       st.booleans())
@settings(max_examples=20, deadline=None)
def test_int8_quantize_error_bounds_property(block, n, stochastic):
    """Per-block absmax scaling: |x - deq(q(x))| <= scale/2 per block for
    round-to-nearest, < scale for stochastic rounding."""
    key = jax.random.PRNGKey(block + n)
    x = jax.random.normal(key, (n,)) * (1.0 + n % 7)
    bits = (jax.random.bits(jax.random.fold_in(key, 1), x.shape, jnp.uint32)
            if stochastic else None)
    v, s = ops.quantize_int8(x, bits, block=block, interpret=True)
    d = ops.dequantize_int8(v, s, block=block, interpret=True)
    # expand per-block scales to elementwise bounds
    nb = s.shape[-1]
    bound = np.repeat(np.asarray(s), block)[:n]
    err = np.abs(np.asarray(d) - np.asarray(x))
    tol = 1e-6
    if stochastic:
        assert np.all(err <= bound + tol)
    else:
        assert np.all(err <= bound / 2 + tol)
    assert nb == -(-n // block)


def test_int8_stochastic_rounding_is_unbiased():
    """Mean of many stochastic draws converges to x (round-to-nearest has
    a deterministic bias of up to scale/2; stochastic is unbiased)."""
    key = jax.random.PRNGKey(0)
    x = np.full(256, 0.325, np.float32)
    x[0] = 12.7  # pins the block scale to 12.7/127 = 0.1 exactly
    x = jnp.asarray(x)
    # deterministic: 0.325/0.1 = 3.25 rounds to 3 -> constant 0.025 bias
    vd, sd = ops.quantize_int8(x, block=256, interpret=True)
    det = np.asarray(ops.dequantize_int8(vd, sd, block=256,
                                         interpret=True))[1:]
    assert abs(det.mean() - 0.325) > 0.02
    acc = 0.0
    draws = 200
    for i in range(draws):
        bits = jax.random.bits(jax.random.fold_in(key, i),
                               x.shape, jnp.uint32)
        vv, ss = ops.quantize_int8(x, bits, block=256, interpret=True)
        acc += np.asarray(ops.dequantize_int8(vv, ss, block=256,
                                              interpret=True))[1:].mean()
    assert abs(acc / draws - 0.325) < 0.005


# --------------------------------------------------- kernels vs refs ----

def test_eq1_merge_kernel_matches_ref():
    key = jax.random.PRNGKey(3)
    local = jax.random.normal(key, (2, 999))
    stale = jax.random.normal(jax.random.fold_in(key, 1), (2, 999))
    out = ops.eq1_merge(local, stale, staleness=3, global_world=16,
                        block=256, interpret=True)
    expect = ref.eq1_merge_ref(local, stale, staleness=3, global_world=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-6)


def test_bf16_pack_unpack_kernels():
    key = jax.random.PRNGKey(4)
    x = jax.random.normal(key, (3, 500))
    b = ops.bf16_pack(x, block=128, interpret=True)
    assert b.dtype == jnp.bfloat16 and b.shape == x.shape
    u = ops.bf16_unpack(b, block=128, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(u), np.asarray(x.astype(jnp.bfloat16)
                                  .astype(jnp.float32)))


def test_quantize_kernel_matches_ref():
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (2, 777)) * 4
    for bits in (None, jax.random.bits(key, x.shape, jnp.uint32)):
        v, s = ops.quantize_int8(x, bits, block=128, interpret=True)
        vr, sr = ref.quantize_int8_block_ref(x, block=128, bits=bits)
        np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                                   rtol=1e-6)
        # a 1-ULP scale difference may flip a rounding boundary
        assert np.max(np.abs(np.asarray(v, np.int32)
                             - np.asarray(vr, np.int32))) <= 1
        d = ops.dequantize_int8(v, s, block=128, interpret=True)
        dr = ref.dequantize_int8_block_ref(vr, sr, block=128)
        np.testing.assert_allclose(np.asarray(d), np.asarray(dr),
                                   atol=1e-4)


# ------------------------------------------------------ byte account ----

def test_transfer_bytes_dtype_and_wire_aware():
    tree = {"w": jnp.zeros((100,), jnp.float32),
            "b": jnp.zeros((10,), jnp.bfloat16),
            "step": jnp.zeros((3,), jnp.int32)}
    # floating leaves charged at the wire tier; int32 at its own 4 bytes.
    # "f32" is identity — the bf16 leaf still crosses at 2 bytes/elem
    assert transfer_bytes(tree, wire_format="f32") == \
        100 * 4 + 10 * 2 + 12
    assert transfer_bytes(tree, wire_format="bf16") == 110 * 2 + 12
    # int8: 1 byte/elem + one f32 scale per (ceil) block per dtype arena
    assert transfer_bytes(tree, wire_format="int8", int8_block=64) == \
        (100 + 4 * 2) + (10 + 4 * 1) + 12
    # blocks span leaf boundaries inside an arena (matching the fused
    # codec, which quantizes the packed arena): two 10-elem f32 leaves
    # share one 64-elem block, not one block each
    pair = {"a": jnp.zeros((10,)), "b": jnp.zeros((10,))}
    assert transfer_bytes(pair, wire_format="int8", int8_block=64) == \
        20 + 4 * 1
    with pytest.raises(ValueError):
        transfer_bytes(tree, wire_format="f8")


def test_int8_wire_halves_bf16_bytes():
    """Acceptance: int8 wire format halves transfer_bytes vs bf16 (up to
    the per-block scale overhead)."""
    tree = {f"w{i}": jnp.zeros((4096,), jnp.float32) for i in range(8)}
    b16 = transfer_bytes(tree, wire_format="bf16")
    i8 = transfer_bytes(tree, wire_format="int8", int8_block=256)
    assert i8 <= b16 * 0.51
    assert wire_itemsize("int8", int8_block=256) == pytest.approx(
        1.0 + 4.0 / 256)


# ------------------------------------------------------ HLO contract ----

def test_one_exchange_is_one_all_reduce_any_leaf_count():
    """The fused exchange lowers to exactly ONE cross-replica all-reduce
    independent of the number of parameter leaves. (The legacy per-leaf
    path is no longer held to one all-reduce per leaf: XLA's all-reduce
    combiner may merge them, so it is only checked to lower to at least
    one.) Runs on a 2-virtual-device pod mesh in a subprocess (the main
    pytest process keeps its single real device)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    script = """
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.daso import blocking_sync, replica_mean_per_leaf
        from repro.launch.hlo_stats import collective_stats

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2,), ("pod",))
        sh = NamedSharding(mesh, P("pod"))

        def n_all_reduce(fn, tree):
            shard = {k: sh for k in tree}
            hlo = jax.jit(fn, in_shardings=(shard,)).lower(
                tree).compile().as_text()
            stats = collective_stats(hlo, {"pod": 2})
            return sum(v["count"] for k, v in stats.items()
                       if isinstance(v, dict) and k.startswith("all-reduce"))

        for n_leaves in (2, 7):
            tree = {f"w{i}": jax.ShapeDtypeStruct((2, 32, 3 + i),
                                                  jnp.float32)
                    for i in range(n_leaves)}
            for wf in ("f32", "bf16", "int8"):
                n = n_all_reduce(
                    lambda t, wf=wf: blocking_sync(t, wire_format=wf), tree)
                assert n == 1, (wf, n_leaves, n)
            n = n_all_reduce(
                lambda t: replica_mean_per_leaf(t, jnp.bfloat16), tree)
            assert 1 <= n <= n_leaves, (n_leaves, n)
        print("ONE COLLECTIVE OK")
    """
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                       capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "ONE COLLECTIVE OK" in r.stdout
