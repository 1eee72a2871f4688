"""The main-path Pallas kernels compile for a TPU v5e at real widths, the
one-chip global send skips the packed arena's traffic, and the four-chip
DASO cycle keeps its carry's shardings.

Nothing runs: each kernel is lowered and compiled for a v5e chip that is
described, not attached, which is what catches tiling and VMEM refusals
that interpret mode cannot see. The widths are those `chip_smoke.py`
trains: the flat-buffer arena of 2 replicas of llama3.2-1b at 2 layers,
and that model's attention head shape.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file."""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.models.lm import init_params

N_REPLICAS = 2
BATCH, SEQ = 2, 1024


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with JAX's persistent cache off while this
    module compiles for it (such an entry could not be read back without
    a chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def model():
    return get_config("llama3.2-1b").replace(n_layers=2)


@pytest.fixture(scope="module")
def arena_width(model):
    shapes = jax.eval_shape(lambda: init_params(model, jax.random.PRNGKey(0)))
    return sum(x.size for x in jax.tree.leaves(shapes))


def _compile_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("kernel,in_dtypes", [
    ("eq1_merge", (jnp.bfloat16, jnp.bfloat16)),
    ("bf16_pack", (jnp.float32,)),
    ("bf16_unpack", (jnp.bfloat16,)),
    ("quantize_int8", (jnp.float32,)),
    ("dequantize_int8", (jnp.int8, jnp.float32)),
])
def test_comm_kernel_compiles_for_v5e(kernel, in_dtypes, one_chip,
                                      arena_width):
    shape = (N_REPLICAS, arena_width)
    if kernel == "dequantize_int8":  # values, then one scale per block
        shapes = (shape, (N_REPLICAS, -(-arena_width // 256)))
    else:
        shapes = (shape,) * len(in_dtypes)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in zip(shapes, in_dtypes)]
    kw = dict(staleness=2, global_world=2) if kernel == "eq1_merge" else {}
    fn = functools.partial(getattr(ops, kernel), interpret=False, **kw)
    assert "tpu_custom_call" in _compile_text(fn, *args)


def test_stochastic_quantize_compiles_for_v5e(one_chip):
    """The stochastic-rounding branch reads uint32 bits; one row block of
    the arena is enough to exercise its lowering."""
    x = jax.ShapeDtypeStruct((N_REPLICAS, 1 << 20), jnp.float32,
                             sharding=one_chip)
    bits = jax.ShapeDtypeStruct(x.shape, jnp.uint32, sharding=one_chip)
    fn = functools.partial(ops.quantize_int8, interpret=False)
    assert "tpu_custom_call" in _compile_text(fn, x, bits)


def test_flash_attention_compiles_for_v5e(one_chip, model):
    q = jax.ShapeDtypeStruct((BATCH, model.n_heads, SEQ, model.head_dim),
                             jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((BATCH, model.n_kv_heads, SEQ,
                               model.head_dim), jnp.bfloat16,
                              sharding=one_chip)
    fn = functools.partial(ops.flash_attention, interpret=False)
    assert "tpu_custom_call" in _compile_text(fn, q, kv, kv)


def test_leafwise_send_reads_half_the_arena_bytes(one_chip, model):
    """The global send of 2 replicas on one v5e, compiled both ways: taken
    leaf by leaf it accesses at most half the bytes of the packed-arena
    send, and holds no temporary of the (R, N) arena's size."""
    from repro.core.daso import global_send
    shapes = jax.eval_shape(lambda: init_params(model,
                                                jax.random.PRNGKey(0)))
    tree = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        (N_REPLICAS,) + s.shape, s.dtype, sharding=one_chip), shapes)
    arena_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(tree))

    def compiled(device_local):
        return jax.jit(functools.partial(
            global_send, device_local=device_local)).lower(tree).compile()

    def bytes_accessed(c):
        cost = c.cost_analysis()
        return (cost[0] if isinstance(cost, list) else cost)[
            "bytes accessed"]

    arena, leafwise = compiled(False), compiled(True)
    assert bytes_accessed(leafwise) <= bytes_accessed(arena) / 2
    assert arena.memory_analysis().temp_size_in_bytes >= arena_bytes
    assert leafwise.memory_analysis().temp_size_in_bytes < arena_bytes


def test_leafwise_send_sums_the_stored_bf16_values(one_chip):
    """A bf16 leaf updated and averaged over replicas in one program, as
    the send step does, compiled for a v5e: the reduction reads the
    updated leaf through its bits. With a plain cast (or a
    `reduce_precision` after it) the compiler fuses the update with the
    reduction, the bit-level read is gone from the optimized HLO, and the
    sum may see the update before its rounding to bf16."""
    from repro.core.daso import replica_mean
    shape = (N_REPLICAS, 1536, 512)
    p = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    g = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def send(p, g):
        new = (p.astype(jnp.float32) - 0.003 * g).astype(jnp.bfloat16)
        return new, replica_mean({"w": new}, jnp.float32, device_local=True)

    assert "bitcast-convert(" in _compile_text(send, p, g)


def test_mesh_cycle_keeps_the_carry_sharding(topo):
    """The send/receive macro-cycle of the `chip:2 x host:2` DASO job,
    compiled for a described v5e:2x2 mesh, returns every carry leaf on the
    sharding it was given. Left to GSPMD, the in-flight global mean comes
    back replicated, and each later cycle recompiles for it."""
    from repro.core.executor import MacroCycleExecutor
    from repro.launch import train
    from repro.launch.distributed import MeshPlacement
    from repro.launch.mesh import make_topology_mesh
    from repro.optim.optimizers import sgd
    from repro.train.loop import build_strategy

    args = train.build_parser().parse_args(
        ["--tiny", "--seq-len", "128", "--per-node-batch", "2", "--steps",
         "20", "--b-max", "4", "--topology", "chip:2 x host:2"])
    job = train.build_job(args, train.arch_config(args))
    pl = MeshPlacement(job.spec, mesh=make_topology_mesh(
        job.spec, devices=topo.devices))
    strat = build_strategy(job.loss_fn, job.loop_cfg, sgd(momentum=0.9))
    strat.plan_cycle(0, 20)  # the blocking warm-up
    shape = strat.plan_cycle(2, 18).shape
    assert [m for m, _ in shape] == ["send", "receive", "local", "local"]

    carry = jax.eval_shape(strat.init_carry, job.params0)
    carry = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        carry, pl.carry_shardings(carry))
    dims = (len(shape), job.spec.n_replicas, 2, 128)
    tokens = jax.ShapeDtypeStruct(dims, jnp.int32,
                                  sharding=pl._batch_sharding(4, dims, 1))
    lrs = jax.ShapeDtypeStruct((len(shape),), jnp.float32,
                               sharding=pl.replicated)
    program = MacroCycleExecutor(strat, placement=pl).program_for(shape)
    compiled = program.lower(carry, {"tokens": tokens, "labels": tokens},
                             lrs).compile()
    leaves = jax.tree.leaves(carry)
    ins = jax.tree.leaves(compiled.input_shardings[0][0])
    outs = jax.tree.leaves(compiled.output_shardings[0])
    assert len(ins) == len(outs) == len(leaves)
    moved = [(x.shape, i.spec, o.spec) for x, i, o in zip(leaves, ins, outs)
             if not o.is_equivalent_to(i, x.ndim)]
    assert not moved, moved
