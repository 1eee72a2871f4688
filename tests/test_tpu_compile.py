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
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import make_exchange
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


# the two benchmark cells' attention: (q heads, kv heads, tokens, head_dim)
CELL_HEADS = {"mistral-7b": (32, 8, 4096, 128),
              "granite-3.0-3b-a800m": (24, 8, 2048, 64)}


@pytest.fixture
def on_tpu(monkeypatch):
    """The attention dispatch rule as it reads on a TPU backend (these
    programs are built on the CPU for a described chip)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("cell", sorted(CELL_HEADS))
def test_flash_attention_backward_compiles_for_v5e(one_chip, cell):
    """Forward and both backward kernels, vmapped over 2 replicas as the
    DASO step runs them, at each benchmark cell's head shapes and tuned
    blocks."""
    from repro.kernels.flash_attention import TUNED_BLOCKS
    hq, hk, seq, hd = CELL_HEADS[cell]
    q = jax.ShapeDtypeStruct((N_REPLICAS, 1, hq, seq, hd), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((N_REPLICAS, 1, hk, seq, hd), jnp.bfloat16,
                              sharding=one_chip)
    fwd, bwd = TUNED_BLOCKS[hd]
    attn = jax.vmap(functools.partial(
        ops.flash_attention, block_q=fwd[0], block_k=fwd[1],
        bwd_blocks=bwd, interpret=False))

    def loss(q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2)

    text = _compile_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    for kernel in ("flash_attn_fwd", "flash_attn_bwd_dq",
                   "flash_attn_bwd_dkv"):
        assert f"%{kernel}" in text


def test_granite_layer_scan_with_the_kernel_compiles_for_v5e(one_chip,
                                                             on_tpu):
    """One Granite-3.0-3B-A800M layer (published widths, experts, so its
    layer scan runs under SCAN_SAVE) at 2 x 2048 tokens, forward and
    backward through the default attention path of a device-local
    program: the kernel takes the attention and the program compiles."""
    from repro.configs import get_config
    from repro.models.lm import forward
    from repro.sharding import trace_device_local
    cfg = get_config("granite-moe-3b-a800m").replace(n_layers=1)
    assert cfg.moe.dropless
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip), params)
    toks = jax.ShapeDtypeStruct((1, 2048), jnp.int32, sharding=one_chip)

    def grads(p, t):
        with trace_device_local(True):
            return jax.grad(lambda p: jnp.sum(
                forward(p, t, cfg)["logits"].astype(jnp.float32)))(p)

    text = _compile_text(grads, params, toks)
    assert "%flash_attn_bwd_dkv" in text


def _without_metadata(text):
    return re.sub(r", metadata=\{[^}]*\}", "", text)


def _both_ways(monkeypatch, build):
    """The optimized HLO of `build()`'s program as the dispatch rule
    builds it on a TPU, and as on any other backend (where no program
    takes the kernel), both without metadata."""
    texts = []
    for backend in ("tpu", "cpu"):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        texts.append(_without_metadata(build()))
    return texts


def test_dispatch_keeps_jnp_in_the_gspmd_dry_run(topo, monkeypatch):
    """The dry run's sync train step under its mesh policy, on a described
    v5e:2x2 at 8 x 512 tokens (head_dim 64, a length the kernel's blocks
    divide), traced even as device-local: no Mosaic call, and the same
    program as where the kernel is never considered."""
    from jax.sharding import Mesh
    from repro.configs import get_reduced
    from repro.launch import dryrun, specs
    from repro.sharding import trace_device_local
    monkeypatch.setitem(specs.INPUT_SHAPES, "train_4k", (512, 8, "train"))
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    cfg = get_reduced("llama3.2-1b")

    def build():
        with trace_device_local(True):
            lowered, _ = dryrun.build_train_lowering(cfg, mesh, daso=False)
        return lowered.compile().as_text()

    tpu, other = _both_ways(monkeypatch, build)
    assert "tpu_custom_call" not in tpu
    assert tpu == other


def _cycle_program(topo, placement_mesh: bool, seq: int):
    """The launcher's DASO send/receive cycle for 2 replicas (head_dim
    64), compiled with a `chip:2 x host:2` MeshPlacement on a described
    v5e:2x2 or, without one, on one of its chips."""
    from repro.core.executor import MacroCycleExecutor
    from repro.launch import train
    from repro.launch.distributed import MeshPlacement
    from repro.launch.mesh import make_topology_mesh
    from repro.optim.optimizers import sgd
    from repro.train.loop import build_strategy

    args = train.build_parser().parse_args(
        ["--tiny", "--seq-len", str(seq), "--per-node-batch", "2",
         "--steps", "20", "--b-max", "4", "--topology", "chip:2 x host:2"])
    cfg = train.arch_config(args).replace(n_heads=2, n_kv_heads=1,
                                          head_dim=64)
    job = train.build_job(args, cfg)
    strat = build_strategy(job.loss_fn, job.loop_cfg, sgd(momentum=0.9))
    strat.plan_cycle(0, 20)  # the blocking warm-up
    shape = strat.plan_cycle(2, 18).shape
    carry = jax.eval_shape(strat.init_carry, job.params0)
    dims = (len(shape), job.spec.n_replicas, 2, seq)
    if placement_mesh:
        pl = MeshPlacement(job.spec, mesh=make_topology_mesh(
            job.spec, devices=topo.devices))
        carry = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=sh),
            carry, pl.carry_shardings(carry))
        tok_sh, lr_sh = pl._batch_sharding(4, dims, 1), pl.replicated
    else:
        pl = None
        one = SingleDeviceSharding(topo.devices[0])
        carry = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), carry)
        tok_sh = lr_sh = one
    tokens = jax.ShapeDtypeStruct(dims, jnp.int32, sharding=tok_sh)
    lrs = jax.ShapeDtypeStruct((len(shape),), jnp.float32, sharding=lr_sh)
    program = MacroCycleExecutor(strat, placement=pl).program_for(shape)
    return program.lower(carry, {"tokens": tokens, "labels": tokens},
                         lrs).compile().as_text()


@pytest.mark.parametrize("placement_mesh,seq,kernels", [
    (False, 512, True), (True, 512, False), (False, 384, False)])
def test_dispatch_follows_placement_and_length(topo, monkeypatch,
                                               placement_mesh, seq,
                                               kernels):
    """The same DASO cycle takes the fused kernel on one chip without a
    placement, and keeps the jnp path, compiled exactly as where the
    kernel is never considered, under a MeshPlacement or at a length the
    kernel's blocks do not divide."""
    tpu, other = _both_ways(
        monkeypatch, lambda: _cycle_program(topo, placement_mesh, seq))
    assert ("%flash_attn_fwd" in tpu) == kernels
    if not kernels:
        assert "tpu_custom_call" not in tpu
        assert tpu == other


def test_dispatch_keeps_jnp_for_decode(one_chip, monkeypatch):
    """One-token decode against a cache (head_dim 64), traced as
    device-local: never the kernel."""
    from repro.configs import get_reduced
    from repro.models.lm import forward, init_cache
    from repro.sharding import trace_device_local
    cfg = get_reduced("llama3.2-1b").replace(param_dtype="bfloat16",
                                             compute_dtype="bfloat16")
    place = functools.partial(jax.tree.map, lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip))
    params = place(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))))
    cache = place(jax.eval_shape(lambda: init_cache(cfg, 2, 1024)))
    tok = jax.ShapeDtypeStruct((2, 1), jnp.int32, sharding=one_chip)

    def decode(p, c, t):
        with trace_device_local(True):
            return forward(p, t, cfg, cache=c, pos=512)["logits"]

    tpu, other = _both_ways(
        monkeypatch, lambda: _compile_text(decode, params, cache, tok))
    assert "tpu_custom_call" not in tpu
    assert tpu == other


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
        ex = make_exchange(N_REPLICAS, device_local=device_local)
        return jax.jit(functools.partial(global_send, ex=ex)).lower(
            tree).compile()

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
        return new, replica_mean(
            {"w": new}, ex=make_exchange(N_REPLICAS, device_local=True),
            wire="f32")

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
