"""DASO core: hierarchical + asynchronous + selective optimization in SPMD JAX.

Layout-agnostic, level-parameterized formulation. Every parameter leaf
carries a leading *replica* axis of size R — one entry per unit of the
finest replica level of the cluster topology (repro/topo; in the paper's
two-level special case, one per node/pod). Inside a replica sits the
innermost topology tier (the `data` mesh axis); the replica axis itself can
span any number of outer tiers (host, pod, ...), inner levels varying
fastest in the replica index. The per-replica training step runs under
vmap, and syncs hit the levels like this:

  * level-0 sync — the loss mean over the per-replica batch makes XLA emit
    a gradient all-reduce over the intra-replica "data" axis only (fast
    NVLink/ICI): exactly the paper's node-local NCCL gradient averaging,
    every step.
  * inner-level sync — `level_group_mean` averages params over contiguous
    replica groups of size g_l (all replicas inside one unit of level l): a
    synchronous tier-l parameter average, one collective per arena spanning
    exactly that level's mesh axes, every B_l steps (scheduled by
    `HierDasoController`; absent from 2-level specs).
  * outermost sync — a mean over the full replica axis lowers to the
    slowest-tier (cross-pod / DCN) all-reduce: exactly the paper's MPI
    group exchange. It appears in the HLO only in the step variants that
    perform it. Every level's exchange runs on the fused flat-buffer arena
    (core/flatbuf.py): the parameter pytree is packed into one contiguous
    buffer per dtype, so a sync at any level is ONE collective per arena
    regardless of leaf count (Horovod-style tensor fusion), with the wire
    tier (f32 | bf16 | int8 block-scaled) applied to the whole arena at
    once (kernels/comm_kernels.py). Where the replica axis never leaves
    one device there is no collective to coalesce, and the outermost mean
    is taken leaf by leaf with the same arithmetic (`replica_mean`'s
    `device_local`).

Step variants (selected by the host-side controllers in core/schedule.py,
mirroring the MPI process flow of paper Fig. 5; static per-variant
compilation keeps each HLO's collective set exact for the roofline audit).
The outermost level's action is one of:

  local     forward/backward + local optimizer step only
  send      local + snapshot params and start the outermost exchange:
            inflight <- mean_replicas(params)
  receive   local + merge the (now stale, S steps old) exchange result via
            paper Eq. (1):  x = (2S * x_local + P * x_stale_mean) / (2S + P)
            — P generalizes per level as the world size of the level that
            went stale (the full world for the outermost level)
  blocking  local + synchronous global parameter average with bf16
            transfer compression (warm-up / cool-down phases)
  hard_avg  local + naive parameter overwrite (local-SGD ablation)

and `inner_syncs` on `daso_train_step` adds the synchronous group averages
of whichever intermediate levels tick that step — empty for the paper's
two-level layout, which keeps that case's compiled step graph identical to
the pre-topology build.

Every variant optionally bakes a static elastic-membership mask
(`membership=` on `daso_train_step`): exchanges at every level become
membership-weighted means over the active replicas of each group (still one
collective per sync per level), Eq. (1) runs with the effective world size,
and dropped replicas' rows are frozen ghosts until a rejoin re-seeds them
(src/repro/resilience/; fault plans may name whole topology subtrees).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import flatbuf
from repro.optim.optimizers import Optimizer

EXCHANGE_IMPLS = ("fused", "per_leaf")

# Compute/communication overlap of the outermost exchange (DasoConfig
# .overlap). "off" keeps the paper-faithful in-cycle dataflow — the step
# graphs are bit-identical to the pre-overlap build. "one_cycle"
# double-buffers the exchange: each cycle all-reduces the PREVIOUS cycle's
# parameter snapshot (the `pending` arena) while the next B local steps
# run, and merges the result one cycle stale via Eq. (1) with the extra
# buffer age added to S (see `daso_overlap_step`).
OVERLAP_MODES = ("off", "one_cycle")


@dataclass(frozen=True)
class DasoConfig:
    n_replicas: int              # R: paper "nodes" (pods / virtual nodes)
    global_world: int            # P in Eq. (1): GPUs in the global network
    b_max: int = 4               # paper: max batches between global syncs
    warmup_steps: int = 0
    cooldown_steps: int = 0
    total_steps: int = 0
    compress_blocking: bool = True
    # BEYOND-PAPER: the paper skips 16-bit packaging for non-blocking sends
    # (MPI packaging delays the Isend). In SPMD/XLA the cast fuses into the
    # collective with no launch delay, so compressing the cycling-phase
    # exchange halves DCN bytes for free. Default False = paper-faithful.
    compress_nonblocking: bool = False
    plateau_patience: int = 5
    plateau_threshold: float = 1e-3
    # Wire format of the global exchange: None derives it from the
    # compress_* flags per phase (bf16 or f32); "f32" | "bf16" | "int8"
    # forces one tier for both phases. int8 is the beyond-paper
    # block-scaled tier (QSGD-style, see core/flatbuf.py).
    wire_format: Optional[str] = None
    # "fused" = flat-buffer arena exchange (one cross-replica reduction per
    # global sync regardless of leaf count); "per_leaf" = the legacy
    # one-collective-per-leaf reference path (equivalence oracle).
    exchange_impl: str = "fused"
    # Transport-invariant exchanges: every cross-replica mean runs as an
    # explicitly associated chain of adds (flatbuf.chain_axis0_sum) instead
    # of one lax.reduce, so results are bit-identical for ANY process
    # layout of the replica axis. The multi-process runtime switches this
    # on (its 1-process oracle too); default False keeps the
    # one-collective-per-arena HLO contract and single-program perf.
    deterministic_reduce: bool = False
    # Route the arena's elementwise exchange math (Eq.(1) merge, wire
    # casts, int8 codec) through the Pallas kernels in
    # repro.kernels.comm_kernels instead of plain jnp. Default False: the
    # jnp path lowers to HLO the SPMD partitioner can shard exactly, which
    # the cross-pod traffic audit (tests/test_distributed.py) relies on;
    # flip on for single-device arenas and compiled TPU kernels.
    exchange_kernels: bool = False
    int8_block: int = 256        # elements per int8 scale block
    # True asynchronous overlap of the outermost exchange ("off" |
    # "one_cycle", see OVERLAP_MODES above). With "one_cycle" the strategy
    # carry grows a fourth slot (the `pending` snapshot arena) and the
    # schedule switches to the ov_start/ov_sync cycle family.
    overlap: str = "off"

    def __post_init__(self):
        if self.wire_format is not None:
            flatbuf._check_wire_format(self.wire_format)
        if self.overlap not in OVERLAP_MODES:
            raise ValueError(f"unknown overlap mode {self.overlap!r}; "
                             f"expected one of {OVERLAP_MODES}")
        if self.exchange_impl not in EXCHANGE_IMPLS:
            raise ValueError(f"unknown exchange_impl "
                             f"{self.exchange_impl!r}; "
                             f"expected one of {EXCHANGE_IMPLS}")
        if self.wire_format == "int8" and self.exchange_impl == "per_leaf":
            raise ValueError("int8 wire format requires the fused arena "
                             "exchange (exchange_impl='fused')")

    def wire_format_for(self, *, blocking: bool) -> str:
        """Resolve the wire tier of a global exchange: the explicit
        `wire_format` if set, else bf16/f32 from the per-phase flag."""
        if self.wire_format is not None:
            return self.wire_format
        flag = self.compress_blocking if blocking \
            else self.compress_nonblocking
        return "bf16" if flag else "f32"


# -- replica-axis helpers ----------------------------------------------------

def _scope(name: str):
    """Decorator: trace the function under `jax.named_scope(name)`, so the
    operations it adds carry `name` in their op_name metadata and a
    profile of the compiled program attributes their device time to it.
    Metadata only: the compiled code is the same."""
    def deco(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped
    return deco


def replicate_params(params, n_replicas: int):
    return jax.tree.map(
        lambda p: jnp.broadcast_to(p[None], (n_replicas,) + p.shape), params)


def dereplicate_params(params, index: int = 0):
    return jax.tree.map(lambda p: p[index], params)


def _wire_format_from(wire_dtype, wire_format) -> str:
    """Back-compat shim: map the legacy `wire_dtype` argument (None /
    jnp.bfloat16) onto the wire-format tiers."""
    if wire_format is not None:
        return flatbuf._check_wire_format(wire_format)
    if wire_dtype is None:
        return "f32"
    if jnp.dtype(wire_dtype) == jnp.dtype(jnp.bfloat16):
        return "bf16"
    if jnp.dtype(wire_dtype) == jnp.dtype(jnp.float32):
        return "f32"
    raise ValueError(f"unsupported wire_dtype {wire_dtype!r}; use "
                     f"wire_format={flatbuf.WIRE_FORMATS}")


def _arena_mean(arena, wire_format: str, *, int8_block: int,
                use_kernels: bool, mask=None, deterministic: bool = False):
    """Mean over the leading replica axis of one arena, kept as a (1, N)
    buffer (the caller broadcasts per leaf after unpacking — one full-size
    materialization instead of two). Exactly one axis-0 reduction per
    arena — the op that lowers to the cross-pod (DCN) all-reduce on the
    production mesh.

    `mask` (a normalized membership tuple, see
    `flatbuf.normalize_membership`) makes the mean membership-weighted:
    dropped replicas' rows are zeroed before the reduce and the divisor is
    the active count — still one collective, the elastic-membership
    contract (tests/test_resilience.py)."""
    if not jnp.issubdtype(arena.dtype, jnp.floating):
        # integer leaves cross the wire at their own dtype; the mean is
        # computed in f32 and rounded back (an int-dtype reduce would
        # truncate the 1/R scale to zero)
        w = arena.astype(jnp.float32)
        return jnp.round(flatbuf.masked_axis0_mean(
            w, mask, deterministic)).astype(arena.dtype)
    if wire_format == "int8":
        # each replica quantizes its arena (int8 + per-block scales is what
        # a real DCN transfer would carry); the mean runs over the
        # dequantized values in f32. Round-to-nearest (no rng_key): the
        # step variants are statically specialized and take no RNG, so the
        # unbiased stochastic tier stays a codec/kernel-API option.
        deq = flatbuf.wire_roundtrip(arena, "int8", int8_block=int8_block,
                                     use_kernels=use_kernels)
        return flatbuf.masked_axis0_mean(
            deq, mask, deterministic).astype(arena.dtype)
    # Pin the reduction computation dtype by reducing the wire-cast arena
    # directly (flatbuf.masked_axis0_mean uses lax.reduce): both jnp.mean
    # and jnp.sum(dtype=...) silently upcast bf16 accumulation to f32,
    # which puts f32 on the cross-pod wire (verified in HLO).
    w = (flatbuf.encode_wire(arena, "bf16", use_kernels=use_kernels)
         if wire_format == "bf16" else arena)
    return flatbuf.masked_axis0_mean(w, mask,
                                     deterministic).astype(arena.dtype)


def _leaf_mean(x, wire_dtype, mask, deterministic: bool):
    """Mean over the leading replica axis of one leaf, broadcast back to
    its shape: `_arena_mean`'s arithmetic on one leaf. A floating leaf is
    reduced in `wire_dtype` (None: its own dtype) with the scale applied
    in that dtype; any other leaf in f32 and rounded back, as the arena
    rounds it (an int-dtype reduce would truncate the 1/R scale to 0)."""
    if jnp.issubdtype(x.dtype, jnp.floating):
        m = flatbuf.masked_axis0_mean(x.astype(wire_dtype or x.dtype), mask,
                                      deterministic)
    else:
        m = jnp.round(flatbuf.masked_axis0_mean(x.astype(jnp.float32), mask,
                                                deterministic))
    return jnp.broadcast_to(m.astype(x.dtype), x.shape)


def replica_mean_per_leaf(tree, wire_dtype=None, mask=None,
                          deterministic: bool = False):
    """Per-leaf exchange: one axis-0 reduction PER LEAF, bit-identical to
    the arena mean for the f32/bf16 wire. `replica_mean` takes it where the
    replica axis never leaves the device (no collective to coalesce, so
    packing the arena is pure copying) and for `impl="per_leaf"`, the
    equivalence oracle and microbenchmark baseline of the fused path.
    `mask` applies the same membership weighting as the fused path."""
    return jax.tree.map(
        lambda x: _leaf_mean(x, wire_dtype, mask, deterministic), tree)


def leafwise_exchange(wire_format, *, impl: str, use_kernels: bool,
                      device_local: bool) -> bool:
    """Whether `replica_mean` takes the mean leaf by leaf rather than over
    the packed arena: for `impl="per_leaf"`, and where the replica axis
    lies in one program on one device (`device_local`), except for the
    int8 wire (its per-block scales cross leaf boundaries) and the Pallas
    exchange kernels (which run over the arena). `wire_format` None is
    the per-phase f32/bf16 choice."""
    return impl == "per_leaf" or (device_local and wire_format != "int8"
                                  and not use_kernels)


def replica_mean(tree, wire_dtype=None, *, wire_format=None,
                 impl: str = "fused", int8_block: int = 256,
                 use_kernels: bool = False, mask=None,
                 deterministic: bool = False, device_local: bool = False):
    """Mean over the leading replica axis, broadcast back.

    Default path packs the pytree into one contiguous arena per dtype
    (core/flatbuf.py) so the whole exchange is ONE cross-replica reduction
    regardless of leaf count; `wire_format` ("f32" | "bf16" | "int8")
    selects the transfer tier. `impl="per_leaf"` restores the legacy
    one-collective-per-leaf reference path. `wire_dtype` is the legacy
    spelling (None = uncompressed, jnp.bfloat16 = 16-bit packaging).
    `mask` (normalized membership tuple, or None = all active) restricts
    the mean to active replicas — the elastic-membership exchange.

    `device_local=True` says the replica axis lies in one program on one
    device, so no collective can be coalesced: the mean is then taken
    leaf by leaf (`leafwise_exchange`), bit-identical to the arena's,
    without the arena's pack, unpack and zero-filled (R, N) buffer."""
    wf = _wire_format_from(wire_dtype, wire_format)
    if impl == "per_leaf" and wf == "int8":
        raise ValueError("int8 wire format requires the fused arena "
                         "exchange (impl='fused')")
    if leafwise_exchange(wf, impl=impl, use_kernels=use_kernels,
                         device_local=device_local):
        return replica_mean_per_leaf(
            tree, jnp.bfloat16 if wf == "bf16" else None, mask=mask,
            deterministic=deterministic)
    layout = flatbuf.build_layout(tree, batch_dims=1)
    arenas = flatbuf.pack(tree, layout)
    out = {k: _arena_mean(a, wf, int8_block=int8_block,
                          use_kernels=use_kernels, mask=mask,
                          deterministic=deterministic)
           for k, a in arenas.items()}
    # unpack the (1, N) means, then broadcast per leaf: the broadcast fuses
    # into each leaf's consumer instead of materializing a second full-size
    # arena before slicing
    mean_tree = flatbuf.unpack(out, layout)
    r = layout.batch_shape[0]
    return jax.tree.map(
        lambda m: jnp.broadcast_to(m, (r,) + m.shape[1:]), mean_tree)


def _arena_group_mean(arena, group_size: int, mask=None,
                      deterministic: bool = False):
    """Mean over contiguous replica groups of size `group_size` on one
    arena: reshape (R, N) -> (R/g, g, N), ONE `lax.reduce` over the group
    axis, broadcast back. On a topology-lowered mesh the group axis is
    exactly the syncing level's mesh axes, so this is one tier-l collective
    per arena — the per-level one-collective contract
    (tests/test_topology.py).

    `mask` (normalized membership tuple) weights the mean by each group's
    active rows; a fully-dead group divides by 1 (its rows are frozen
    ghosts that `freeze_inactive` pins anyway)."""
    r = arena.shape[0]
    if group_size == r:
        return jnp.broadcast_to(
            flatbuf.masked_axis0_mean(arena, mask, deterministic),
            arena.shape)
    if r % group_size:
        raise ValueError(f"replica axis {r} not divisible by group size "
                         f"{group_size}")
    g, n_groups = group_size, r // group_size
    w = arena if mask is None else arena * flatbuf.membership_col(
        mask, arena.dtype, arena.ndim)
    wr = jnp.reshape(w, (n_groups, g) + arena.shape[1:])
    if deterministic:
        # same chain formulation as flatbuf.chain_axis0_sum, over the
        # group axis: order-fixed adds, transport-invariant result
        s = wr[:, 0]
        for i in range(1, g):
            s = s + wr[:, i]
    else:
        s = jax.lax.reduce(wr, jnp.zeros((), arena.dtype), jax.lax.add, (1,))
    if mask is None:
        inv = jnp.asarray(1.0 / g, arena.dtype)
    else:
        counts = [max(1.0, sum(mask[i * g:(i + 1) * g]))
                  for i in range(n_groups)]
        inv = jnp.asarray([1.0 / c for c in counts], arena.dtype).reshape(
            (n_groups,) + (1,) * (arena.ndim - 1))
    m = s * inv
    return jnp.reshape(
        jnp.broadcast_to(m[:, None], (n_groups, g) + arena.shape[1:]),
        arena.shape)


def normalize_group_perm(perm, n_replicas: int):
    """Validate and canonicalize a replica regrouping permutation: a tuple
    permutation of ``range(n_replicas)`` mapping *group slot* -> *replica
    index* (slot i holds replica perm[i], so consecutive slots share an
    inner group). The identity normalizes to None — the unpermuted HLO —
    so callers can compare against the fast path cheaply."""
    if perm is None:
        return None
    perm = tuple(int(i) for i in perm)
    if sorted(perm) != list(range(n_replicas)):
        raise ValueError(f"group permutation {perm!r} is not a permutation "
                         f"of range({n_replicas})")
    return None if perm == tuple(range(n_replicas)) else perm


def _permuted_group_mean(arena, group_size: int, mask, deterministic: bool,
                         perm):
    """`_arena_group_mean` under a replica regrouping: gather the rows into
    slot order, group-mean contiguous slots, scatter back to replica order.
    `perm` is static, so the gathers compile to fixed-index slices that XLA
    fuses into the reduction; mask weights travel with their rows. A
    whole-world group is permutation-invariant, so it skips the gathers."""
    if perm is None or group_size == arena.shape[0]:
        return _arena_group_mean(arena, group_size, mask, deterministic)
    idx = jnp.asarray(perm, dtype=jnp.int32)
    inv = [0] * len(perm)
    for slot, rep in enumerate(perm):
        inv[rep] = slot
    pmask = None if mask is None else tuple(mask[i] for i in perm)
    gm = _arena_group_mean(jnp.take(arena, idx, axis=0), group_size,
                           pmask, deterministic)
    return jnp.take(gm, jnp.asarray(inv, dtype=jnp.int32), axis=0)


@_scope("repro.exchange.level")
def level_group_mean(tree, group_size: int, *, wire_format: str = "f32",
                     use_kernels: bool = False, mask=None,
                     deterministic: bool = False, perm=None):
    """Synchronous parameter average over contiguous replica groups of
    `group_size` — the sync primitive of one intermediate topology level
    (repro/topo: group_size = prod of replica-level fanouts up to the
    syncing level, so each group is the set of replicas inside one unit of
    that level; inner levels vary fastest in the replica index).

    Runs on the fused flat-buffer arenas, one group reduction per arena
    regardless of leaf count. `wire_format` selects the tier-l transfer
    dtype ("f32" default — intermediate links are fast; "bf16" for the
    paper-style 16-bit packaging; int8 is outermost-only). `group_size ==
    R` degenerates to the full replica mean (= `replica_mean`).

    `perm` (see `normalize_group_perm`) regroups the replicas before the
    mean: slot order replaces replica order, so which replicas share a
    group becomes a static schedule choice — the straggler-aware
    reshuffle knob (repro.topo.probe.skew_permutation). Every group mean
    preserves its group's sum and the groups partition the rows, so the
    exact global mean is invariant under ANY permutation
    (tests/test_tuning.py pins this as a hypothesis property)."""
    if wire_format not in ("f32", "bf16"):
        raise ValueError("level_group_mean supports wire_format 'f32' | "
                         f"'bf16', got {wire_format!r} (the int8 tier is "
                         "for the outermost exchange)")
    layout = flatbuf.build_layout(tree, batch_dims=1)
    arenas = flatbuf.pack(tree, layout)
    perm = normalize_group_perm(perm, layout.batch_shape[0])
    out = {}
    for k, a in arenas.items():
        if not jnp.issubdtype(a.dtype, jnp.floating):
            w = a.astype(jnp.float32)
            out[k] = jnp.round(_permuted_group_mean(
                w, group_size, mask, deterministic, perm)).astype(a.dtype)
            continue
        w = (flatbuf.encode_wire(a, "bf16", use_kernels=use_kernels)
             if wire_format == "bf16" else a)
        out[k] = _permuted_group_mean(w, group_size, mask,
                                      deterministic, perm).astype(a.dtype)
    return flatbuf.unpack(out, layout)


def replica_divergence(params) -> jnp.ndarray:
    """Max abs deviation of any replica from the replica mean (diagnostic)."""
    def leaf(x):
        x = x.astype(jnp.float32)
        return jnp.max(jnp.abs(x - x.mean(axis=0, keepdims=True)))
    return functools.reduce(jnp.maximum,
                            [leaf(x) for x in jax.tree.leaves(params)])


# -- elastic membership --------------------------------------------------------

def freeze_inactive(new_tree, old_tree, mask):
    """Select per replica row: active rows advance to `new_tree`, dropped
    rows keep `old_tree`. A dropped replica's row is a ghost in the SPMD
    emulation (the real node is gone); freezing it keeps the ghost from
    drifting so a later rejoin re-seed is the only thing that writes it.
    mask=None (all active) is the identity."""
    if mask is None:
        return new_tree
    keep = jnp.asarray([m != 0.0 for m in mask])

    def leaf(n, o):
        col = keep.reshape((len(mask),) + (1,) * (n.ndim - 1))
        return jnp.where(col, n, o)

    return jax.tree.map(leaf, new_tree, old_tree)


# -- DASO primitive operations ------------------------------------------------

@_scope("repro.exchange.send")
def global_send(params, *, compress: bool = False, wire_format=None,
                impl: str = "fused", int8_block: int = 256,
                use_kernels: bool = False, mask=None,
                deterministic: bool = False, device_local: bool = False):
    """Snapshot + start global exchange: returns the in-flight buffer
    (replica mean of current params, one copy per replica). The wire tier
    comes from `wire_format` (or legacy compress=True -> bf16,
    beyond-paper for the non-blocking path, see DasoConfig). `mask`
    restricts the mean to active replicas (elastic membership);
    `device_local` as in `replica_mean`."""
    wf = wire_format or ("bf16" if compress else "f32")
    return replica_mean(params, wire_format=wf, impl=impl,
                        int8_block=int8_block, use_kernels=use_kernels,
                        mask=mask, deterministic=deterministic,
                        device_local=device_local)


def global_receive_per_leaf(params, inflight, *, staleness: int,
                            global_world: int, extra_staleness: int = 0):
    """Legacy per-leaf Eq. (1) merge (one fused-multiply chain per leaf);
    equivalence oracle for the fused arena merge. `extra_staleness` adds
    the overlap executor's one-cycle buffer age to S (0 = pre-overlap
    math, bit-exact)."""
    s2 = jnp.asarray(2.0 * (staleness + extra_staleness), jnp.float32)
    p_ = jnp.asarray(float(global_world), jnp.float32)
    denom = s2 + p_

    def leaf(x_local, x_stale):
        merged = (s2 * x_local.astype(jnp.float32)
                  + p_ * x_stale.astype(jnp.float32)) / denom
        return merged.astype(x_local.dtype)

    return jax.tree.map(leaf, params, inflight)


@_scope("repro.exchange.receive")
def global_receive(params, inflight, *, staleness: int, global_world,
                   impl: str = "fused", use_kernels: bool = False,
                   mask=None, extra_staleness: int = 0):
    """Paper Eq. (1): weighted merge of stale global average with current
    local params. staleness S = batches waited; global_world P — a float
    under elastic membership (the effective P of the surviving world,
    `global_world * n_active / n_replicas`), so the merge weighting tracks
    dynamic membership. Dropped replicas' rows stay frozen (`mask`).
    `extra_staleness` is the overlap executor's one-cycle buffer age — it
    adds to S in the weighting (the stale buffer really is that much
    older); 0 keeps the pre-overlap merge bit-exact.

    The merge has no collective, so in jnp-land XLA already fuses the
    leaf-wise multiply-add chains into one elementwise pass — packing an
    arena would only add two copies. With `use_kernels=True` the merge
    runs as ONE Pallas `eq1_merge` program over the packed arena (the
    TPU-kernel tier, where a single contiguous launch is the point)."""
    if impl == "per_leaf":
        merged = global_receive_per_leaf(params, inflight,
                                         staleness=staleness,
                                         global_world=global_world,
                                         extra_staleness=extra_staleness)
        return freeze_inactive(merged, params, mask)
    from repro.kernels.ref import eq1_merge_ref
    if not use_kernels:
        merged = jax.tree.map(
            lambda a, b: eq1_merge_ref(a, b, staleness=staleness,
                                       global_world=global_world,
                                       extra_staleness=extra_staleness),
            params, inflight)
        return freeze_inactive(merged, params, mask)
    from repro.kernels.ops import eq1_merge
    layout = flatbuf.build_layout(params, batch_dims=1)
    locals_ = flatbuf.pack(params, layout)
    stales = flatbuf.pack(inflight, layout)
    out = {k: (eq1_merge(a, stales[k], staleness=staleness,
                         global_world=global_world,
                         extra_staleness=extra_staleness)
               if jnp.issubdtype(a.dtype, jnp.floating) else
               eq1_merge_ref(a, stales[k], staleness=staleness,
                             global_world=global_world,
                             extra_staleness=extra_staleness))
           for k, a in locals_.items()}
    return freeze_inactive(flatbuf.unpack(out, layout), params, mask)


@_scope("repro.exchange.blocking")
def blocking_sync(params, *, compress: bool = True, wire_format=None,
                  impl: str = "fused", int8_block: int = 256,
                  use_kernels: bool = False, mask=None,
                  deterministic: bool = False, device_local: bool = False):
    """Synchronous global average (warm-up / cool-down), with the paper's
    16-bit transfer compression (or the tier in `wire_format`). `mask`
    restricts the average to active replicas and freezes dropped rows;
    `device_local` as in `replica_mean`."""
    wf = wire_format or ("bf16" if compress else "f32")
    synced = replica_mean(params, wire_format=wf, impl=impl,
                          int8_block=int8_block, use_kernels=use_kernels,
                          mask=mask, deterministic=deterministic,
                          device_local=device_local)
    return freeze_inactive(synced, params, mask)


# -- assembled train step ------------------------------------------------------

def microbatched_value_and_grad(loss_fn: Callable, n_micro: int):
    """Gradient accumulation: split the batch along its leading dim into
    n_micro chunks and lax.scan the fwd+bwd over them. Cuts the live
    activation/residual footprint ~n_micro-fold (beyond-paper memory
    optimization, EXPERIMENTS.md §Perf)."""
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    if n_micro <= 1:
        return grad_fn

    def fn(params, batch):
        micro = jax.tree.map(
            lambda x: x.reshape((n_micro, x.shape[0] // n_micro)
                                + x.shape[1:]), batch)

        def body(carry, mb):
            loss_acc, aux_acc, g_acc = carry
            (loss, aux), g = grad_fn(params, mb)
            g_acc = jax.tree.map(jnp.add, g_acc, g)
            aux_acc = jax.tree.map(jnp.add, aux_acc, aux)
            return (loss_acc + loss, aux_acc, g_acc), None

        (loss0, aux0), g0 = jax.eval_shape(grad_fn, params,
                                           jax.tree.map(lambda x: x[0],
                                                        micro))
        zeros = lambda t: jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), t)
        (loss, aux, grads), _ = jax.lax.scan(
            body, (jnp.zeros(loss0.shape, loss0.dtype), zeros(aux0),
                   zeros(g0)), micro)
        inv = 1.0 / n_micro
        scale = lambda t: jax.tree.map(
            lambda x: (x * inv).astype(x.dtype) if jnp.issubdtype(
                x.dtype, jnp.floating) else x, t)
        return (loss * inv, scale(aux)), scale(grads)

    return fn


def local_step(loss_fn: Callable, optimizer: Optimizer,
               spmd_axis_name: Optional[str] = None, n_micro: int = 1):
    """Returns step(params_R, opt_R, batch_R, lr) -> (params, opt, metrics).
    loss_fn(params, batch) -> (loss, aux). vmapped over the replica axis.

    On a mesh, pass spmd_axis_name="pod": sharding constraints inside the
    model then keep the replica dim pod-sharded (plain vmap would mark it
    replicated and force cross-pod all-gathers of every constrained
    activation — verified in the HLO audit, see EXPERIMENTS.md)."""
    grad_fn = microbatched_value_and_grad(loss_fn, n_micro)

    def one(params, opt_state, batch, lr):
        with jax.named_scope("repro.fwd_bwd"):
            (loss, aux), grads = grad_fn(params, batch)
        with jax.named_scope("repro.optimizer"):
            new_params, new_opt = optimizer.update(grads, opt_state, params,
                                                   lr)
        return new_params, new_opt, loss, aux

    return jax.vmap(one, in_axes=(0, 0, 0, None),
                    spmd_axis_name=spmd_axis_name)


MODES = ("local", "send", "receive", "send_receive", "blocking", "hard_avg")

# Outermost-level actions of the overlap (double-buffered) schedule. The
# ov_* pair replaces send/receive in the cycling phase when
# DasoConfig.overlap == "one_cycle":
#   ov_start  local step + snapshot pending <- params (no exchange yet;
#             first cycling step, and the restart after any blocking phase)
#   ov_sync   local step + inflight <- mean(pending_old) [the one outer
#             all-reduce] + params <- Eq. (1) merge + pending <- params
OV_MODES = ("local", "ov_start", "ov_sync", "blocking")


def _cross_replica_loss(cfg: DasoConfig, mask, n_active: int,
                        loss_r, *, axis: int = 0):
    """The scalar training loss the plateau controller consumes: the mean
    of the per-replica losses over the ACTIVE replicas, reduced along
    `axis` (the replica axis). Shared by the in-step metric block of
    `daso_train_step` and the overlap merge program (where the reduction
    is deferred out of the compute program — it is a cross-process
    collective on a process-sharded replica axis, and the overlap contract
    requires the compute program to be collective-free). Deterministic
    mode uses the same order-fixed chain adds in both places, so deferring
    the reduction is bit-exact."""
    det = cfg.deterministic_reduce
    w_l = (jnp.ones((cfg.n_replicas,), loss_r.dtype) if mask is None
           else jnp.asarray(mask, loss_r.dtype))
    if axis != 0:
        loss_r = jnp.moveaxis(loss_r, axis, 0)
    shape = (cfg.n_replicas,) + (1,) * (loss_r.ndim - 1)
    weighted = loss_r * w_l.reshape(shape)
    if det:
        return flatbuf.chain_axis0_sum(weighted) / n_active
    if mask is None:
        return jnp.mean(loss_r, axis=0)
    return jnp.sum(weighted, axis=0) / n_active


def daso_train_step(loss_fn: Callable, optimizer: Optimizer, cfg: DasoConfig,
                    *, mode: str, staleness: int = 1,
                    spmd_axis_name: Optional[str] = None, n_micro: int = 1,
                    membership=None,
                    inner_syncs: Tuple[Tuple[str, int], ...] = (),
                    group_perm=None, device_local: bool = False):
    """Build one statically-specialized DASO step function.

    step(params_R, opt_R, inflight, batch_R, lr)
        -> (params_R, opt_R, inflight, metrics)

    `mode` is the outermost level's action (one of MODES). `inner_syncs`
    is the step's intermediate-level phase vector: `(level_name,
    group_size)` pairs, innermost first, for every topology level whose
    period elapses this step — each adds one synchronous
    `level_group_mean` over that level's replica groups, applied after the
    local optimizer step and before the outermost send (so an outer
    exchange always ships tier-synced values). Empty (the default, and
    always for 2-level topologies) adds nothing: the compiled graph is the
    pre-topology one.

    `membership` (optional 0/1 mask over the R replicas) bakes elastic
    membership into the compiled step: exchanges at every level become
    membership-weighted means over the active set, Eq. (1) runs with the
    effective world size P_eff = P * n_active / R, dropped replicas' rows
    are frozen, and the reported loss averages active replicas only. The
    mask is a *static* constant — a membership change compiles new step
    variants (the executor invalidates its cycle cache, see
    resilience/supervisor.py), which keeps the fixed-membership HLO
    bit-identical to the non-elastic build.

    `group_perm` (normalize_group_perm) statically regroups the replicas
    for every inner-level sync — the straggler-aware reshuffle. Like the
    membership mask it is baked into the compiled step; changing it means
    new variants (DasoStrategy.set_group_permutation).

    `device_local` (static, `replica_mean`) says the replica axis lies in
    this program on one device: the outermost replica means (send,
    blocking, hard_avg) are then taken leaf by leaf instead of over the
    packed arena (DasoStrategy.set_device_local)."""
    assert mode in MODES, mode
    lstep = local_step(loss_fn, optimizer, spmd_axis_name=spmd_axis_name,
                       n_micro=n_micro)

    impl, kern, blk = (cfg.exchange_impl, cfg.exchange_kernels,
                       cfg.int8_block)
    det = cfg.deterministic_reduce
    perm = normalize_group_perm(group_perm, cfg.n_replicas)
    mask = flatbuf.normalize_membership(membership, cfg.n_replicas)
    n_active = cfg.n_replicas if mask is None else int(sum(mask))
    p_eff = (cfg.global_world if mask is None
             else cfg.global_world * n_active / cfg.n_replicas)
    for _name, g in inner_syncs:
        if not 1 < g <= cfg.n_replicas:
            raise ValueError(f"inner sync {_name!r}: group size {g} outside "
                             f"2..{cfg.n_replicas}")

    def step(params, opt_state, inflight, batch, lr):
        if mode in ("receive", "send_receive"):
            params = global_receive(params, inflight,
                                    staleness=staleness,
                                    global_world=p_eff,
                                    impl=impl, use_kernels=kern, mask=mask)
        new_p, new_o, loss_r, aux_r = lstep(params, opt_state, batch, lr)
        if mask is not None:
            new_p = freeze_inactive(new_p, params, mask)
            new_o = freeze_inactive(new_o, opt_state, mask)
        params, opt_state = new_p, new_o
        for _name, g in inner_syncs:
            params = freeze_inactive(
                level_group_mean(params, g, use_kernels=kern, mask=mask,
                                 deterministic=det, perm=perm),
                params, mask)
        if mode in ("send", "send_receive"):
            inflight = global_send(
                params, wire_format=cfg.wire_format_for(blocking=False),
                impl=impl, int8_block=blk, use_kernels=kern, mask=mask,
                deterministic=det, device_local=device_local)
        elif mode == "blocking":
            params = blocking_sync(
                params, wire_format=cfg.wire_format_for(blocking=True),
                impl=impl, int8_block=blk, use_kernels=kern, mask=mask,
                deterministic=det, device_local=device_local)
        elif mode == "hard_avg":
            with jax.named_scope("repro.exchange.blocking"):
                params = freeze_inactive(
                    replica_mean(params, impl=impl, mask=mask,
                                 deterministic=det,
                                 device_local=device_local), params, mask)
        # the reported loss feeds the plateau controller on the host, so
        # it needs the same transport invariance as the exchanges
        loss = _cross_replica_loss(cfg, mask, n_active, loss_r)
        metrics = {"loss": loss, "loss_per_replica": loss_r}
        for k, v in aux_r.items():
            if isinstance(v, jnp.ndarray) and v.ndim <= 1:
                if (mask is not None and v.ndim == 1
                        and v.shape[0] == cfg.n_replicas):
                    metrics[k] = jnp.sum(
                        v * jnp.asarray(mask, v.dtype)) / n_active
                else:
                    metrics[k] = jnp.mean(v)
        return params, opt_state, inflight, metrics

    return step


def daso_overlap_step(loss_fn: Callable, optimizer: Optimizer,
                      cfg: DasoConfig, *, mode: str, staleness: int = 1,
                      extra_staleness: int = 0,
                      spmd_axis_name: Optional[str] = None, n_micro: int = 1,
                      membership=None,
                      inner_syncs: Tuple[Tuple[str, int], ...] = (),
                      group_perm=None, device_local: bool = False):
    """Build one step variant of the double-buffered overlap schedule
    (DasoConfig.overlap == "one_cycle"). The carry grows a fourth slot —
    the `pending` snapshot arena awaiting its exchange:

    step(params_R, opt_R, inflight, pending, batch_R, lr)
        -> (params_R, opt_R, inflight, pending, metrics)

    `mode` is one of OV_MODES. Semantics (macro-executor order — the
    compiled overlap dispatch runs the same ops, just split across the
    exchange / compute / merge programs so the exchange can be in flight
    during the local steps):

      local     local optimizer step; both buffers pass through
      ov_start  local step, then pending <- params (snapshot only — the
                first cycling step has nothing in flight to merge)
      ov_sync   local step, then inflight <- mean(pending_old) [the ONE
                outer all-reduce, over the snapshot taken at the previous
                ov step], params <- Eq. (1) merge with S = staleness +
                extra_staleness (the snapshot's true age in batches),
                pending <- merged params
      blocking  local step + synchronous global average (warm-up /
                cool-down; buffers pass through — the next cycling phase
                restarts with ov_start, so a dangling snapshot is never
                merged)

    The merge lands AFTER the step's local update (off-mode `receive`
    merges before it): the exchange result arrives at the cycle boundary,
    which is exactly when the macro executor joins the in-flight
    collective with the computed params. `device_local` as in
    `daso_train_step`."""
    assert mode in OV_MODES, mode
    lstep = local_step(loss_fn, optimizer, spmd_axis_name=spmd_axis_name,
                       n_micro=n_micro)
    impl, kern, blk = (cfg.exchange_impl, cfg.exchange_kernels,
                       cfg.int8_block)
    det = cfg.deterministic_reduce
    perm = normalize_group_perm(group_perm, cfg.n_replicas)
    mask = flatbuf.normalize_membership(membership, cfg.n_replicas)
    n_active = cfg.n_replicas if mask is None else int(sum(mask))
    p_eff = (cfg.global_world if mask is None
             else cfg.global_world * n_active / cfg.n_replicas)
    for _name, g in inner_syncs:
        if not 1 < g <= cfg.n_replicas:
            raise ValueError(f"inner sync {_name!r}: group size {g} outside "
                             f"2..{cfg.n_replicas}")

    def step(params, opt_state, inflight, pending, batch, lr):
        new_p, new_o, loss_r, aux_r = lstep(params, opt_state, batch, lr)
        if mask is not None:
            new_p = freeze_inactive(new_p, params, mask)
            new_o = freeze_inactive(new_o, opt_state, mask)
        params, opt_state = new_p, new_o
        for _name, g in inner_syncs:
            params = freeze_inactive(
                level_group_mean(params, g, use_kernels=kern, mask=mask,
                                 deterministic=det, perm=perm),
                params, mask)
        if mode == "ov_start":
            pending = params
        elif mode == "ov_sync":
            inflight = global_send(
                pending, wire_format=cfg.wire_format_for(blocking=False),
                impl=impl, int8_block=blk, use_kernels=kern, mask=mask,
                deterministic=det, device_local=device_local)
            params = global_receive(params, inflight, staleness=staleness,
                                    extra_staleness=extra_staleness,
                                    global_world=p_eff, impl=impl,
                                    use_kernels=kern, mask=mask)
            pending = params
        elif mode == "blocking":
            params = blocking_sync(
                params, wire_format=cfg.wire_format_for(blocking=True),
                impl=impl, int8_block=blk, use_kernels=kern, mask=mask,
                deterministic=det, device_local=device_local)
        loss = _cross_replica_loss(cfg, mask, n_active, loss_r)
        metrics = {"loss": loss, "loss_per_replica": loss_r}
        for k, v in aux_r.items():
            if isinstance(v, jnp.ndarray) and v.ndim <= 1:
                if (mask is not None and v.ndim == 1
                        and v.shape[0] == cfg.n_replicas):
                    metrics[k] = jnp.sum(
                        v * jnp.asarray(mask, v.dtype)) / n_active
                else:
                    metrics[k] = jnp.mean(v)
        return params, opt_state, inflight, pending, metrics

    return step


def daso_overlap_compute_step(loss_fn: Callable, optimizer: Optimizer,
                              cfg: DasoConfig, *,
                              spmd_axis_name: Optional[str] = None,
                              n_micro: int = 1, membership=None,
                              inner_syncs: Tuple[Tuple[str, int],
                                                 ...] = (),
                              group_perm=None):
    """The compute-program half of one overlap-dispatched macro-cycle:

    step(params_R, opt_R, batch_R, lr) -> (params_R, opt_R, metrics)

    A plain local step (plus any inner-level group syncs) that is — by
    construction — free of collectives over the OUTER (cross-process)
    replica axes: the scalar-loss reduction of `daso_train_step` is a
    cross-replica reduce, so it is deferred to the merge program
    (`_cross_replica_loss` over the stacked per-replica losses, bit-exact
    in deterministic mode). That is the property that makes dispatching
    this program concurrently with the in-flight gloo exchange safe on the
    multi-process runtime (launch/distributed.py, dispatch="overlap"):
    at most one collective-bearing program is ever in flight, so the PR-5
    shared-TCP-pair interleaving failure cannot occur. Aux metrics are
    dropped here for the same reason (their means reduce over the replica
    axis). Inner-level syncs stay: the overlap dispatch validator requires
    them to be process-local (launch.distributed.check_overlap_topology),
    where they lower to in-process collectives gloo never sees."""
    lstep = local_step(loss_fn, optimizer, spmd_axis_name=spmd_axis_name,
                       n_micro=n_micro)
    kern = cfg.exchange_kernels
    det = cfg.deterministic_reduce
    perm = normalize_group_perm(group_perm, cfg.n_replicas)
    mask = flatbuf.normalize_membership(membership, cfg.n_replicas)

    def step(params, opt_state, batch, lr):
        new_p, new_o, loss_r, _aux_r = lstep(params, opt_state, batch, lr)
        if mask is not None:
            new_p = freeze_inactive(new_p, params, mask)
            new_o = freeze_inactive(new_o, opt_state, mask)
        params, opt_state = new_p, new_o
        for _name, g in inner_syncs:
            params = freeze_inactive(
                level_group_mean(params, g, use_kernels=kern, mask=mask,
                                 deterministic=det, perm=perm),
                params, mask)
        return params, opt_state, {"loss_per_replica": loss_r}

    return step


def sync_train_step(loss_fn: Callable, optimizer: Optimizer,
                    n_micro: int = 1):
    """Horovod-analog baseline: flat data parallelism, no replica axis; XLA
    emits the global gradient all-reduce over ("pod","data") every step."""
    grad_fn = microbatched_value_and_grad(loss_fn, n_micro)

    def step(params, opt_state, batch, lr):
        with jax.named_scope("repro.fwd_bwd"):
            (loss, aux), grads = grad_fn(params, batch)
        with jax.named_scope("repro.optimizer"):
            new_params, new_opt = optimizer.update(grads, opt_state, params,
                                                   lr)
        metrics = {"loss": loss}
        for k, v in aux.items():
            if isinstance(v, jnp.ndarray) and v.ndim == 0:
                metrics[k] = v
        return new_params, new_opt, metrics

    return step
