"""Production meshes. TPU v5e target: one pod = 256 chips as (data=16,
model=16); multi-pod adds a leading "pod" axis — in topology terms
(repro/topo) that is the 2-level ``data x pod`` layout, with "pod" the
outermost (DASO-async) replica level. `make_topology_mesh` lowers an
arbitrary N-level `TopologySpec` to a mesh with one axis per level, so
syncs at level l produce collectives spanning exactly that level's axis
(the per-level HLO contract, tests/test_topology.py).

Functions, not module constants: importing this module must never touch
jax device state (smoke tests see 1 CPU device).

`make_mesh` is the one mesh constructor of the repo: every mesh, in the
program, the tests and the benchmarks, is built through it."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """`jax.make_mesh` with every axis of type Auto. The DASO step code is
    written for GSPMD propagation (vmap over a sharded replica axis,
    `with_sharding_constraint`, dynamic-update-slice arena packing), which
    `jax.make_mesh`'s default Explicit axes reject."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(n_pods: int = 2, data: int = 2, model: int = 2):
    """Small mesh for multi-device CPU tests (XLA host platform devices)."""
    return make_mesh((n_pods, data, model), ("pod", "data", "model"))


def make_topology_mesh(spec, model: int = 1, *, devices=None):
    """Lower a `repro.topo.TopologySpec` to a JAX mesh: one axis per
    topology level, outermost level first (major-to-minor device order
    matches the replica-index layout: inner levels vary fastest), plus a
    trailing "model" axis for tensor parallelism inside level 0.

    The replica axis of the training arrays shards over ALL replica-level
    axes at once (``PartitionSpec((outer_name, ..., inner_name))``), which
    is what makes a level-l group mean lower to an all-reduce whose
    replica groups span exactly the axes of levels <= l.

    Under `jax.distributed` the same call on every process builds the same
    *global* mesh: `jax.devices()` orders devices process-major, and the
    mesh axes are outermost-level-first, so each process's contiguous
    device block lands on a contiguous replica range — the subtree that
    `process_node_paths` reports it as owning.

    `devices` defaults to all of JAX's devices; a test passes described
    ones to compile for a chip that is not attached."""
    shape = spec.mesh_shape() + (model,)
    axes = spec.mesh_axis_names() + ("model",)
    return make_mesh(shape, axes, devices=devices)


# -- process <-> topology partitioning (multi-process runtime) ----------------
#
# Pure host-side functions — no jax device state — so the partition contract
# is testable without spawning processes (tests/test_process_mesh.py).

def replica_unit_sizes(spec):
    """Replicas per unit of each replica level, innermost first:
    ``{level_name: unit_size}``. A unit of the finest replica level is one
    replica; a unit of level l contains the product of the replica-level
    fanouts below it."""
    sizes, u = {}, 1
    for lvl in spec.replica_levels:
        sizes[lvl.name] = u
        u *= lvl.fanout
    return sizes


def validate_process_topology(spec, num_processes: int) -> int:
    """Check that `num_processes` coordinator-connected processes can carve
    the topology into equal per-process subtrees. Returns the number of
    devices each process must host (``spec.world // num_processes``).

    Raises ValueError with a precise reason when the split is impossible:
    the world not dividing evenly, a replica straddling two processes, or
    a process block cutting through a topology level's units."""
    if num_processes < 1:
        raise ValueError(f"num_processes must be >= 1, got {num_processes}")
    if spec.world % num_processes:
        raise ValueError(
            f"topology world {spec.world} ({spec.to_str()}) does not divide "
            f"over {num_processes} processes")
    local = spec.world // num_processes
    if local % spec.local_world:
        raise ValueError(
            f"{num_processes} processes would split a replica: each process "
            f"gets {local} devices but one replica spans "
            f"{spec.local_world} (level {spec.levels[0].name!r} fanout)")
    block = spec.n_replicas // num_processes
    for name, u in replica_unit_sizes(spec).items():
        if block % u and u % block:
            raise ValueError(
                f"process blocks of {block} replicas cut through "
                f"{name!r} units of {u} replicas: {num_processes} processes "
                f"cannot own whole subtrees of {spec.to_str()!r}")
    return local


def process_replica_slice(spec, num_processes: int,
                          process_id: int) -> range:
    """Replica indices owned by `process_id` (contiguous: the mesh lowers
    the replica axis process-major, inner levels varying fastest)."""
    validate_process_topology(spec, num_processes)
    if not 0 <= process_id < num_processes:
        raise ValueError(f"process_id {process_id} outside "
                         f"0..{num_processes - 1}")
    block = spec.n_replicas // num_processes
    return range(process_id * block, (process_id + 1) * block)


def _node_path(spec, level_index: int, replica: int) -> str:
    """Node path ("pod1/host0") of the level-`level_index` unit containing
    `replica`, descending outermost-first as `TopologySpec.replicas_of`
    expects."""
    sizes = replica_unit_sizes(spec)
    segs = []
    for i in range(len(spec.levels) - 1, level_index - 1, -1):
        lvl = spec.levels[i]
        u = sizes[lvl.name]
        idx = (replica // u) % lvl.fanout if i < len(spec.levels) - 1 \
            else replica // u
        segs.append(f"{lvl.name}{idx}")
    return "/".join(segs)


def process_node_paths(spec, num_processes: int, process_id: int):
    """The maximal topology subtrees owned by `process_id`, as node paths
    (`TopologySpec.replicas_of` round-trips them). With processes mapped
    one-to-one onto units of some level this is a single path — the
    process's subtree; coarser splits own several sibling subtrees."""
    rng = process_replica_slice(spec, num_processes, process_id)
    block = len(rng)
    best_i, best_u = 1, 1
    for i, lvl in enumerate(spec.levels[1:], start=1):
        u = replica_unit_sizes(spec)[lvl.name]
        if block % u == 0 and u >= best_u:
            best_i, best_u = i, u
    return tuple(_node_path(spec, best_i, r)
                 for r in range(rng.start, rng.stop, best_u))


def device_node_path(spec, device_index: int) -> str:
    """Topology path of one global device: the finest replica-level node it
    sits in, plus its rank inside that replica's level-0 tier —
    ``"pod1/host0:chip2"``."""
    if not 0 <= device_index < spec.world:
        raise ValueError(f"device {device_index} outside the topology "
                         f"world 0..{spec.world - 1}")
    replica, local = divmod(device_index, spec.local_world)
    return (f"{_node_path(spec, 1, replica)}:"
            f"{spec.levels[0].name}{local}")


# -- hardware constants (TPU v5e) used by the roofline analysis -------------
PEAK_FLOPS_BF16 = 197e12       # per chip
HBM_BW = 819e9                 # bytes/s per chip
ICI_BW = 50e9                  # bytes/s per link (intra-pod)
DCN_BW = 25e9                  # bytes/s per host aggregate (cross-pod)
