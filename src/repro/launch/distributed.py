"""Multi-process distributed runtime (`jax.distributed`).

Everything below this module runs the paper's algorithm as one SPMD program;
what this module adds is the *real* deployment shape: N coordinator-connected
processes (one per host in production; `tools/launch_procs.py` spawns local
CPU-pinned ones for development and CI), each hosting a contiguous block of
the topology's devices, jointly executing that same program over the global
mesh. Three pieces:

  * `DistributedConfig` / `initialize` — `jax.distributed.initialize`
    bootstrap from flags or the ``DASO_COORDINATOR`` / ``DASO_NUM_PROCS`` /
    ``DASO_PROC_ID`` environment (what `tools/launch_procs.py` exports).
    Must run before any JAX device use; `launch/train.py` calls it first.
  * `MeshPlacement` — the placement layer the train loop, both executors,
    and the resilience supervisor thread their arrays through: the
    `TopologySpec` lowered to the global mesh (one axis per level, so
    levels map onto (process, local-device) axes — each process owns
    exactly the subtree `launch.mesh.process_node_paths` reports), carry
    and batch shardings over the replica-level axes, and host gather for
    metrics/checkpoints (only process 0 writes).
  * the SPMD-equivalence contract — because every process runs the same
    deterministic host loop (synthetic data, controller, fault plans are
    all seeded) and the global mesh is identical for any process count, an
    N-process run is bit-exact with the 1-process run of the same spec,
    seed, and fault plan (tests/test_multiprocess.py asserts it on both
    executors, with real subprocesses).

The contract's load-bearing assumption — worth stating because it is the
thing a new backend could break — is that the per-device programs GSPMD
emits depend only on the mesh, never on process boundaries; the only
cross-process difference is collective transport (XLA in-process vs gloo),
which is reduction-order-identical on the CPU backend.
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass
from typing import Optional

import jax
import numpy as np

# the one host-fetchability predicate, shared with the executor's metric
# filter and the checkpoint-save guard
from repro.core.flatbuf import host_fetchable  # noqa: F401  (re-exported)
from repro.launch.mesh import make_topology_mesh, validate_process_topology

ENV_COORDINATOR = "DASO_COORDINATOR"
ENV_NUM_PROCS = "DASO_NUM_PROCS"
ENV_PROC_ID = "DASO_PROC_ID"
ENV_DISPATCH = "DASO_DISPATCH"

DISPATCH_MODES = ("serial", "overlap")

_initialized = False


@dataclass(frozen=True)
class DistributedConfig:
    """Who we are in the process group. `num_processes == 1` means the
    single-process SPMD simulation — same code path, no coordinator.

    `dispatch` picks the executable-dispatch discipline for multi-process
    gloo runs:

      * "serial" (default) — async dispatch disabled; at most one
        executable in flight per process. Safe for every program mix:
        concurrent executables' gloo collectives would interleave on the
        same shared TCP pairs and abort (see `initialize`).
      * "overlap" — async dispatch left ON so the overlap executor can
        keep the exchange program in flight under the compute program.
        Safe ONLY because that executor's dispatch discipline guarantees
        at most one collective-bearing program in flight at a time (the
        compute program is collective-free over the outer axis and the
        merge data-depends on the exchange); `launch/train.py` therefore
        refuses this mode unless the strategy runs with overlap on.
    """
    coordinator: Optional[str] = None     # "host:port"
    num_processes: int = 1
    process_id: int = 0
    dispatch: str = "serial"

    def __post_init__(self):
        if self.dispatch not in DISPATCH_MODES:
            raise ValueError(f"unknown dispatch mode {self.dispatch!r}; "
                             f"expected one of {DISPATCH_MODES}")

    @classmethod
    def from_env(cls, *, coordinator: Optional[str] = None,
                 num_processes: Optional[int] = None,
                 process_id: Optional[int] = None,
                 dispatch: Optional[str] = None) -> "DistributedConfig":
        """Resolve explicit flag values, falling back to the DASO_* env
        vars `tools/launch_procs.py` exports for its children."""
        coord = coordinator or os.environ.get(ENV_COORDINATOR)
        n = num_processes if num_processes is not None else int(
            os.environ.get(ENV_NUM_PROCS, "1"))
        pid = process_id if process_id is not None else int(
            os.environ.get(ENV_PROC_ID, "0"))
        disp = dispatch or os.environ.get(ENV_DISPATCH, "serial")
        if n > 1 and not coord:
            raise ValueError(
                f"{n} processes need a coordinator address "
                f"(--coordinator host:port or ${ENV_COORDINATOR})")
        if not 0 <= pid < n:
            raise ValueError(f"process_id {pid} outside 0..{n - 1}")
        return cls(coordinator=coord, num_processes=n, process_id=pid,
                   dispatch=disp)


# Failure signatures of a transient coordinator connect/bind race: the
# coordinator process losing the port between free_port() and bind (a
# just-torn-down group's socket in TIME_WAIT, or a concurrent test group),
# or clients racing a coordinator that died and is being restarted. Fresh
# attempts resolve these — TIME_WAIT drains and regrouped coordinators come
# back — so `initialize` retries them with exponential backoff. Anything
# not matching fails immediately; a retry must never paper over a real
# failure. (tests/conftest.py used to carry a retry-once wrapper around
# whole subprocess groups for the same races; fixed here at the source.)
CONNECT_RACE_SIGNATURES = (
    "Address already in use",
    "ADDRESS_IN_USE",
    "Failed to bind",
    "Connection reset by peer",
    "coordinator service failed to start",
    "DEADLINE_EXCEEDED",
    "UNAVAILABLE",
)


def _is_connect_race(exc: BaseException) -> bool:
    return any(sig in str(exc) for sig in CONNECT_RACE_SIGNATURES)


def initialize(cfg: DistributedConfig, *, max_attempts: int = 5,
               backoff_s: float = 0.5) -> None:
    """Connect this process to the coordinator (idempotent; no-op for a
    single process). Must be called before anything touches JAX devices —
    the backend is configured here (CPU cross-process collectives run on
    gloo).

    Connect/bind failures matching `CONNECT_RACE_SIGNATURES` are retried
    up to `max_attempts` times with exponential backoff (0.5 s, 1 s, 2 s,
    …): the coordinator port race is transient by construction, and a
    regrouped epoch's workers may connect while the fresh coordinator is
    still coming up. Non-transient errors raise on the first attempt."""
    global _initialized
    if cfg.num_processes <= 1 or _initialized:
        return
    try:
        # gloo is the CPU cross-process transport; newer jaxlibs select it
        # automatically once distributed is initialized
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except AttributeError:
        pass
    if cfg.dispatch == "serial":
        try:
            # async dispatch lets consecutive executables be in flight at
            # once; their gloo collectives then interleave on the same TCP
            # pairs and abort with size-mismatch errors (observed: "op.
            # preamble.length <= op.nbytes" / "connection reset by peer"
            # flakes under load). Serial dispatch pins one collective in
            # flight per process — the same order on every process.
            jax.config.update("jax_cpu_enable_async_dispatch", False)
        except AttributeError:
            pass
    # dispatch == "overlap": async dispatch stays on. The overlap
    # executor's discipline (one collective-bearing program in flight,
    # enforced by construction — see DistributedConfig.dispatch) is what
    # stands in for the serial-dispatch guarantee.
    for attempt in range(max_attempts):
        try:
            jax.distributed.initialize(coordinator_address=cfg.coordinator,
                                       num_processes=cfg.num_processes,
                                       process_id=cfg.process_id)
            break
        except Exception as e:
            if attempt == max_attempts - 1 or not _is_connect_race(e):
                raise
            try:
                # a half-initialized client/service must be torn down
                # before the next attempt re-binds
                jax.distributed.shutdown()
            except Exception:
                pass
            delay = backoff_s * (2 ** attempt)
            print(f"[distributed] initialize attempt {attempt + 1}/"
                  f"{max_attempts} hit a transient connect race ({e}); "
                  f"retrying in {delay:.1f}s")
            time.sleep(delay)
    _initialized = True


def check_overlap_topology(spec, n_procs: int) -> None:
    """Fail fast when a topology cannot run under dispatch="overlap".

    The overlap compute program may carry INNER-level group syncs; those
    are safe concurrently with the in-flight outer exchange only when
    every inner group lies within one process (they then lower to
    in-process collectives gloo never sees). Each process owns a
    contiguous block of R // n_procs replica rows, so an inner level with
    cumulative group size g is process-local iff g divides that block
    evenly. Raises with the offending level spelled out — the actionable
    alternative being dispatch="serial" (correct for every topology,
    just no overlap win)."""
    if n_procs <= 1:
        return
    rows_per_proc, rem = divmod(spec.n_replicas, n_procs)
    if rem:
        return  # validate_process_topology already rejects this split
    for name in spec.inner_names():  # intermediate replica levels
        g = spec.group_size(name)
        if rows_per_proc % g != 0:
            raise ValueError(
                f"dispatch='overlap' needs process-local inner syncs, but "
                f"level {name!r} groups {g} replicas while each of the "
                f"{n_procs} processes holds only {rows_per_proc} "
                f"({spec.to_str()}): a {name!r} group sync would be a "
                f"cross-process gloo collective racing the in-flight "
                f"exchange. Use --dispatch serial for this topology, or "
                f"launch with a process count whose per-process replica "
                f"block is a multiple of {g}.")


def is_coordinator() -> bool:
    return jax.process_index() == 0


def forced_cpu_env(devices: int, base: Optional[dict] = None) -> dict:
    """Environment for a spawned CPU-JAX subprocess, with the JAX-relevant
    variables pinned EXPLICITLY — never inherited — so a local run behaves
    exactly like CI: platform is cpu (a developer's exported
    JAX_PLATFORMS=cuda would silently turn the forced-device-count flag
    into a no-op), and XLA_FLAGS forces `devices` host devices. The single
    definition behind both tests/conftest.py's subprocess helpers and
    tools/launch_procs.py's child environments."""
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    src = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))  # .../src
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _is_jax_array(x) -> bool:
    return isinstance(x, jax.Array)


class MeshPlacement:
    """Array placement for one topology on the global device set.

    Construction validates that the topology fits the process group (world
    == device count, each process an integral subtree) and lowers the spec
    to the global mesh. The same placement object drives single-process
    SPMD runs (the equivalence oracle) and N-process runs — the shardings,
    and therefore the compiled programs, are identical in both.
    """

    def __init__(self, spec, *, mesh=None):
        from jax.sharding import NamedSharding, PartitionSpec

        self.spec = spec
        n_procs = jax.process_count()
        if n_procs > 1:
            validate_process_topology(spec, n_procs)
        # a given mesh (e.g. of described devices, for a compile without
        # the chip) brings its own devices
        n_dev = mesh.devices.size if mesh is not None else jax.device_count()
        if n_dev != spec.world:
            raise ValueError(
                f"topology world {spec.world} ({spec.to_str()}) != global "
                f"device count {n_dev}; launch with "
                f"world/num_processes devices per process "
                f"(tools/launch_procs.py does this)")
        self.mesh = mesh if mesh is not None else make_topology_mesh(spec)
        names = spec.mesh_axis_names()           # outermost first
        self.replica_axes = names[:-1]           # all replica levels
        self.level0_axis = names[-1]             # intra-replica tier
        self._P = PartitionSpec
        self._NS = NamedSharding
        self.replicated = NamedSharding(self.mesh, PartitionSpec())
        # leading replica axis sharded over every replica-level mesh axis
        # at once: level-l group means lower to collectives spanning
        # exactly levels <= l (the per-level HLO contract)
        self.carry_sharding = NamedSharding(
            self.mesh, PartitionSpec(self.replica_axes))
        self._gather = None

    # -- identity ----------------------------------------------------------
    @property
    def is_coordinator(self) -> bool:
        return is_coordinator()

    # -- placement ---------------------------------------------------------
    def _put(self, x, sharding):
        """Build a global array from host data WITHOUT cross-process
        traffic: every process holds the full value (the deterministic
        host loops guarantee they agree), so each can materialize its own
        addressable shards locally. `jax.device_put` would instead run an
        assert-equal broadcast per leaf — a per-transfer collective on its
        own communicator clique, which both costs a round-trip and races
        other gloo traffic."""
        if _is_jax_array(x) and not x.is_fully_addressable:
            return x  # already global (resumed carry re-placed twice)
        host = np.asarray(jax.device_get(x))
        return jax.make_array_from_callback(host.shape, sharding,
                                            lambda idx: host[idx])

    def carry_shardings(self, carry):
        """The sharding of each leaf of a strategy carry: every leaf with a
        leading replica axis shards over the replica-level mesh axes;
        anything else (scalar counters) replicates."""
        R = self.spec.n_replicas
        return jax.tree.map(
            lambda x: (self.carry_sharding
                       if getattr(x, "ndim", 0) >= 1 and x.shape[0] == R
                       else self.replicated), carry)

    def put_carry(self, carry):
        """Place a strategy carry by `carry_shardings`."""
        return jax.tree.map(self._put, carry, self.carry_shardings(carry))

    def constrain_carry(self, carry):
        """Inside a traced program: keep the carry on `carry_shardings`.
        Left to itself GSPMD may return a leaf replicated (the broadcast
        global mean of a send), which takes R times its memory on each
        device and makes the next program that takes the carry recompile
        for the new input sharding."""
        return jax.lax.with_sharding_constraint(carry,
                                                self.carry_shardings(carry))

    def _batch_sharding(self, ndim: int, shape, lead: int):
        """Batch leaves are (R, per, ...) with `lead` extra leading axes
        (the macro executor stacks a cycle axis in front). The per-replica
        batch dim shards over the level-0 axis when it divides — the
        intra-replica "data" tier of the topology."""
        axes = [None] * lead + [self.replica_axes]
        per_dim = lead + 1
        if (ndim > per_dim and self.spec.local_world > 1
                and shape[per_dim] % self.spec.local_world == 0):
            axes.append(self.level0_axis)
        return self._NS(self.mesh, self._P(*axes))

    def place_batch(self, batch, *, lead: int = 0):
        """Place one step's batch pytree (`lead=1` for a stacked cycle)."""
        R = self.spec.n_replicas

        def one(x):
            x = np.asarray(jax.device_get(x))
            if x.ndim <= lead or x.shape[lead] != R:
                raise ValueError(
                    f"batch leaf shape {x.shape} lacks the replica axis "
                    f"R={R} at dim {lead} (distributed runs use "
                    "replica-axis strategies)")
            return self._put(x, self._batch_sharding(x.ndim, x.shape,
                                                     lead))

        return jax.tree.map(one, batch)

    def stage_cycle(self, per_step_batches, lrs):
        """Stack a macro-cycle's per-step batches on the host and place
        them: batches (L, R, per, ...) sharded over the replica axes, lrs
        (L,) replicated."""
        stacked = jax.tree.map(
            lambda *xs: np.stack([np.asarray(jax.device_get(x))
                                  for x in xs]), *per_step_batches)
        return (self.place_batch(stacked, lead=1),
                self._put(np.asarray(lrs, np.float32), self.replicated))

    # -- host gather -------------------------------------------------------
    def fetch(self, tree):
        """Gather a (possibly process-sharded) pytree to host numpy — the
        same values on every process. Collective: every process must call
        it at the same point (they do: the host loops are deterministic)."""
        leaves = jax.tree.leaves(tree)
        if all(host_fetchable(x) for x in leaves):
            return jax.tree.map(lambda x: np.asarray(jax.device_get(x)),
                                tree)
        if self._gather is None:
            self._gather = jax.jit(lambda t: t,
                                   out_shardings=self.replicated)
        rep = self._gather(tree)
        return jax.tree.map(lambda x: np.asarray(jax.device_get(x)), rep)

    def finalize_params(self, strategy, carry):
        """Host-side final params: gather the carry, then the strategy's
        own finalize (membership-aware row selection) on numpy."""
        return strategy.finalize_params(self.fetch(carry))
