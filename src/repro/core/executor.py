"""Compiled macro-cycle executor + unified strategy registry.

The host-side driver used to dispatch one compiled step per training step, so
a DASO cycle of B local batches plus the send/receive merge cost B+1 host
dispatches (controller decision, batch staging, dispatch, metric fetch — per
step). At small step times that host loop dominates wall-clock, the same
granularity problem DS-Sync (arXiv 2007.03298) restructures synchronization
around. This module fuses each controller macro-cycle into ONE compiled,
buffer-donating program:

  * the `DasoController` emits a *cycle plan* — the exact (mode, staleness)
    sequence the per-step path would have run, cut at natural boundaries
    (next send, phase change, plateau-window edge) so host-side feedback
    (`observe_loss`) never needs to land mid-cycle;
  * `MacroCycleExecutor` compiles one program per distinct cycle *shape*
    (e.g. ``(send, receive@1, local, local)`` for B=4/W=1, or
    ``(blocking,)*10`` for warm-up), caching compilations by shape. Inside a
    program, homogeneous runs of the same variant execute under
    ``jax.lax.scan`` over the stacked per-step batches, so the whole cycle is
    a single XLA invocation with donated carry buffers;
  * irregular tail cycles (a shape that would be compiled for a single use
    at the end of training) fall back to the existing per-step path.

Strategies (``sync`` / ``daso`` / ``local_sgd``, plus ``hier_daso`` from
repro/topo) register here behind a common *plan -> compiled-program*
interface: each provides its carry pytree, its per-(mode, staleness) step
builder, and its cycle planner. Mode tokens are opaque strings to the
executor — under an N-level topology they carry the per-level phase vector
(``"send+host"``), so a cycle shape IS the vector of per-level phases and
the executor needs no topology awareness. The executor is
strategy-agnostic; `core/simulator.py` reuses the same interface for the
per-step reference path that the equivalence tests compare against
(see tests/test_executor.py: macro path == step path, allclose at f32).
"""
from __future__ import annotations

import difflib
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import flatbuf
from repro.core.daso import (DasoConfig, _cross_replica_loss,
                             daso_overlap_compute_step, daso_overlap_step,
                             daso_train_step, dereplicate_params,
                             global_receive, global_send, leafwise_exchange,
                             normalize_group_perm, replica_divergence,
                             replicate_params, sync_train_step)
from repro.core.schedule import (DasoController, Mode, is_ov_mode, join_mode,
                                 split_mode, split_ov)
from repro.obs.trace import NULL_TRACER, backend_compiles
from repro.optim.optimizers import Optimizer

# A cycle shape is the static fingerprint of a macro-cycle: one
# (mode, staleness) pair per step. Distinct shapes compile distinct programs.
CycleShape = Tuple[Tuple[str, int], ...]

# Mode-token prefix for the collective-free compute half of an
# overlap-dispatched cycle ("ovc:local", "ovc:local+host", ...). These
# tokens exist only inside OverlapCycle.compute_shape — the controller
# never emits them and they never enter its history.
OVERLAP_COMPUTE_PREFIX = "ovc:"


@dataclass(frozen=True)
class OverlapCycle:
    """Execution recipe for one overlap-dispatched macro-cycle: launch the
    exchange program on the pending arena, run the compute program (free of
    outer-axis collectives) while the exchange is in flight, then merge the
    exchange result into the computed params one cycle stale — Eq. (1) with
    effective S = staleness + extra_staleness."""
    compute_shape: CycleShape
    staleness: int
    extra_staleness: int


@dataclass(frozen=True)
class CyclePlan:
    """A controller-emitted macro-cycle: `shape[i]` is the (mode, staleness)
    of training step `start_step + i`."""
    start_step: int
    shape: CycleShape

    def __len__(self) -> int:
        return len(self.shape)


# -- strategy registry --------------------------------------------------------

_REGISTRY: Dict[str, type] = {}


def register_strategy(name: str):
    """Class decorator: register a Strategy subclass under `name`."""
    def deco(cls):
        cls.name = name
        _REGISTRY[name] = cls
        return cls
    return deco


def get_strategy(name: str) -> type:
    try:
        return _REGISTRY[name]
    except KeyError:
        hint = difflib.get_close_matches(name, _REGISTRY, n=1)
        suggest = f"; did you mean {hint[0]!r}?" if hint else ""
        raise KeyError(f"unknown strategy {name!r}; registered: "
                       f"{sorted(_REGISTRY)}{suggest}") from None


def list_strategies() -> List[str]:
    return sorted(_REGISTRY)


def make_strategy(name: str, loss_fn: Callable, optimizer: Optimizer,
                  cfg: Optional[DasoConfig] = None, **kw) -> "Strategy":
    return get_strategy(name)(loss_fn, optimizer, cfg, **kw)


class Strategy:
    """Common plan -> compiled-program interface.

    A strategy owns (a) the carry pytree threaded through training, (b) a
    builder for statically-specialized step functions
    ``step(carry, batch, lr) -> (carry, metrics)``, and (c) a planner that
    emits the next macro-cycle. Both executors (macro-cycle and per-step
    reference) drive strategies only through this interface.
    """
    name = "?"
    #: how the step variants take the replica mean ("leafwise" | "arena"),
    #: for the executor's compile trace and the run summary; None for a
    #: strategy without a replica exchange
    exchange_layout: Optional[str] = None

    def __init__(self, loss_fn: Callable, optimizer: Optimizer,
                 cfg: Optional[DasoConfig] = None, *,
                 controller: Optional[DasoController] = None,
                 n_micro: int = 1):
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.cfg = cfg
        self.n_micro = n_micro
        self.controller = controller or (DasoController(cfg) if cfg else None)
        self._steps: Dict[Tuple[str, int], Callable] = {}

    # -- carry lifecycle ---------------------------------------------------
    def init_carry(self, params0):
        raise NotImplementedError

    def finalize_params(self, carry):
        raise NotImplementedError

    # -- step building (cached per static variant) -------------------------
    def step_fn(self, mode: str, staleness: int) -> Callable:
        key = (mode, staleness)
        if key not in self._steps:
            self._steps[key] = self.build_step(mode, staleness)
        return self._steps[key]

    def build_step(self, mode: str, staleness: int) -> Callable:
        raise NotImplementedError

    # -- scheduling --------------------------------------------------------
    def plan_cycle(self, step: int, max_len: int) -> CyclePlan:
        raise NotImplementedError

    def next_mode(self, step: int) -> Tuple[str, int]:
        """Per-step decision for the reference path. Must be consumed in
        step order, exactly once per step, and must produce the same
        sequence `plan_cycle` would emit."""
        raise NotImplementedError

    def observe(self, losses: List[float]) -> None:
        """Feed per-step losses (in step order) back to the scheduler."""
        if self.controller is not None:
            for loss in losses:
                self.controller.observe_loss(loss)

    # -- reporting ---------------------------------------------------------
    def sync_fraction(self) -> float:
        return (self.controller.global_sync_fraction()
                if self.controller is not None else 1.0)

    def divergence(self, carry) -> Optional[float]:
        return None

    # -- controller factory ------------------------------------------------
    @classmethod
    def make_controller(cls, cfg: Optional[DasoConfig], *,
                        loss_window: int = 50):
        """The controller class this strategy schedules with — train/loop.py
        resolves it through here so strategies whose mode tokens need a
        non-default controller (core/baselines.py) stay registry-driven."""
        return (DasoController(cfg, loss_window=loss_window)
                if cfg is not None else None)


@register_strategy("daso")
class DasoStrategy(Strategy):
    """Paper strategy: replica-axis carry (params, opt_state, inflight),
    `DasoController`-planned cycles, step variants from core/daso.py.

    The DasoConfig carries the fused-exchange knobs (`wire_format`,
    `exchange_impl`, `int8_block`, `exchange_kernels`): every step variant
    this strategy builds runs its global exchange over the flat-buffer
    arena (one cross-replica collective per sync regardless of leaf
    count), so each compiled macro-cycle contains exactly one fused
    exchange program per sync step in its shape."""

    def __init__(self, loss_fn, optimizer, cfg, *, membership=None, **kw):
        assert cfg is not None, "daso strategy requires a DasoConfig"
        super().__init__(loss_fn, optimizer, cfg, **kw)
        self._membership = flatbuf.normalize_membership(
            membership, cfg.n_replicas)
        self._group_perm = None
        self._device_local = False

    # -- elastic membership ------------------------------------------------
    @property
    def membership(self):
        """Active-replica mask as a 0/1 tuple, or None when every replica
        is active (the non-elastic fast path)."""
        return self._membership

    def n_active(self) -> int:
        return (self.cfg.n_replicas if self._membership is None
                else int(sum(self._membership)))

    def set_membership(self, mask) -> None:
        """Change the active-replica set. The mask is baked *statically*
        into every step variant (membership-weighted exchange, frozen ghost
        rows — core/daso.py), so this drops the strategy's step-fn cache;
        an executor holding compiled cycles over the old variants must be
        `invalidate()`d by the caller (resilience/supervisor.py does both).
        Static baking keeps the steady-state HLO free of membership
        arithmetic — faults are rare, recompiles at fault boundaries are
        the right trade."""
        self._membership = flatbuf.normalize_membership(
            mask, self.cfg.n_replicas)
        self._steps.clear()

    # -- straggler-aware reshuffle -----------------------------------------
    @property
    def group_perm(self):
        """Replica regrouping permutation for inner-level syncs (None =
        contiguous identity grouping, the non-reshuffled fast path)."""
        return self._group_perm

    def set_group_permutation(self, perm) -> None:
        """Rotate which replicas share an inner group: slot i of the new
        grouping holds replica `perm[i]` (repro.core.daso.
        normalize_group_perm). Same contract as `set_membership` — the
        permutation is baked statically into every step variant, so this
        drops the step-fn cache and the caller must `invalidate()` any
        executor holding compiled cycles over the old variants (the
        resilience supervisor's autotune path does both). Driven by
        per-replica cycle-time skew: `repro.topo.probe.skew_permutation`
        packs similar-speed replicas into the same group so a straggler
        delays only its own group's inner syncs."""
        self._group_perm = normalize_group_perm(perm, self.cfg.n_replicas)
        self._steps.clear()

    # -- where the replica axis lives --------------------------------------
    def set_device_local(self, local: bool) -> bool:
        """Say whether the replica axis lies in one program on one device.
        The executor says it, since it holds the placement: without one
        there is no collective to coalesce, and the replica means are taken
        leaf by leaf (core/daso.py `replica_mean`); a strategy never told
        keeps the packed arena, whose one collective per sync a replica
        axis across devices needs. Same contract as `set_membership`: this
        drops the step-fn cache. Returns whether the setting changed, so
        the caller knows to `invalidate()` cycles compiled before."""
        local = bool(local)
        if local == self._device_local:
            return False
        self._device_local = local
        self._steps.clear()
        return True

    @property
    def exchange_layout(self) -> str:
        cfg = self.cfg
        return "leafwise" if leafwise_exchange(
            cfg.wire_format, impl=cfg.exchange_impl,
            use_kernels=cfg.exchange_kernels,
            device_local=self._device_local) else "arena"

    @property
    def overlap(self) -> bool:
        """True when this strategy runs the double-buffered overlap
        schedule (cfg.overlap != "off"): 4-slot carry, OV_* mode tokens,
        and — on the macro executor — async exchange dispatch."""
        return self.cfg.overlap != "off"

    def init_carry(self, params0):
        params = replicate_params(params0, self.cfg.n_replicas)
        opt_state = replicate_params(self.optimizer.init(params0),
                                     self.cfg.n_replicas)
        # warm buffer; a real copy (not an alias of params) so the executor
        # can donate both leaves of the carry independently
        inflight = jax.tree.map(jnp.array, params)
        if not self.overlap:
            return (params, opt_state, inflight)
        # overlap: the fourth slot is the pending snapshot arena — the
        # params image awaiting its (next cycle's) exchange
        pending = jax.tree.map(jnp.array, params)
        return (params, opt_state, inflight, pending)

    def finalize_params(self, carry):
        # under elastic membership row 0 may be a dead replica's frozen
        # ghost — report the first ACTIVE replica's params instead
        idx = (0 if self._membership is None
               else self._membership.index(1.0))
        return dereplicate_params(carry[0], index=idx)

    def _inner_syncs_of(self, inner: Tuple[str, ...]):
        """Map the inner-level names of a hierarchical mode token to the
        (name, group_size) pairs core/daso.py consumes. The base strategy
        has no topology, so any inner sync is a planning bug."""
        if inner:
            raise ValueError(
                f"mode carries inner-level syncs {inner!r} but strategy "
                f"{self.name!r} has no topology; use hier_daso")
        return ()

    def _build_raw(self, mode, staleness):
        """Build the 3-slot-carry step for one (mode, staleness) variant;
        the carry-unpacking wrapper in `build_step` stays shared across
        subclasses (HierDasoStrategy only overrides `_inner_syncs_of`)."""
        outer, inner = split_mode(mode)
        return daso_train_step(self.loss_fn, self.optimizer, self.cfg,
                               mode=outer, staleness=staleness,
                               n_micro=self.n_micro,
                               membership=self._membership,
                               inner_syncs=self._inner_syncs_of(inner),
                               group_perm=self._group_perm,
                               device_local=self._device_local)

    def _build_raw_overlap(self, mode, staleness):
        """Overlap counterpart of `_build_raw`: 4-slot carry, OV_* tokens,
        extra staleness decoded from the token's "~E" suffix."""
        outer, inner = split_mode(mode)
        base, extra = split_ov(outer)
        return daso_overlap_step(self.loss_fn, self.optimizer, self.cfg,
                                 mode=base, staleness=staleness,
                                 extra_staleness=extra,
                                 n_micro=self.n_micro,
                                 membership=self._membership,
                                 inner_syncs=self._inner_syncs_of(inner),
                                 group_perm=self._group_perm,
                                 device_local=self._device_local)

    def build_step(self, mode, staleness):
        if mode.startswith(OVERLAP_COMPUTE_PREFIX):
            # compute half of an overlap dispatch: 2-slot carry, no outer
            # collectives (loss reduction deferred to the merge program)
            _, inner = split_mode(mode[len(OVERLAP_COMPUTE_PREFIX):])
            raw = daso_overlap_compute_step(
                self.loss_fn, self.optimizer, self.cfg,
                n_micro=self.n_micro, membership=self._membership,
                inner_syncs=self._inner_syncs_of(inner),
                group_perm=self._group_perm)

            def cstep(carry, batch, lr):
                params, opt_state = carry
                params, opt_state, m = raw(params, opt_state, batch, lr)
                return (params, opt_state), m

            return cstep
        if self.overlap:
            raw = self._build_raw_overlap(mode, staleness)

            def ostep(carry, batch, lr):
                params, opt_state, inflight, pending = carry
                params, opt_state, inflight, pending, m = raw(
                    params, opt_state, inflight, pending, batch, lr)
                return (params, opt_state, inflight, pending), m

            return ostep
        raw = self._build_raw(mode, staleness)

        def step(carry, batch, lr):
            params, opt_state, inflight = carry
            params, opt_state, inflight, m = raw(params, opt_state, inflight,
                                                 batch, lr)
            return (params, opt_state, inflight), m

        return step

    # -- overlap dispatch recipe -------------------------------------------
    def overlap_cycle(self, shape: CycleShape) -> Optional[OverlapCycle]:
        """Return the overlap-dispatch recipe for `shape`, or None when the
        shape must run as one ordinary compiled program. Dispatchable
        shapes are the controller's overlap cycling cycles: a run of local
        steps ending in one ov_sync. Everything else — blocking phases,
        the lone ov_start opener, window-cut all-local cycles — has no
        in-flight exchange to hide and the ordinary path is already
        correct for it (the OV_* step variants pass the buffers
        through)."""
        if not self.overlap or not shape:
            return None
        last_outer, _ = split_mode(shape[-1][0])
        base, extra = split_ov(last_outer)
        if base != Mode.OV_SYNC:
            return None
        for mode, _stale in shape[:-1]:
            if split_mode(mode)[0] != Mode.LOCAL:
                return None
        compute_shape = tuple(
            (OVERLAP_COMPUTE_PREFIX
             + join_mode(Mode.LOCAL, split_mode(mode)[1]), 1)
            for mode, _stale in shape)
        return OverlapCycle(compute_shape=compute_shape,
                            staleness=shape[-1][1],
                            extra_staleness=extra)

    def overlap_exchange_fn(self):
        """pending -> inflight: the ONE outer-level collective of an
        overlap cycle, compiled as its own program so the executor can put
        it in flight before the compute program."""
        cfg, mask, local = self.cfg, self._membership, self._device_local

        def exchange(pending):
            return global_send(
                pending, wire_format=cfg.wire_format_for(blocking=False),
                impl=cfg.exchange_impl, int8_block=cfg.int8_block,
                use_kernels=cfg.exchange_kernels, mask=mask,
                deterministic=cfg.deterministic_reduce, device_local=local)

        return exchange

    def overlap_merge_fn(self, staleness: int, extra_staleness: int):
        """(params, inflight, loss_per_replica (L,R)) -> (merged params,
        per-step loss (L,)). Runs after compute and exchange both land:
        Eq. (1) with effective S = staleness + extra_staleness, plus the
        cross-replica loss reduction the compute program deferred (same
        chained order as the per-step path — bit-exact under
        deterministic_reduce)."""
        cfg, mask = self.cfg, self._membership
        n_active = self.n_active()
        p_eff = (cfg.global_world if mask is None
                 else cfg.global_world * n_active / cfg.n_replicas)

        def merge(params, inflight, loss_r):
            params = global_receive(params, inflight, staleness=staleness,
                                    extra_staleness=extra_staleness,
                                    global_world=p_eff,
                                    impl=cfg.exchange_impl,
                                    use_kernels=cfg.exchange_kernels,
                                    mask=mask)
            loss = _cross_replica_loss(cfg, mask, n_active, loss_r, axis=1)
            return params, loss

        return merge

    def plan_cycle(self, step, max_len):
        return CyclePlan(step, self.controller.plan_cycle(step, max_len))

    def next_mode(self, step):
        return self.controller.mode_for_step(step)

    def divergence(self, carry):
        return float(replica_divergence(carry[0]))


@register_strategy("sync")
class SyncStrategy(Strategy):
    """Horovod-analog baseline: flat data parallelism, no replica axis.
    Every step is the same variant, so cycles are fixed-length chunks."""

    default_cycle_len = 8

    def init_carry(self, params0):
        # copy: the executor donates the carry, and params0 belongs to the
        # caller (who may reuse it for another run)
        return (jax.tree.map(jnp.array, params0),
                self.optimizer.init(params0))

    def finalize_params(self, carry):
        return carry[0]

    def build_step(self, mode, staleness):
        raw = sync_train_step(self.loss_fn, self.optimizer,
                              n_micro=self.n_micro)

        def step(carry, batch, lr):
            params, opt_state = carry
            params, opt_state, m = raw(params, opt_state, batch, lr)
            return (params, opt_state), m

        return step

    def plan_cycle(self, step, max_len):
        n = max(1, min(max_len, self.default_cycle_len))
        return CyclePlan(step, (("sync", 1),) * n)

    def next_mode(self, step):
        return ("sync", 1)

    def observe(self, losses):
        pass

    def sync_fraction(self):
        return 1.0


@register_strategy("local_sgd")
class LocalSGDStrategy(DasoStrategy):
    """Ablation: naive periodic parameter overwrite (hard_avg every b_max
    steps), no Eq. (1) staleness weighting, no plateau schedule."""

    def _mode_at(self, step: int) -> str:
        return Mode.HARD_AVG if step % max(1, self.cfg.b_max) == 0 \
            else Mode.LOCAL

    def plan_cycle(self, step, max_len):
        b = max(1, self.cfg.b_max)
        shape = []
        while len(shape) < max_len:
            t = step + len(shape)
            if shape and t % b == 0:
                break  # next hard_avg starts the next cycle
            shape.append(self.next_mode(t))
        return CyclePlan(step, tuple(shape))

    def next_mode(self, step):
        mode = self._mode_at(step)
        self.controller.history.append((step, mode, self.controller.b,
                                        self.controller.w))
        return (mode, 1)


# -- the executor --------------------------------------------------------------

#: The host loop's phases of one cycle, in order (`dispatch_planned_cycle`,
#: `run_compiled_training`, the resilience supervisor): each runs under a
#: `repro.<phase>` span and is timed into `ExecutorStats.phase_s`.
#:   stage            data_fn calls, batch stacking/placement, lr upload
#:   dispatch         the cycle program's launch (a compile lands here)
#:   wait             until the cycle's metrics are computed on the device
#:   readback         their copy to the host and conversion to floats
#:   control          plan_cycle, observe (and divergence when tracked)
#:   checkpoint_save  the checkpoint callback
LOOP_PHASES = ("stage", "dispatch", "wait", "readback", "control",
               "checkpoint_save")


@dataclass
class ExecutorStats:
    dispatches: int = 0        # host->device program invocations
    steps: int = 0             # training steps covered by those dispatches
    cycles: int = 0            # macro-cycles executed compiled
    compiles: int = 0          # distinct cycle shapes compiled
    # XLA backend compiles (or persistent-cache loads) during cycles: also
    # counts a jit recompile of a built shape for a new input placement
    backend_compiles: int = 0
    fallback_steps: int = 0    # steps run on the per-step fallback path
    invalidations: int = 0     # cache flushes (membership changes etc.)
    # overlap-dispatch timing (wall-clock, host-observed):
    overlap_cycles: int = 0           # cycles run via the overlap dispatch
    overlap_compute_s: float = 0.0    # time until compute outputs are ready
    # extra wait for the in-flight exchange AFTER compute finished — the
    # part of the exchange that compute failed to hide
    overlap_exchange_visible_s: float = 0.0
    # exchange time when forced serial (serial_exchange=True): the
    # blocking-cost baseline the hidden fraction is measured against
    overlap_exchange_blocking_s: float = 0.0
    # the stale Eq.(1) merge after both legs completed, and the whole
    # overlap dispatch wall time. Every leg is bounded by
    # jax.block_until_ready, so compute + visible/blocking + merge == wall
    # exactly (tests/test_overlap.py asserts it) — the legs are device
    # completion times, not async dispatch returns
    overlap_merge_s: float = 0.0
    overlap_wall_s: float = 0.0
    # host seconds per loop phase (LOOP_PHASES), summed over every cycle
    phase_s: Dict[str, float] = field(
        default_factory=lambda: dict.fromkeys(LOOP_PHASES, 0.0))
    # the phase split of the slowest cycle so far (by its phases' sum; a
    # cycle runs from one stage phase to the next). A caller that wants
    # the slowest of a later window resets it to {}
    slowest_cycle_s: Dict[str, float] = field(default_factory=dict)

    def dispatches_per_step(self) -> float:
        total = self.steps + self.fallback_steps
        return self.dispatches / total if total else 0.0


def _group_runs(shape: CycleShape) -> List[Tuple[str, int, int, int]]:
    """Group consecutive identical (mode, staleness) pairs into
    (mode, staleness, offset, length) runs."""
    runs: List[Tuple[str, int, int, int]] = []
    for i, (mode, stale) in enumerate(shape):
        if runs and runs[-1][0] == mode and runs[-1][1] == stale:
            mode_, stale_, off, k = runs[-1]
            runs[-1] = (mode_, stale_, off, k + 1)
        else:
            runs.append((mode, stale, i, 1))
    return runs


class MacroCycleExecutor:
    """Compiles controller-emitted cycle plans into single XLA programs.

    One compilation per distinct `CycleShape`, cached in `_programs`.
    Homogeneous runs inside a shape execute under `jax.lax.scan`; the carry
    (params / opt state / inflight buffer) is donated so XLA reuses the
    parameter buffers in place across the whole cycle.
    """

    def __init__(self, strategy: Strategy, *, max_cycle_len: int = 32,
                 donate: bool = True, tail_fallback: bool = True,
                 placement=None, serial_exchange: bool = False,
                 health=None, tracer=None):
        self.strategy = strategy
        self.max_cycle_len = max_cycle_len
        self.donate = donate
        self.tail_fallback = tail_fallback
        # optional resilience.runtime.HealthMonitor: every completed cycle
        # is a progress report (heartbeat step + watchdog deadline push) —
        # the hook that lets a supervised run detect a peer death wedging
        # a gloo collective instead of hanging forever
        self.health = health
        # debug/measurement knob: block on the exchange BEFORE running
        # compute, turning the overlap dispatch into its blocking
        # equivalent — numerics identical, overlap_exchange_blocking_s
        # measured. benchmarks/overlap.py uses this as the baseline leg.
        self.serial_exchange = serial_exchange
        # obs.trace span/counter sink; NULL_TRACER keeps every call site
        # branch-free when tracing is off
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.stats = ExecutorStats()
        self._cycle_s: Dict[str, float] = {}   # the running cycle's split
        self._programs: Dict[CycleShape, Callable] = {}
        self._per_step: Dict[Tuple[str, int], Callable] = {}
        # jitted overlap exchange/merge programs ("exchange", or
        # ("merge", S, E)); dropped by invalidate() with everything else
        self._ov_fns: Dict[object, Callable] = {}
        self.placement = placement

    @property
    def placement(self):
        """Optional launch.distributed.MeshPlacement: carry and batches on
        the global topology mesh instead of the local default device."""
        return self._placement

    @placement.setter
    def placement(self, placement) -> None:
        # a placement may spread the replica axis over devices, which the
        # arena exchange's one collective per sync is for; without one the
        # replica axis stays in this program on one device
        self._placement = placement
        tell = getattr(self.strategy, "set_device_local", None)
        if (tell is not None and tell(placement is None)
                and (self._programs or self._per_step or self._ov_fns)):
            self.invalidate()

    # -- compilation -------------------------------------------------------
    @property
    def cached_shapes(self) -> List[CycleShape]:
        return list(self._programs)

    def program_for(self, shape: CycleShape) -> Callable:
        if shape not in self._programs:
            self._programs[shape] = self._build_program(shape)
            self.stats.compiles += 1
            # instant, not a span: jit is lazy, the XLA compile itself
            # lands inside the first cycle span of this shape (whose
            # `compiles` arg counts it)
            self.tracer.instant("compile", cat="executor",
                                shape_len=len(shape),
                                modes=[m for m, _ in shape],
                                exchange=self.strategy.exchange_layout)
        return self._programs[shape]

    def invalidate(self) -> int:
        """Drop every compiled cycle program and per-step fallback. Called
        when something the step builders bake statically changed — a
        membership change re-bakes the exchange weights into new step
        variants (DasoStrategy.set_membership), so programs closed over the
        old variants are stale. Returns the number of programs dropped;
        subsequent cycles recompile against the strategy's current step
        fns."""
        n = len(self._programs) + len(self._per_step) + len(self._ov_fns)
        self._programs.clear()
        self._per_step.clear()
        self._ov_fns.clear()
        self.stats.invalidations += 1
        self.tracer.instant("invalidate", cat="executor", dropped=n)
        return n

    def _build_program(self, shape: CycleShape) -> Callable:
        runs = _group_runs(shape)

        def program(carry, batches, lrs):
            chunks = []
            for mode, stale, off, k in runs:
                fn = self.strategy.step_fn(mode, stale)
                if k == 1:
                    batch = jax.tree.map(lambda x, i=off: x[i], batches)
                    carry, m = fn(carry, batch, lrs[off])
                    chunks.append(jax.tree.map(lambda x: x[None], m))
                else:
                    part = jax.tree.map(
                        lambda x, i=off, n=k: x[i:i + n], batches)

                    def body(c, xs, fn=fn):
                        batch, lr = xs
                        return fn(c, batch, lr)

                    carry, ms = jax.lax.scan(body, carry,
                                             (part, lrs[off:off + k]))
                    chunks.append(ms)
            metrics = jax.tree.map(
                lambda *xs: jnp.concatenate(xs, axis=0), *chunks)
            return self._constrain(carry), metrics

        # overlap forbids donation: the pending slot aliases the params
        # object in the carry (the snapshot is by-reference), and the
        # exchange program reads pending concurrently with compute — a
        # donated buffer could be reused while the collective still
        # needs it
        donate = ((0,) if self.donate
                  and not getattr(self.strategy, "overlap", False) else ())
        # keep_unused: a cycle that starts with a send never reads the
        # donated inflight buffer; kept as an argument it is aliased to
        # the new inflight instead of staying allocated beside it
        return jax.jit(program, donate_argnums=donate, keep_unused=True)

    def _constrain(self, carry):
        """A program's output carry, held on the placement's shardings
        (unchanged without a placement)."""
        if self.placement is None:
            return carry
        return self.placement.constrain_carry(carry)

    def _per_step_fn(self, mode: str, stale: int) -> Callable:
        key = (mode, stale)
        if key not in self._per_step:
            fn = self.strategy.step_fn(mode, stale)

            def step(carry, batch, lr):
                carry, metrics = fn(carry, batch, lr)
                return self._constrain(carry), metrics

            self._per_step[key] = jax.jit(step)
        return self._per_step[key]

    # -- host-loop phases --------------------------------------------------
    @contextmanager
    def phase(self, name: str, cat: str = "executor", **args):
        """One host-loop phase of a cycle (LOOP_PHASES): a span, hence a
        `repro.<name>` profiler annotation, timed into `stats.phase_s` and
        the running cycle's split. `stage` begins a new cycle."""
        if name == "stage":
            self.end_cycle()
        t0 = time.perf_counter()
        with self.tracer.span(name, cat=cat, **args) as sp:
            yield sp
        dt = time.perf_counter() - t0
        self.stats.phase_s[name] += dt
        self._cycle_s[name] = self._cycle_s.get(name, 0.0) + dt

    def end_cycle(self) -> None:
        """Close the running cycle's phase split, keeping it as
        `stats.slowest_cycle_s` when it is the slowest so far."""
        if (sum(self._cycle_s.values())
                > sum(self.stats.slowest_cycle_s.values())):
            self.stats.slowest_cycle_s = self._cycle_s
        self._cycle_s = {}

    # -- execution ---------------------------------------------------------
    def run_cycle(self, carry, plan: CyclePlan, batches, lrs, *,
                  is_tail: bool = False):
        """Execute one macro-cycle. `batches`/`lrs` carry a leading axis of
        length len(plan). Returns (carry, stacked per-step metrics)."""
        shape = plan.shape
        ov = getattr(self.strategy, "overlap_cycle", lambda s: None)(shape)
        if ov is not None:
            return self._run_overlap(carry, ov, batches, lrs)
        if (self.tail_fallback and is_tail and len(shape) > 1
                and shape not in self._programs):
            return self._run_per_step(carry, shape, batches, lrs)
        program = self.program_for(shape)
        carry, metrics = program(carry, batches, lrs)
        self.stats.dispatches += 1
        self.stats.steps += len(shape)
        self.stats.cycles += 1
        return carry, metrics

    def _ov_exchange(self) -> Callable:
        if "exchange" not in self._ov_fns:
            self._ov_fns["exchange"] = jax.jit(
                self.strategy.overlap_exchange_fn())
        return self._ov_fns["exchange"]

    def _ov_merge(self, staleness: int, extra: int) -> Callable:
        key = ("merge", staleness, extra)
        if key not in self._ov_fns:
            self._ov_fns[key] = jax.jit(
                self.strategy.overlap_merge_fn(staleness, extra))
        return self._ov_fns[key]

    def _run_overlap(self, carry, ov: OverlapCycle, batches, lrs):
        """Execute one overlap cycle as three programs: (1) the exchange
        on the pending snapshot, (2) the collective-free compute run over
        the cycle's batches, (3) the stale merge + deferred loss
        reduction. Under JAX's async dispatch (1) and (2) execute
        concurrently — (2) has no data dependence on (1), and by the
        overlap-safety contract it carries no outer-axis collective that
        could interleave with the exchange on the wire. The host blocks on
        compute first, then on the exchange, so the extra wait attributed
        to the exchange is exactly the part compute failed to hide
        (`overlap_exchange_visible_s`). With `serial_exchange` the
        exchange is awaited up front — same numerics, blocking cost
        (`overlap_exchange_blocking_s`) — which is the baseline leg of
        benchmarks/overlap.py's hidden-fraction measurement."""
        params, opt_state, _inflight_old, pending = carry
        exchange = self._ov_exchange()
        merge = self._ov_merge(ov.staleness, ov.extra_staleness)
        program = self.program_for(ov.compute_shape)
        # every leg ends on a jax.block_until_ready and the boundary
        # timestamps are shared between consecutive legs, so the three
        # stats legs partition the dispatch wall time EXACTLY (device
        # completion, never async dispatch returns) — the invariant
        # tests/test_overlap.py asserts
        t0 = time.perf_counter()
        if self.serial_exchange:
            with self.tracer.span("ov_exchange_blocking", cat="executor"):
                inflight = exchange(pending)
                jax.block_until_ready(inflight)
                t1 = time.perf_counter()
                self.stats.overlap_exchange_blocking_s += t1 - t0
            with self.tracer.span("ov_compute", cat="executor",
                                  steps=len(ov.compute_shape)):
                (params, opt_state), m = program((params, opt_state),
                                                 batches, lrs)
                jax.block_until_ready(params)
                t2 = time.perf_counter()
                self.stats.overlap_compute_s += t2 - t1
        else:
            with self.tracer.span("ov_compute", cat="executor",
                                  steps=len(ov.compute_shape)):
                inflight = exchange(pending)      # in flight, not awaited
                (params, opt_state), m = program((params, opt_state),
                                                 batches, lrs)
                jax.block_until_ready(params)
                t1 = time.perf_counter()
                self.stats.overlap_compute_s += t1 - t0
            with self.tracer.span("ov_exchange_visible", cat="executor"):
                jax.block_until_ready(inflight)
                t2 = time.perf_counter()
                self.stats.overlap_exchange_visible_s += t2 - t1
        with self.tracer.span("ov_merge", cat="executor",
                              staleness=ov.staleness,
                              extra=ov.extra_staleness):
            params, loss = merge(params, inflight, m["loss_per_replica"])
            jax.block_until_ready(params)
            t3 = time.perf_counter()
            self.stats.overlap_merge_s += t3 - t2
        self.stats.overlap_wall_s += t3 - t0
        metrics = dict(m)
        metrics["loss"] = loss
        # pending <- merged params (by reference — donation is off under
        # overlap, so the alias is safe): the next cycle's exchange sends
        # exactly the params this cycle's merge produced
        carry = (params, opt_state, inflight, params)
        self.stats.dispatches += 3
        self.stats.steps += len(ov.compute_shape)
        self.stats.cycles += 1
        self.stats.overlap_cycles += 1
        return carry, metrics

    def _run_per_step(self, carry, shape: CycleShape, batches, lrs):
        """Irregular-tail fallback: the old one-dispatch-per-step path, so a
        shape used exactly once never pays a fresh compilation."""
        chunks = []
        for i, (mode, stale) in enumerate(shape):
            fn = self._per_step_fn(mode, stale)
            batch = jax.tree.map(lambda x, j=i: x[j], batches)
            carry, m = fn(carry, batch, lrs[i])
            chunks.append(jax.tree.map(lambda x: x[None], m))
            self.stats.dispatches += 1
            self.stats.fallback_steps += 1
        metrics = jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *chunks)
        return carry, metrics


def resolve_executor(strategy: Strategy,
                     executor: Optional[MacroCycleExecutor],
                     placement) -> Tuple[MacroCycleExecutor, object]:
    """One rule for marrying a (possibly caller-built) executor with a
    (possibly absent) placement: build the executor if needed, hand it the
    placement unless it already carries one, and return the placement that
    is actually in force. Shared by `run_compiled_training` and the
    resilience supervisor so the two dispatch loops cannot drift."""
    ex = executor or MacroCycleExecutor(strategy, placement=placement)
    if placement is not None and ex.placement is None:
        ex.placement = placement
    return ex, ex.placement


def shape_sync_counts(shape: CycleShape) -> Dict[str, int]:
    """Per-level sync tally of ONE cycle shape — the plan-side counterpart
    of `DasoController.level_sync_counts` (which tallies the whole
    history). Cycle trace spans carry this so tools/trace_report.py can
    regress per-level sync costs out of cycle durations."""
    counts: Dict[str, int] = {"_outer": 0}
    for (m, _) in shape:
        if m.startswith(OVERLAP_COMPUTE_PREFIX):
            m = m[len(OVERLAP_COMPUTE_PREFIX):]
        outer, inner = split_mode(m)
        if split_ov(outer)[0] in (Mode.SEND, Mode.SEND_RECEIVE,
                                  Mode.BLOCKING, Mode.HARD_AVG,
                                  Mode.OV_SYNC, Mode.GOSSIP,
                                  Mode.ELASTIC, Mode.PUSH):
            counts["_outer"] += 1
        for name in inner:
            counts[name] = counts.get(name, 0) + 1
    return counts


def dispatch_planned_cycle(ex: MacroCycleExecutor, carry, plan: CyclePlan,
                           data_fn: Callable, lr_fn: Callable,
                           n_steps: int):
    """Stage one planned cycle's batches/lrs, execute it, and convert the
    stacked device metrics to host floats. Returns (carry, cycle_losses,
    per_step_metrics). Shared by `run_compiled_training` and the resilience
    supervisor so the two dispatch loops cannot silently drift.

    The whole sequence is one "cycle" span over the loop phases stage ->
    dispatch -> wait -> readback (`MacroCycleExecutor.phase`). `wait`
    blocks until the cycle's metrics are computed, so the span covers the
    cycle's device time and `readback` only the copy and conversion. The
    cycle span's args carry the per-level sync counts (when the stream is
    on: the drift-table fit reads them) and the backend compiles seen
    during the cycle; `fresh_compile` is True when there was one (a new
    shape, or a jit recompile of a built one for a new input placement)
    — the fit leaves those cycles out."""
    fallback0 = ex.stats.fallback_steps
    compiles0 = backend_compiles()
    args = {"start_step": plan.start_step, "steps": len(plan)}
    if ex.tracer.enabled:
        args["syncs"] = shape_sync_counts(plan.shape)
    with ex.tracer.span("cycle", cat="executor", **args) as sp:
        with ex.phase("stage", steps=len(plan)):
            steps = range(plan.start_step, plan.start_step + len(plan))
            per_step = [data_fn(t) for t in steps]
            lr_list = [lr_fn(t) for t in steps]
            if ex.placement is not None:
                batches, lrs = ex.placement.stage_cycle(per_step, lr_list)
            else:
                batches = jax.tree.map(lambda *xs: jnp.stack(xs), *per_step)
                lrs = jnp.asarray(lr_list, jnp.float32)
        with ex.phase("dispatch") as dsp:
            d0 = backend_compiles()
            carry, metrics = ex.run_cycle(
                carry, plan, batches, lrs,
                is_tail=plan.start_step + len(plan) >= n_steps)
            dsp.set_metadata(compiles=backend_compiles() - d0)
        # per-replica diagnostics may be sharded across processes in a
        # distributed run; only host-fetchable metrics (scalars are always
        # replicated) feed the loss trace
        fetch = [(k, v) for k, v in metrics.items()
                 if flatbuf.host_fetchable(v)]
        with ex.phase("wait"):
            jax.block_until_ready([v for _, v in fetch])
        with ex.phase("readback", arrays=len(fetch)):
            host = {k: np.asarray(v) for k, v in fetch}
            cycle_losses = [float(host["loss"][j]) for j in range(len(plan))]
            per_step_metrics = [{k: float(v[j]) for k, v in host.items()
                                 if v.ndim == 1} for j in range(len(plan))]
        compiles = backend_compiles() - compiles0
        ex.stats.backend_compiles += compiles
        sp.set_metadata(compiles=compiles, fresh_compile=compiles > 0,
                        fallback=ex.stats.fallback_steps > fallback0)
    if ex.health is not None:
        # progress report AFTER the wait above forced the cycle's
        # collectives to complete: the watchdog deadline only moves when
        # the group demonstrably made it through the exchange
        ex.health.cycle_done(plan.start_step + len(plan))
    return carry, cycle_losses, per_step_metrics


def run_compiled_training(strategy: Strategy, params0, data_fn: Callable,
                          lr_fn: Callable, n_steps: int, *,
                          executor: Optional[MacroCycleExecutor] = None,
                          track_divergence: bool = False,
                          start_step: int = 0, carry=None,
                          ckpt_every: int = 0,
                          ckpt_cb: Optional[Callable] = None,
                          placement=None):
    """Macro-cycle counterpart of `simulator.run_per_step_training`: plans
    cycles from the strategy's controller, stacks the per-step batches, and
    dispatches one compiled program per cycle. Numerically equivalent to the
    per-step path (allclose at f32; tests/test_executor.py).

    With `track_divergence` the replica divergence is sampled once per cycle
    (the per-step path samples every step) — it is a host-side diagnostic
    that would otherwise force a per-step sync point.

    Resume/checkpoint surface (checkpoint/io.py TrainState): pass
    `start_step` + the restored `carry` to continue a run (the strategy's
    controller must already be restored — train/loop.py does both), and
    `ckpt_every` + `ckpt_cb(completed_steps, carry, losses)` to snapshot.
    The callback fires at the first *cycle boundary* at or past each
    `ckpt_every` multiple — a checkpointed step is therefore always a step
    where a fresh run also had a plan boundary, which is what makes a
    resumed schedule (and hence the numerics) identical to an
    uninterrupted run.

    `placement` (launch.distributed.MeshPlacement) runs the identical loop
    over the global topology mesh: carry and batches are sharded over the
    replica-level axes, final params are gathered to host. The compiled
    programs do not depend on the process count, which is what makes an
    N-process run bit-exact with the 1-process one
    (tests/test_multiprocess.py).
    """
    from repro.core.simulator import SimResult

    ex, placement = resolve_executor(strategy, executor, placement)
    carry = strategy.init_carry(params0) if carry is None else carry
    if placement is not None:
        carry = placement.put_carry(carry)
    losses: List[float] = []
    metrics_log: List[Dict[str, float]] = []
    divs: List[float] = []
    step = start_step
    next_ckpt = ((start_step // ckpt_every + 1) * ckpt_every
                 if ckpt_every else None)
    while step < n_steps:
        with ex.phase("control"):
            plan = strategy.plan_cycle(step, min(ex.max_cycle_len,
                                                 n_steps - step))
        carry, cycle_losses, per_step_metrics = dispatch_planned_cycle(
            ex, carry, plan, data_fn, lr_fn, n_steps)
        losses.extend(cycle_losses)
        metrics_log.extend(per_step_metrics)
        with ex.phase("control"):
            strategy.observe(cycle_losses)
            if track_divergence:
                d = strategy.divergence(carry)
                if d is not None:
                    divs.extend([d] * len(plan))
        step += len(plan)
        if next_ckpt is not None and ckpt_cb is not None and step >= next_ckpt:
            with ex.phase("checkpoint_save", cat="checkpoint", step=step):
                ckpt_cb(step, carry, losses)
            next_ckpt = (step // ckpt_every + 1) * ckpt_every
    ex.end_cycle()
    params = (placement.finalize_params(strategy, carry)
              if placement is not None
              else strategy.finalize_params(carry))
    return SimResult(losses=losses, metrics=metrics_log, params=params,
                     sync_fraction=strategy.sync_fraction(),
                     controller=strategy.controller, divergence=divs,
                     executor_stats=ex.stats)


# registered on import so every registry consumer (launch/train.py argparse
# choices, train/loop.py, the conformance suite) sees the baseline family;
# imported last because baselines.py subclasses DasoStrategy from this module
from repro.core import baselines  # noqa: E402,F401
