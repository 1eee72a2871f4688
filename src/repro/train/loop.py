"""End-to-end training driver: strategy selection via the registry
(sync / daso / local_sgd), LR scheduling, metrics, and full-state
checkpointing (`ckpt_every`/`ckpt_dir` save a resumable
`checkpoint.io.TrainState` — carry, controller schedule state, membership,
loss trace; `resume_from` continues a run with numerics identical to an
uninterrupted one, tests/test_resilience.py). Used by launch/train.py, the
examples, and the convergence benchmarks.

Two execution paths, numerically equivalent (allclose at f32):

  * ``executor="macro"`` (default) — the compiled macro-cycle path
    (core/executor.py): one buffer-donating XLA dispatch per controller
    cycle instead of one per step. Checkpoints land on cycle boundaries.
  * ``executor="per_step"`` — the reference path (core/simulator.py): one
    dispatch per step, useful for debugging and as the equivalence oracle.
    Checkpoints land on exact `ckpt_every` multiples.
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass
from typing import Callable, Optional

from repro.checkpoint.io import (TrainState, load_train_state,
                                 save_train_state)
from repro.core.daso import DasoConfig
from repro.core.executor import (MacroCycleExecutor, get_strategy,
                                 list_strategies, make_strategy,
                                 run_compiled_training)
from repro.core.simulator import SimResult, run_per_step_training
from repro.optim.optimizers import Optimizer, sgd
from repro.optim.schedules import constant_lr


@dataclass
class TrainLoopConfig:
    strategy: str = "daso"            # registered name: daso|hier_daso|sync|...
    n_steps: int = 200
    n_replicas: int = 4               # paper "nodes"
    local_world: int = 4              # paper GPUs-per-node (data-axis size)
    b_max: int = 4
    # explicit N-level cluster topology (repro/topo): a spec string
    # ("chip:4 x host:2 x pod:2"), inline JSON, or a JSON file path. When
    # set it *supersedes* n_replicas/local_world (derived from the level
    # fanouts) and selects the per-level sync schedule: 2-level specs
    # lower to the stock daso strategy (bit-exact with the legacy path),
    # deeper specs to hier_daso. Only meaningful for the daso family.
    topology: Optional[str] = None
    warmup_frac: float = 0.1          # paper: warm-up epochs -> step fraction
    cooldown_frac: float = 0.1
    lr: float = 0.05
    loss_window: int = 20
    log_every: int = 50
    executor: str = "macro"           # macro | per_step
    max_cycle_len: int = 32           # cap on compiled macro-cycle length
    # wire tier of the global exchange (DasoConfig.wire_format): None is
    # the paper's bf16 blocking / f32 cycling; "f32" | "bf16" | "int8"
    # forces one tier.
    wire_format: Optional[str] = None
    # double-buffered compute/communication overlap (core/daso.py
    # OVERLAP_MODES): "off" = the blocking schedule, bit-exact with
    # pre-overlap runs; "one_cycle" = each global exchange runs on the
    # previous sync's snapshot, hidden behind the next B local steps and
    # merged one cycle stale (Eq. (1) with the snapshot's true age as S).
    # Only meaningful for the daso family.
    overlap: str = "off"
    # debug/benchmark knob: execute overlap cycles with the exchange
    # blocked BEFORE compute (same numerics, no hiding) — the baseline leg
    # of benchmarks/overlap.py's hidden-fraction measurement
    overlap_serial_exchange: bool = False
    # full-state checkpointing: every `ckpt_every` steps (0 = off) a
    # TrainState lands in `ckpt_dir/step_XXXXXXXX/`; `resume_from` points at
    # one such directory to continue the run deterministically.
    ckpt_every: int = 0
    ckpt_dir: Optional[str] = None
    resume_from: Optional[str] = None
    # multi-process runtime (launch/distributed.py): run over the global
    # topology mesh — jax.distributed must already be initialized (the
    # launcher entry point does it) and `topology` must be set; replica
    # levels shard over the (process, local-device) axes, process 0 owns
    # logging and checkpoint writes. The same flag with one process is the
    # single-process SPMD oracle the N-process run is bit-exact with.
    distributed: bool = False
    # self-tuning topology (repro/topo/probe, docs/tuning.md): time one
    # real per-level sync on the live mesh at startup and retune the
    # lowered schedule against the spec's annotations before training
    # (controller.retune — measured == annotated is a strict no-op).
    # `autotune_every` is the probe cadence in cycles for the supervised
    # fault path (resilience/supervisor.py; the plain loop probes once).
    autotune: bool = False
    autotune_every: int = 8


# strategies that take a topology spec purely for sizing — replica count,
# world size, outer sync period — with no per-level sync schedule
# (core/baselines.py; a spec with intermediate levels is rejected for them)
_FLAT_TOPOLOGY_STRATEGIES = ("gossip", "easgd", "downpour")


def resolve_topology(cfg: TrainLoopConfig):
    """The `TopologySpec` of this run, or None when cfg.topology is unset.
    Validates that the strategy is topology-capable."""
    if cfg.topology is None:
        return None
    if cfg.strategy not in (("daso", "hier_daso")
                            + _FLAT_TOPOLOGY_STRATEGIES):
        raise ValueError(f"topology specs drive the replica-axis strategies "
                         f"(daso / hier_daso / gossip / easgd / downpour); "
                         f"strategy {cfg.strategy!r} does not take one")
    from repro.topo import TopologySpec
    return TopologySpec.load(cfg.topology)


def build_strategy(loss_fn: Callable, cfg: TrainLoopConfig,
                   optimizer: Optimizer):
    """Resolve cfg.strategy through the registry into a Strategy instance
    (with its DasoConfig + controller for the replica-axis strategies).
    With cfg.topology set, the instance is lowered from the spec instead
    (repro.topo.lower.build_topology_strategy): replica count and world
    size come from the level fanouts, intermediate levels get their
    per-level sync periods, and the plateau controller drives the
    outermost level."""
    import repro.topo.strategy  # noqa: F401  (registers "hier_daso")

    if cfg.strategy not in list_strategies():
        raise KeyError(f"unknown strategy {cfg.strategy!r}; "
                       f"registered: {list_strategies()}")
    if cfg.strategy == "sync":
        if cfg.topology is not None:
            resolve_topology(cfg)  # raises with the explanation
        if cfg.overlap != "off":
            raise ValueError("overlap is a daso-family schedule; the sync "
                             "baseline has no non-blocking exchange to "
                             "overlap (drop --overlap or switch strategy)")
        return make_strategy("sync", loss_fn, optimizer)
    spec = resolve_topology(cfg)
    n_replicas = spec.n_replicas if spec is not None else cfg.n_replicas
    world = spec.world if spec is not None \
        else cfg.n_replicas * cfg.local_world
    b_max = (spec.outer.period if spec is not None
             and spec.outer.period is not None else cfg.b_max)
    dcfg = DasoConfig(
        n_replicas=n_replicas,
        global_world=world,
        b_max=b_max,
        warmup_steps=int(cfg.warmup_frac * cfg.n_steps),
        cooldown_steps=int(cfg.cooldown_frac * cfg.n_steps),
        total_steps=cfg.n_steps,
        wire_format=cfg.wire_format,
        overlap=cfg.overlap,
        # distributed runs pin every cross-replica reduction to the
        # order-fixed chain formulation so the result is independent of
        # the process layout (the N-proc == 1-proc bit-exactness contract)
        deterministic_reduce=cfg.distributed)
    if spec is not None and cfg.strategy not in _FLAT_TOPOLOGY_STRATEGIES:
        from repro.topo import build_topology_strategy
        return build_topology_strategy(loss_fn, optimizer, spec, dcfg,
                                       loss_window=cfg.loss_window)
    if spec is not None and tuple(spec.inner_names()):
        raise ValueError(
            f"strategy {cfg.strategy!r} has no per-level sync schedule; "
            f"topology spec carries intermediate levels "
            f"{tuple(spec.inner_names())} — use a 2-level spec, or "
            f"daso/hier_daso for hierarchical syncing")
    if cfg.strategy == "hier_daso":
        raise ValueError("strategy 'hier_daso' needs a topology spec "
                         "(TrainLoopConfig.topology / --topology)")
    cls = get_strategy(cfg.strategy)
    controller = cls.make_controller(dcfg, loss_window=cfg.loss_window)
    return cls(loss_fn, optimizer, dcfg, controller=controller)


def ckpt_step_dir(ckpt_dir: str, step: int) -> str:
    return os.path.join(ckpt_dir, f"step_{step:08d}")


def run_training(loss_fn: Callable, params0, data_fn: Callable,
                 cfg: TrainLoopConfig, *, optimizer: Optional[Optimizer] = None,
                 lr_fn: Optional[Callable] = None,
                 log: Optional[Callable] = print,
                 health=None, tracer=None) -> SimResult:
    """data_fn(step) -> batch. For daso/local_sgd strategies the batch must
    carry the leading replica axis; for sync it is flat.

    On resume (`cfg.resume_from`), the returned SimResult's loss trace is
    the *full* run (checkpointed prefix + resumed segment), so downstream
    reporting (final_loss, metrics JSON) is seamless across restarts.

    `health` (resilience.runtime.HealthMonitor) threads the live-fault
    heartbeat/watchdog into the macro executor — supervised multi-process
    runs only (launch/train.py wires it from the launcher environment).

    `tracer` (obs.trace.Tracer) threads the telemetry plane through the
    macro executor (cycle/overlap/checkpoint spans) and the strategy's
    controller (decision events) — launch/train.py wires it from
    --trace-out. The per-step reference path is deliberately untraced:
    it exists as a numerics oracle, not a performance surface."""
    optimizer = optimizer or sgd(momentum=0.9, weight_decay=1e-4)
    lr_fn = lr_fn or constant_lr(cfg.lr)
    if cfg.executor not in ("macro", "per_step"):
        raise ValueError(f"unknown executor {cfg.executor!r}; "
                         "expected 'macro' or 'per_step'")
    if health is not None and cfg.executor != "macro":
        raise ValueError("live supervision (health monitor) reports "
                         "progress from the macro executor's cycle "
                         "dispatch; run supervised jobs with "
                         "--executor macro")
    strategy = build_strategy(loss_fn, cfg, optimizer)
    if tracer is not None and strategy.controller is not None:
        strategy.controller.tracer = tracer

    if cfg.autotune:
        spec = resolve_topology(cfg)
        if spec is None or strategy.controller is None:
            if log is not None:
                log("[train] autotune: no topology spec to probe; "
                    "schedule left as configured")
        elif cfg.distributed:
            # per-process wall-clock probes could disagree and desync the
            # schedule; the distributed probe channel is the supervised
            # path's deterministic cost model (launch/train.py
            # --fault-plan --autotune) or the passive tracer samples
            if log is not None:
                log("[train] autotune: startup wall-clock probe skipped "
                    "under --distributed (see docs/tuning.md)")
        else:
            from repro.topo import probe as topo_probe
            pr = topo_probe.active_probe(spec)
            changed = strategy.controller.retune(
                pr.costs, annotated=topo_probe.annotated_level_costs(
                    spec, pr.param_bytes))
            if log is not None:
                periods = getattr(strategy.controller, "inner_periods", {})
                log(f"[train] autotune probe: measured "
                    f"{ {k: round(v * 1e6, 1) for k, v in pr.costs.items()} }"
                    f" us/sync -> retuned={changed} b={strategy.controller.b}"
                    f" inner_periods={periods}")

    placement = None
    if cfg.distributed:
        from repro.launch.distributed import MeshPlacement
        spec = resolve_topology(cfg)
        if spec is None:
            raise ValueError("distributed runs derive their mesh from the "
                             "topology; set TrainLoopConfig.topology "
                             "(--topology)")
        placement = MeshPlacement(spec)
        if log is not None and not placement.is_coordinator:
            log = None  # one process speaks for the group

    start_step, carry, prior_losses = 0, None, []
    if cfg.resume_from:
        # reject carry-layout mismatches up front: a pre-overlap (v1 /
        # overlap="off") checkpoint has no pending arena to resume
        # mid-overlap from, and vice versa
        expect = cfg.overlap if cfg.strategy != "sync" else "off"
        # fallback=True: a crash mid-save (the live-fault SIGKILL case)
        # leaves the newest snapshot torn; resume from the newest intact
        # sibling instead of dying on it
        ts = load_train_state(cfg.resume_from, expect_overlap=expect,
                              fallback=True)
        if ts.strategy != cfg.strategy:
            raise ValueError(f"checkpoint was written by strategy "
                             f"{ts.strategy!r}, run requests "
                             f"{cfg.strategy!r}")
        start_step, carry = ts.step, ts.carry
        prior_losses = list(ts.losses)
        if ts.controller is not None and strategy.controller is not None:
            strategy.controller.load_state_dict(ts.controller)
        if ts.membership is not None and hasattr(strategy, "set_membership"):
            strategy.set_membership(ts.membership)
        if log is not None:
            log(f"[train] resumed from {cfg.resume_from} at step "
                f"{start_step}")

    ckpt_cb = None
    if cfg.ckpt_every and cfg.ckpt_dir:
        def ckpt_cb(step, cur_carry, seg_losses):
            # process-aware: the carry is gathered on EVERY process (the
            # gather is a collective), then only process 0 touches the
            # filesystem
            if placement is not None:
                cur_carry = placement.fetch(cur_carry)
                if not placement.is_coordinator:
                    return
            state = TrainState(
                step=step, carry=cur_carry,
                controller=(strategy.controller.state_dict()
                            if strategy.controller is not None else None),
                membership=(list(strategy.membership)
                            if getattr(strategy, "membership", None)
                            is not None else None),
                strategy=cfg.strategy,
                overlap=(cfg.overlap if cfg.strategy != "sync" else "off"),
                losses=prior_losses + seg_losses)
            save_train_state(ckpt_step_dir(cfg.ckpt_dir, step), state)

    t0 = time.time()
    if cfg.executor == "per_step":
        result = run_per_step_training(
            strategy, params0, data_fn, lr_fn, cfg.n_steps,
            start_step=start_step, carry=carry,
            ckpt_every=cfg.ckpt_every, ckpt_cb=ckpt_cb,
            placement=placement)
    else:
        executor = MacroCycleExecutor(
            strategy, max_cycle_len=cfg.max_cycle_len, placement=placement,
            serial_exchange=cfg.overlap_serial_exchange, health=health,
            tracer=tracer)
        result = run_compiled_training(
            strategy, params0, data_fn, lr_fn, cfg.n_steps,
            executor=executor, start_step=start_step, carry=carry,
            ckpt_every=cfg.ckpt_every, ckpt_cb=ckpt_cb)
    if prior_losses:
        result.losses = prior_losses + result.losses
    if log is not None:
        dt = time.time() - t0
        stats = result.executor_stats
        disp = (f" dispatches={stats.dispatches}/{cfg.n_steps}"
                if stats is not None else "")
        wire = (f" wire={cfg.wire_format or 'auto'}"
                f" exchange={strategy.exchange_layout}"
                if cfg.strategy != "sync" else "")
        log(f"[train] strategy={cfg.strategy} steps={cfg.n_steps} "
            f"final_loss={result.final_loss:.4f} "
            f"sync_frac={result.sync_fraction:.3f} wall={dt:.1f}s"
            f"{disp}{wire}")
    return result
