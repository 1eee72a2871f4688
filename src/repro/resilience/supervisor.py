"""Resilience supervisor: drives a fault plan end-to-end.

Wraps the compiled macro-cycle executor loop (core/executor.py) with the
three resilience pillars:

  * **elastic membership** — at a crash/rejoin boundary the supervisor
    updates the strategy's static membership mask
    (`DasoStrategy.set_membership`), invalidates the executor's compiled
    cycle cache (the old programs bake the old exchange weights), and on
    rejoin re-seeds the joiner's carry rows from the survivors' merged
    state (resilience/membership.py);
  * **deterministic fault injection** — cycle plans are cut at fault-plan
    boundaries, so every event lands between compiled cycles exactly where
    the plan says, and the controller is notified
    (`notify_membership_change` / `notify_dcn_scale`) so the B/W schedule
    adapts;
  * **full-state checkpointing** — optional periodic TrainState saves, same
    contract as train/loop.py, so a faulty run is also resumable.

Besides the training result the supervisor reports per-event recovery cost
(host handling time + the first post-event cycle, which carries the
recompile) and a simulated wall-clock that charges compute at each step's
worst active straggler and exchanges at the degraded DCN rate — the numbers
`benchmarks/resilience.py` turns into BENCH_resilience.json.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

from repro.core.executor import (MacroCycleExecutor, Strategy,
                                 dispatch_planned_cycle, resolve_executor)
from repro.core.schedule import Mode, split_mode, split_ov
from repro.core.simulator import SimResult
from repro.resilience.faults import FaultPlan
from repro.resilience.membership import reseed_carry
from repro.topo import probe as probe_mod

# outermost-level actions that touch the cross-pod network (charged an
# exchange on the simulated clock; hierarchical mode tokens are split to
# their outer action first — intermediate-level syncs ride faster links and
# are not charged at the DCN rate)
_SYNC_MODES = (Mode.SEND, Mode.SEND_RECEIVE, Mode.BLOCKING, Mode.HARD_AVG,
               Mode.GOSSIP, Mode.ELASTIC, Mode.PUSH)


@dataclass
class ResilienceReport:
    result: SimResult
    applied: List[Dict] = field(default_factory=list)  # per-event records
    invalidations: int = 0
    simulated_time_s: float = 0.0
    membership_timeline: List = field(default_factory=list)  # (step, mask)
    # autotune plane (run_with_faults autotune_every > 0): one record per
    # probe round that changed the schedule, count of group reshuffles,
    # and the accumulated straggler wait an inner-group barrier wasted on
    # the simulated clock (repro.topo.probe.wasted_wait_s)
    retunes: List[Dict] = field(default_factory=list)
    reshuffles: int = 0
    wasted_wait_s: float = 0.0

    def recovery_s(self) -> List[float]:
        """Per membership event: host handling + first post-event cycle
        (the recompile)."""
        return [e["handle_s"] + e["first_cycle_s"] for e in self.applied
                if e["kind"] in ("crash", "rejoin")]


def run_with_faults(strategy: Strategy, params0, data_fn: Callable,
                    lr_fn: Callable, n_steps: int, plan: FaultPlan, *,
                    executor: Optional[MacroCycleExecutor] = None,
                    t_compute_s: float = 0.0,
                    exchange_cost_fn: Optional[Callable] = None,
                    topo=None,
                    ckpt_every: int = 0,
                    ckpt_cb: Optional[Callable] = None,
                    placement=None,
                    start_step: int = 0, carry=None,
                    membership=None,
                    health=None, tracer=None,
                    autotune_every: int = 0,
                    oracle_notify: Optional[bool] = None,
                    reshuffle: bool = True) -> ResilienceReport:
    """Run `n_steps` of compiled training while replaying `plan`.

    `strategy` must be a replica-axis strategy (daso / hier_daso /
    local_sgd); its controller receives the notify_* adaptation hooks.
    `t_compute_s` and `exchange_cost_fn(n_active, dcn_scale) -> seconds`
    feed the simulated clock (both optional — zero cost models 'numerics
    only'). `topo` (a `repro.topo.TopologySpec`) resolves plans whose
    events name topology nodes ("pod1", "pod1/host0") into the per-replica
    events of those subtrees; without it such plans are rejected by
    `validate`. `ckpt_every`/`ckpt_cb` follow the
    executor.run_compiled_training contract.

    `placement` (launch.distributed.MeshPlacement) replays the same plan
    over the multi-process mesh: every process applies the identical
    membership flips and cache invalidations (the plan is deterministic),
    a lost process's replicas are exactly a membership-mask event on its
    subtree, and rejoin re-seeding runs on the gathered host carry so the
    re-placed rows are identical on every process.

    Resume surface (mirrors executor.run_compiled_training, used by the
    live regroup path): `start_step` + restored `carry` + the checkpoint's
    `membership` mask continue an interrupted fault run — the strategy's
    controller must already be restored by the caller. Events scheduled
    before `start_step` are rejected: anything already in the past is
    either reflected in the checkpoint's membership or meaningless to
    replay. `health` (resilience.runtime.HealthMonitor) arms the progress
    watchdog around every dispatched cycle.

    **Self-tuning** (`autotune_every` = K > 0, docs/tuning.md): every K
    cycles the supervisor probes one exchange at the current network state
    (`exchange_cost_fn(n_active, dcn_scale)` — charged to the simulated
    clock: probing is not free), compares it against the nominal cost
    (`dcn_scale == 1`), and feeds the result through
    `controller.retune(...)`; a schedule change invalidates the executor's
    compiled cycles, exactly the membership machinery. With `reshuffle`
    on, the same probe round sorts the per-replica slowdowns into a
    `repro.topo.probe.skew_permutation` regrouping and applies it via
    `strategy.set_group_permutation`. `oracle_notify` controls whether the
    degrade_dcn/restore_dcn fault events tell the controller directly (the
    pre-autotune oracle behavior); it defaults to True only when autotune
    is off — a self-tuning run must *discover* the degradation by probing,
    and a static-baseline run (`oracle_notify=False`, autotune off) never
    learns of it at all (the honest comparison BENCH_tuning.json gates)."""
    cfg = strategy.cfg
    if cfg is None:
        raise ValueError("run_with_faults needs a replica-axis strategy "
                         "with a DasoConfig (daso / hier_daso / local_sgd / "
                         "gossip / easgd / downpour)")
    n_replicas = cfg.n_replicas
    if topo is None:
        topo = getattr(strategy, "topo", None)
    if topo is not None:
        plan = plan.resolve(topo)
    mask = (list(membership) if membership is not None
            else [1.0] * n_replicas)
    past = [e for e in plan.events if e.step < start_step]
    if past:
        raise ValueError(
            f"fault plan has {len(past)} event(s) before resume step "
            f"{start_step} (first: {past[0]}); a resumed run replays only "
            "future events — the past is already in the checkpoint")
    plan.validate(n_replicas, alive0=[m > 0.0 for m in mask])

    ex, placement = resolve_executor(strategy, executor, placement)
    if health is not None and ex.health is None:
        ex.health = health
    if tracer is not None and not ex.tracer.enabled:
        ex.tracer = tracer
    if (strategy.controller is not None and ex.tracer.enabled
            and getattr(strategy.controller, "tracer", None) is None):
        # schedule decisions (plateau, dcn, retune) land in the same trace
        strategy.controller.tracer = ex.tracer
    if membership is not None and any(m <= 0.0 for m in mask):
        # the checkpoint was taken under a reduced active set: rebuild the
        # step variants with its mask baked in before anything compiles
        strategy.set_membership(mask)
    carry = strategy.init_carry(params0) if carry is None else carry
    if placement is not None:
        carry = placement.put_carry(carry)
    slowdowns = [1.0] * n_replicas
    dcn_scale = 1.0
    if oracle_notify is None:
        oracle_notify = autotune_every <= 0
    # probe pricing: the exchange cost model doubles as the probe's
    # measurement (one timed exchange at the live network state); without
    # a cost model the probe still observes the *normalized* cost 1/scale
    # vs nominal 1 — same inferred scale, zero simulated price
    probe_cost = (exchange_cost_fn if exchange_cost_fn is not None
                  else (lambda n, s: 1.0 / max(s, 1e-9)))
    # innermost non-degenerate inner-group size, for the wasted-wait
    # accounting of the inner barrier (no inner levels -> the only barrier
    # is the global one and reshuffling has nothing to recover)
    inner_group = n_replicas
    if topo is not None:
        sizes = [topo.group_size(lvl.name) for lvl in topo.levels[1:-1]
                 if topo.group_size(lvl.name) > 1]
        if sizes:
            inner_group = min(sizes)

    report = ResilienceReport(result=None)
    report.membership_timeline.append((start_step, tuple(mask)))
    losses: List[float] = []
    metrics_log: List[Dict[str, float]] = []
    sim_time = 0.0
    pending_first_cycle: List[Dict] = []  # events awaiting recompile timing
    next_ckpt = ((start_step // ckpt_every + 1) * ckpt_every
                 if ckpt_every else None)

    def apply_event(ev, step):
        nonlocal carry, dcn_scale
        t0 = time.perf_counter()
        rec = {"step": step, "kind": ev.kind, "replica": ev.replica,
               "factor": ev.factor, "first_cycle_s": 0.0}
        if ev.kind == "crash":
            mask[ev.replica] = 0.0
            strategy.set_membership(mask)
            ex.invalidate()
            if strategy.controller is not None:
                strategy.controller.notify_membership_change(
                    step, int(sum(mask)))
            report.membership_timeline.append((step, tuple(mask)))
            pending_first_cycle.append(rec)
        elif ev.kind == "rejoin":
            # re-seed BEFORE flipping the mask: donors are the survivors.
            # Distributed: surgery on the gathered host carry, re-placed —
            # identical bytes on every process by construction.
            if placement is not None:
                carry = placement.put_carry(
                    reseed_carry(placement.fetch(carry), tuple(mask),
                                 [ev.replica]))
            else:
                carry = reseed_carry(carry, tuple(mask), [ev.replica])
            mask[ev.replica] = 1.0
            strategy.set_membership(mask)
            ex.invalidate()
            if strategy.controller is not None:
                strategy.controller.notify_membership_change(
                    step, int(sum(mask)))
            report.membership_timeline.append((step, tuple(mask)))
            pending_first_cycle.append(rec)
        elif ev.kind == "straggle":
            slowdowns[ev.replica] = ev.factor
        elif ev.kind == "recover":
            slowdowns[ev.replica] = 1.0
        elif ev.kind == "degrade_dcn":
            dcn_scale = ev.factor
            if oracle_notify and strategy.controller is not None:
                strategy.controller.notify_dcn_scale(ev.factor, step=step)
        elif ev.kind == "restore_dcn":
            dcn_scale = 1.0
            if oracle_notify and strategy.controller is not None:
                strategy.controller.notify_dcn_scale(1.0, step=step)
        rec["handle_s"] = time.perf_counter() - t0
        report.applied.append(rec)

    def autotune(step, cycle_idx):
        """One probe round: measure the exchange at the live network state,
        retune the controller against the nominal cost, reshuffle groups by
        straggler skew. Returns the probe's simulated price."""
        nonlocal sim_time
        ctl = strategy.controller
        if ctl is None or not hasattr(ctl, "retune"):
            return
        n_active = int(sum(1 for m in mask if m > 0.0))
        measured = probe_cost(n_active, dcn_scale)
        nominal = probe_cost(n_active, 1.0)
        if exchange_cost_fn is not None:
            sim_time += measured  # the probe's own exchange is not free
        with ex.tracer.span("autotune_probe", cat="resilience", step=step,
                            cycle=cycle_idx, measured_s=measured,
                            nominal_s=nominal):
            changed = ctl.retune({"_outer": measured},
                                 annotated={"_outer": nominal}, step=step)
            reshuffled = False
            if reshuffle and hasattr(strategy, "set_group_permutation") \
                    and inner_group < n_replicas:
                perm = probe_mod.skew_permutation(slowdowns)
                if perm != strategy.group_perm:
                    strategy.set_group_permutation(perm)
                    reshuffled = True
                    report.reshuffles += 1
        if changed or reshuffled:
            ex.invalidate()
            report.retunes.append(
                {"step": step, "cycle": cycle_idx, "measured_s": measured,
                 "nominal_s": nominal, "schedule_changed": bool(changed),
                 "reshuffled": reshuffled})

    step = start_step
    cycle_idx = 0
    while step < n_steps:
        for ev in plan.events_at(step):
            # the span covers membership surgery + cache invalidation; the
            # recompile it provokes lands in the NEXT cycle span (its
            # compiles arg — same attribution as first_cycle_s)
            with ex.tracer.span("fault_event", cat="resilience",
                                kind=ev.kind, step=step,
                                replica=ev.replica, factor=ev.factor):
                apply_event(ev, step)
        if autotune_every > 0 and cycle_idx % autotune_every == 0:
            autotune(step, cycle_idx)
        # cut the cycle at the next fault boundary: events must land
        # between compiled cycles, mirroring the plateau-window cut
        max_len = min(ex.max_cycle_len, n_steps - step)
        boundary = plan.next_boundary_after(step)
        if boundary is not None:
            max_len = min(max_len, boundary - step)
        with ex.phase("control"):
            cycle_plan = strategy.plan_cycle(step, max_len)
        t0 = time.perf_counter()
        carry, cycle_losses, per_step_metrics = dispatch_planned_cycle(
            ex, carry, cycle_plan, data_fn, lr_fn, n_steps)
        cycle_s = time.perf_counter() - t0
        for rec in pending_first_cycle:
            rec["first_cycle_s"] = cycle_s
        pending_first_cycle.clear()
        # simulated clock: compute gated on the slowest ACTIVE replica,
        # sync steps charged one exchange at the degraded DCN rate
        worst = max((s for s, m in zip(slowdowns, mask) if m), default=1.0)
        sim_time += len(cycle_plan) * t_compute_s * worst
        if exchange_cost_fn is not None:
            n_active = int(sum(mask))
            for mode, _ in cycle_plan.shape:
                if split_ov(split_mode(mode)[0])[0] in _SYNC_MODES:
                    sim_time += exchange_cost_fn(n_active, dcn_scale)
        # straggler wait the inner-group barrier wastes under the current
        # grouping (the reshuffle's target metric — the makespan above is
        # gated by the global worst either way)
        report.wasted_wait_s += len(cycle_plan) * probe_mod.wasted_wait_s(
            slowdowns, mask, inner_group,
            getattr(strategy, "group_perm", None), t_compute_s)
        losses.extend(cycle_losses)
        metrics_log.extend(per_step_metrics)
        with ex.phase("control"):
            strategy.observe(cycle_losses)
        step += len(cycle_plan)
        cycle_idx += 1
        if next_ckpt is not None and ckpt_cb is not None and step >= next_ckpt:
            with ex.phase("checkpoint_save", cat="checkpoint", step=step):
                ckpt_cb(step, carry, losses)
            next_ckpt = (step // ckpt_every + 1) * ckpt_every
    ex.end_cycle()

    final = (placement.finalize_params(strategy, carry)
             if placement is not None else strategy.finalize_params(carry))
    report.result = SimResult(losses=losses, metrics=metrics_log,
                              params=final,
                              sync_fraction=strategy.sync_fraction(),
                              controller=strategy.controller,
                              executor_stats=ex.stats)
    report.invalidations = ex.stats.invalidations
    report.simulated_time_s = sim_time
    return report
