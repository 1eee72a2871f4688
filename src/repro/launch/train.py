"""Training launcher.

On this container it runs REAL training of a reduced architecture with DASO
(virtual nodes on one device) or sync; on a TPU cluster the same entry points
drive the production mesh (the dry-run proves those shardings compile).

Training drives through the strategy registry and the compiled macro-cycle
executor (core/executor.py) by default: one buffer-donating XLA dispatch per
controller cycle instead of one per step. `--executor per_step` selects the
reference path (identical numerics, allclose at f32).

Resilience surface:

  * ``--ckpt DIR --ckpt-every N`` writes a full resumable TrainState
    (params + optimizer + controller + in-flight exchange) every N steps;
  * ``--resume DIR/step_XXXXXXXX`` continues such a run with numerics
    identical to an uninterrupted one;
  * ``--fault-plan plan.json`` replays a declarative fault plan (node
    crash / rejoin / straggler / DCN degradation) through the resilience
    supervisor (resilience/supervisor.py).

Distributed surface (launch/distributed.py): ``--distributed`` runs the
same training over `jax.distributed` — one process per host, the topology
mesh spanning all of them, process 0 owning logs/checkpoints/metrics.
``--coordinator``/``--procs``/``--proc-id`` come from flags or from the
``DASO_*`` environment that ``tools/launch_procs.py`` exports when it
spawns N local coordinator-connected processes:

  python tools/launch_procs.py --procs 2 -- \
      --arch llama3.2-1b --topology "chip:1 x host:2 x pod:2" \
      --distributed --steps 40

  python -m repro.launch.train --arch llama3.2-1b --strategy daso \
      --steps 300 --nodes 4 --b-max 4 [--executor macro|per_step] [--full]
"""
import argparse
import dataclasses
import json
import os

import jax

from repro.configs import get_config, get_reduced
from repro.core.executor import list_strategies
from repro.data.synthetic import SyntheticLM
from repro.models import attention
from repro.models.lm import init_params
from repro.train.loop import TrainLoopConfig, run_training
from repro.train.step import make_lm_loss
from repro.optim.schedules import warmup_linear_scaled
from repro.checkpoint.io import save_checkpoint
from repro.launch.compile_cache import enable_compile_cache


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--strategy", default="daso",
                    choices=list_strategies())
    ap.add_argument("--executor", default="macro",
                    choices=["macro", "per_step"],
                    help="macro = one compiled dispatch per controller "
                         "cycle; per_step = reference path")
    ap.add_argument("--max-cycle-len", type=int, default=32)
    ap.add_argument("--wire-format", default=None,
                    choices=["f32", "bf16", "int8"],
                    help="wire tier of the global exchange; default is "
                         "the paper's bf16 blocking / f32 cycling, int8 is "
                         "the beyond-paper block-scaled tier")
    ap.add_argument("--overlap", default="off",
                    choices=["off", "one_cycle"],
                    help="double-buffered compute/communication overlap: "
                         "one_cycle hides each global exchange behind the "
                         "next B local steps and merges it one cycle stale "
                         "(Eq. (1) with the snapshot's true age as S); off "
                         "is bit-exact with pre-overlap runs. daso family "
                         "only")
    ap.add_argument("--overlap-serial-exchange", action="store_true",
                    help="debug/benchmark: block on each overlap exchange "
                         "before running compute — identical numerics, no "
                         "hiding; the baseline leg of benchmarks/"
                         "overlap.py")
    ap.add_argument("--dispatch", default=None,
                    choices=["serial", "overlap"],
                    help="multi-process executable dispatch (default "
                         "$DASO_DISPATCH or serial): serial pins one "
                         "program in flight per process (safe for every "
                         "program mix on gloo); overlap leaves async "
                         "dispatch on so the overlap executor can hide the "
                         "exchange — requires --overlap one_cycle")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--nodes", type=int, default=4,
                    help="DASO replicas (paper nodes / pods); superseded "
                         "by --topology when given")
    ap.add_argument("--local-world", type=int, default=4)
    ap.add_argument("--b-max", type=int, default=4)
    ap.add_argument("--topology", default=None, metavar="SPEC",
                    help="explicit N-level cluster topology (repro/topo): "
                         "a spec string like 'chip:4 x host:2 x pod:2', "
                         "inline JSON, or a JSON file path. Replica count "
                         "and world size derive from the level fanouts; "
                         ">2-level specs run the hier_daso per-level sync "
                         "schedule (docs/topologies.md)")
    ap.add_argument("--per-node-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds both the parameter init PRNGKey and the "
                         "synthetic data stream")
    ap.add_argument("--full", action="store_true",
                    help="use the full (published) config instead of reduced"
                         " — only sensible on real hardware")
    ap.add_argument("--tiny", action="store_true",
                    help="shrink the reduced LM config to quickstart scale "
                         "(2 layers, d_model 128, vocab 256) — the CI / "
                         "multiprocess-smoke arch. At this scale per-device "
                         "compute sits below XLA CPU's intra-op partitioning "
                         "thresholds, which the N-process bit-exactness "
                         "contract relies on (docs/architecture.md)")
    ap.add_argument("--ckpt", default=None,
                    help="checkpoint directory: final params always land "
                         "here; with --ckpt-every, periodic TrainStates in "
                         "step_XXXXXXXX/ subdirs")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="save a full resumable TrainState every N steps "
                         "(requires --ckpt)")
    ap.add_argument("--resume", default=None, metavar="STATE_DIR",
                    help="resume from a TrainState directory written by "
                         "--ckpt-every; the run continues deterministically")
    ap.add_argument("--fault-plan", default=None, metavar="PLAN_JSON",
                    help="replay a declarative fault plan (JSON: crash/"
                         "rejoin/straggle/degrade_dcn events) through the "
                         "resilience supervisor; daso-family strategies "
                         "only")
    ap.add_argument("--autotune", action="store_true",
                    help="self-tuning topology (docs/tuning.md): probe the "
                         "live mesh's per-level sync cost and retune the "
                         "lowered schedule online (controller.retune — "
                         "periods re-derived from measurements, effective "
                         "DCN scale inferred). Plain runs probe once at "
                         "startup; --fault-plan runs re-probe every "
                         "--autotune-every cycles and reshuffle inner "
                         "groups by straggler skew. Measurements matching "
                         "the spec's annotations are a strict no-op")
    ap.add_argument("--autotune-every", type=int, default=8, metavar="K",
                    help="probe cadence in macro-cycles for --autotune "
                         "under --fault-plan (default 8; the adapt-within-K "
                         "bound BENCH_tuning.json gates)")
    ap.add_argument("--metrics-out", default=None)
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a JSONL run trace (obs/trace.py): spans "
                         "from the executor/scheduler/resilience layers + "
                         "comm meters. Multi-process runs write one "
                         "PATH.e{epoch}p{proc}.jsonl stream per process, "
                         "merged into PATH by tools/launch_procs.py; "
                         "export/inspect with tools/trace_report.py")
    ap.add_argument("--distributed", action="store_true",
                    help="run over jax.distributed: the topology mesh "
                         "spans every coordinator-connected process "
                         "(launch/distributed.py); requires --topology. "
                         "With 1 process this is the SPMD oracle the "
                         "N-process run is bit-exact with")
    ap.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                    help="jax.distributed coordinator address (default "
                         "$DASO_COORDINATOR — tools/launch_procs.py "
                         "exports it)")
    ap.add_argument("--procs", type=int, default=None,
                    help="total process count (default $DASO_NUM_PROCS)")
    ap.add_argument("--proc-id", type=int, default=None,
                    help="this process's id (default $DASO_PROC_ID)")
    return ap


def arch_config(args):
    """The architecture config `args` ask for: the reduced config, the
    published one under --full, or the quickstart-scale LM under --tiny.
    Raises ValueError on a contradictory request."""
    cfg = get_config(args.arch) if args.full else get_reduced(args.arch)
    if args.tiny:
        if args.full:
            raise ValueError("--tiny and --full are mutually exclusive")
        for f in ("n_layers", "d_model", "n_heads", "d_ff", "vocab_size"):
            if not hasattr(cfg, f):
                raise ValueError(f"--tiny shrinks LM configs; {args.arch!r} "
                                 f"has no {f!r}")
        cfg = cfg.replace(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
                          head_dim=32, d_ff=256, vocab_size=256)
    return cfg


@dataclasses.dataclass
class Job:
    """Everything one training run needs, built from the launcher's
    arguments and an architecture config: `run_training(job.loss_fn,
    job.params0, job.data_fn, job.loop_cfg, lr_fn=job.lr_fn)` trains it."""
    spec: object            # repro.topo.TopologySpec, or None
    params0: dict
    loss_fn: object
    data_fn: object         # the strategy's batch stream
    daso_data: object       # the replica-axis batch stream
    loop_cfg: TrainLoopConfig
    lr_fn: object


def build_job(args, cfg) -> Job:
    """Parameters, loss, data, loop config and LR schedule of the run that
    `args` describe, for the architecture `cfg`. With --topology the
    replica count and local world come from the spec (and are written
    back to `args.nodes` / `args.local_world`)."""
    spec = None
    if args.topology:
        from repro.topo import TopologySpec
        spec = TopologySpec.load(args.topology)
        args.nodes, args.local_world = spec.n_replicas, spec.local_world
    # the initial parameters are kept on the host: the strategy builds its
    # device-resident training carry from them, and a device copy held
    # beside that carry for the whole run would only take its memory
    params0 = jax.device_get(init_params(cfg, jax.random.PRNGKey(args.seed)))
    src = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                      seed=args.seed)
    R, per = args.nodes, args.per_node_batch

    def daso_data(step):
        b = src.batch(R * per, step)
        return {k: v.reshape((R, per) + v.shape[1:]) for k, v in b.items()}

    def sync_data(step):
        return src.batch(R * per, step)

    loop_cfg = TrainLoopConfig(
        strategy=args.strategy, n_steps=args.steps, n_replicas=R,
        local_world=args.local_world, b_max=args.b_max,
        # canonical string from the spec parsed above — the strategy must
        # train on exactly the topology R/data shapes were derived from,
        # even if --topology named a file that changes under us
        topology=spec.to_str() if spec is not None else None, lr=args.lr,
        executor=args.executor, max_cycle_len=args.max_cycle_len,
        wire_format=args.wire_format, overlap=args.overlap,
        overlap_serial_exchange=args.overlap_serial_exchange,
        ckpt_every=args.ckpt_every, ckpt_dir=args.ckpt,
        resume_from=args.resume, distributed=args.distributed,
        autotune=args.autotune, autotune_every=args.autotune_every)
    lr_fn = warmup_linear_scaled(args.lr / (R * args.local_world),
                                 R * args.local_world,
                                 max(1, args.steps // 10))
    return Job(spec=spec, params0=params0,
               loss_fn=make_lm_loss(cfg),
               data_fn=sync_data if args.strategy == "sync" else daso_data,
               daso_data=daso_data, loop_cfg=loop_cfg, lr_fn=lr_fn)


def _phase_line(phase_s, scale: float) -> str:
    """`stage=1.234 dispatch=...`: host ms per loop phase, times `scale`."""
    return " ".join(f"{k}={v * 1e3 * scale:.3f}" for k, v in phase_s.items())


# step metrics of the expert layer (models/moe.py), taken at their largest
# over the run: the share of (token, slot) pairs dropped and the busiest
# expert's rows over the mean
EXPERT_METRICS = ("moe_drop_frac", "moe_load_max")


def expert_counters(metrics) -> dict:
    """{name: largest value over the steps} of the expert layer's step
    metrics; empty for a model without experts."""
    return {k: max(m[k] for m in metrics) for k in EXPERT_METRICS
            if metrics and k in metrics[0]}


def main():
    enable_compile_cache()
    ap = build_parser()
    args = ap.parse_args()

    say = print
    health = None
    live_cfg = None
    tracer = None
    if args.distributed:
        from repro.launch.distributed import (DistributedConfig, initialize,
                                              is_coordinator)
        from repro.resilience.runtime import HealthConfig, HealthMonitor
        if not args.topology:
            ap.error("--distributed derives its mesh from --topology")
        dist = DistributedConfig.from_env(coordinator=args.coordinator,
                                          num_processes=args.procs,
                                          process_id=args.proc_id,
                                          dispatch=args.dispatch)
        live_cfg = HealthConfig.from_env()  # None unless supervised
        if args.trace_out:
            # one stream per (epoch, proc), next to the heartbeat files'
            # run dir semantics; the launcher merges them into the single
            # run trace at --trace-out after the group exits
            from repro.obs.trace import Tracer, stream_path
            tracer = Tracer(stream_path(
                args.trace_out, dist.process_id,
                live_cfg.epoch if live_cfg is not None else 0),
                proc_id=dist.process_id)
        if live_cfg is not None:
            if args.executor != "macro":
                ap.error("supervised runs (DASO_RUN_DIR set) report "
                         "progress from the macro executor; drop "
                         "--executor per_step")
            # heartbeats start BEFORE the coordinator connect so even a
            # wedged initialize is watchdog-bounded
            health = HealthMonitor(live_cfg, proc_id=dist.process_id,
                                   tracer=tracer)
            health.start()
            health.phase("init")
        if dist.dispatch == "overlap" and args.overlap == "off":
            # fail BEFORE jax.distributed comes up: async dispatch with the
            # blocking schedule would put two collective-bearing programs
            # in flight on the shared gloo TCP pairs (the PR-5 interleaving
            # failure). Only the overlap executor's dispatch discipline
            # makes "overlap" safe.
            ap.error("--dispatch overlap requires --overlap one_cycle: "
                     "without the overlap executor's one-collective-in-"
                     "flight discipline, async dispatch interleaves gloo "
                     "collectives on shared TCP pairs and aborts. Use "
                     "--dispatch serial (default) for blocking schedules.")
        initialize(dist)  # before anything touches devices
        if not is_coordinator():
            if args.metrics_out:
                # raw print: `say` is about to be silenced, and the user
                # deserves to know why the file never appears on this rank
                print(f"[train][proc {dist.process_id}] --metrics-out is "
                      f"written by the coordinator only; this rank drops "
                      f"{args.metrics_out}")
            # one process speaks for the group; files are proc-0-only too
            say = lambda *a, **k: None
            args.metrics_out = None
        say(f"[train] distributed: process {dist.process_id}/"
            f"{dist.num_processes} "
            f"({jax.local_device_count()} local of "
            f"{jax.device_count()} global devices)")

    if args.ckpt_every and not args.ckpt:
        ap.error("--ckpt-every requires --ckpt")
    if args.topology and args.strategy not in ("daso", "hier_daso", "gossip",
                                               "easgd", "downpour"):
        ap.error("--topology drives the replica-axis strategies "
                 "(daso / hier_daso / gossip / easgd / downpour)")
    try:
        cfg = arch_config(args)
    except ValueError as e:
        ap.error(str(e))
    job = build_job(args, cfg)
    spec, params0, loss_fn = job.spec, job.params0, job.loss_fn
    daso_data, loop_cfg = job.daso_data, job.loop_cfg
    R = args.nodes
    if spec is not None:
        from repro.topo import derive_inner_periods
        # a %period on the outermost level overrides --b-max (exactly as
        # build_strategy's lowering does), so log the schedule that runs
        b_eff = (spec.outer.period if spec.outer.period is not None
                 else args.b_max)
        say(f"[train] topology: {spec.to_str()} -> R={spec.n_replicas} "
            f"world={spec.world} inner_periods="
            f"{derive_inner_periods(spec, b_max=b_eff)}")
    if args.distributed and spec is not None and dist.dispatch == "overlap":
        # inner-level group syncs ride inside the overlap compute program;
        # they must be process-local or they'd race the in-flight exchange
        from repro.launch.distributed import check_overlap_topology
        check_overlap_topology(spec, dist.num_processes)

    if args.trace_out and tracer is None:  # single-process run
        from repro.obs.trace import Tracer, stream_path
        tracer = Tracer(stream_path(args.trace_out, 0), proc_id=0)
    if tracer is not None:
        # everything tools/trace_report.py needs to price the model side
        # of its drift table rides in the stream itself
        param_bytes = sum(int(x.size) * x.dtype.itemsize
                          for x in jax.tree.leaves(params0))
        tracer.metadata(
            arch=args.arch, strategy=args.strategy, steps=args.steps,
            topology=spec.to_str() if spec is not None else None,
            n_replicas=R, local_world=args.local_world,
            b_max=(spec.outer.period if spec is not None
                   and spec.outer.period is not None else args.b_max),
            wire_format=args.wire_format, overlap=args.overlap,
            param_bytes=param_bytes,
            procs=dist.num_processes if args.distributed else 1,
            seed=args.seed, tiny=bool(args.tiny))

    # a supervised regroup epoch (launcher relaunched us after a real
    # process death) turns into a fault-plan run: resume from the newest
    # intact checkpoint, the death replayed as crash event(s) at the
    # resume step — numerics identical to the simulated oracle
    regroup = None
    if live_cfg is not None and live_cfg.regroup_file:
        from repro.resilience.runtime import load_regroup
        regroup = load_regroup(live_cfg.regroup_file)
        if not args.ckpt:
            ap.error("a regrouped epoch resumes from --ckpt; the "
                     "supervisor must pass --ckpt DIR --ckpt-every N")

    report = None
    live_meta = None
    if args.fault_plan or regroup is not None:
        if args.strategy == "sync":
            ap.error("--fault-plan requires a replica-axis strategy "
                     "(daso / local_sgd / gossip / easgd / downpour)")
        if args.executor != "macro":
            ap.error("--fault-plan drives the macro-cycle supervisor; "
                     "--executor per_step is not supported with it")
        if args.overlap != "off":
            ap.error("--fault-plan with --overlap is not supported: a "
                     "membership change mid-cycle would merge a pending "
                     "snapshot taken under the old active set (stale "
                     "exchange weights). Run fault plans with the blocking "
                     "schedule (--overlap off).")
        from repro.checkpoint.io import (TrainState, load_latest_train_state,
                                         load_train_state, save_train_state)
        from repro.resilience.faults import FaultPlan
        from repro.resilience.supervisor import run_with_faults
        from repro.train.loop import build_strategy, ckpt_step_dir
        from repro.optim.optimizers import sgd

        ts = None
        if regroup is not None:
            from repro.resilience.runtime import regroup_fault_events
            resumed_from, ts = load_latest_train_state(
                args.ckpt, expect_overlap="off")
            events = regroup_fault_events(ts.step, ts.membership,
                                          regroup.dead_replicas,
                                          rejoin=regroup.rejoin)
            plan = FaultPlan(tuple(events))
            if args.fault_plan:
                # keep any scripted events still ahead of the resume step
                scripted = FaultPlan.from_json(args.fault_plan)
                if spec is not None:
                    scripted = scripted.resolve(spec)
                plan = FaultPlan(plan.events + tuple(
                    e for e in scripted.events if e.step >= ts.step))
            live_meta = {"epoch": regroup.epoch, "crash_step": ts.step,
                         "dead_replicas": list(regroup.dead_replicas),
                         "rejoin": regroup.rejoin,
                         "resumed_from": resumed_from,
                         "watchdog_s": live_cfg.watchdog_s}
            say(f"[train] regroup epoch {regroup.epoch}: resumed "
                f"{resumed_from} at step {ts.step}, replaying "
                f"{len(plan.events)} event(s) for dead replicas "
                f"{list(regroup.dead_replicas)}"
                + (" with elastic rejoin" if regroup.rejoin else ""))
        else:
            plan = FaultPlan.from_json(args.fault_plan)
            if spec is not None:
                plan = plan.resolve(spec)  # topology-node events -> replicas
            if args.resume:
                ts = load_train_state(args.resume, expect_overlap="off",
                                      fallback=True)
        strategy = build_strategy(loss_fn, loop_cfg,
                                  sgd(momentum=0.9, weight_decay=1e-4))
        placement = None
        if args.distributed:
            from repro.launch.distributed import MeshPlacement
            placement = MeshPlacement(spec)

        start_step, carry, membership, prior_losses = 0, None, None, []
        if ts is not None:
            if ts.strategy != args.strategy:
                ap.error(f"checkpoint was written by strategy "
                         f"{ts.strategy!r}, run requests {args.strategy!r}")
            start_step, carry, membership = ts.step, ts.carry, ts.membership
            prior_losses = list(ts.losses)
            if ts.controller is not None and strategy.controller is not None:
                strategy.controller.load_state_dict(ts.controller)

        ckpt_cb = None
        if args.ckpt_every:
            def ckpt_cb(step, carry, seg_losses):
                if placement is not None:
                    carry = placement.fetch(carry)  # collective: all procs
                    if not placement.is_coordinator:
                        return
                save_train_state(
                    ckpt_step_dir(args.ckpt, step),
                    TrainState(
                        step=step, carry=carry,
                        controller=strategy.controller.state_dict(),
                        membership=(list(strategy.membership)
                                    if strategy.membership is not None
                                    else None),
                        strategy=args.strategy,
                        losses=prior_losses + list(seg_losses)))

        if health is not None:
            health.phase("train")
        if tracer is not None and strategy.controller is not None:
            strategy.controller.tracer = tracer
        with attention.counting_paths() as attn_paths:
            report = run_with_faults(
                strategy, params0, daso_data, job.lr_fn, args.steps, plan,
                ckpt_every=args.ckpt_every, ckpt_cb=ckpt_cb,
                placement=placement, start_step=start_step, carry=carry,
                membership=membership, health=health, tracer=tracer,
                autotune_every=(args.autotune_every
                                if args.autotune else 0))
        result = report.result
        if prior_losses:
            result.losses = prior_losses + result.losses
        say(f"[train] fault plan: {len(plan.events)} events, "
            f"{report.invalidations} cycle-cache invalidations, "
            f"simulated_time={report.simulated_time_s:.2f}s")
        for rt in report.retunes:
            say(f"[train]   step {rt['step']:>5} retune       "
                f"cycle={rt['cycle']} changed={rt['schedule_changed']} "
                f"reshuffled={rt['reshuffled']}")
        for ev in report.applied:
            say(f"[train]   step {ev['step']:>5} {ev['kind']:<12} "
                f"replica={ev.get('replica')} "
                f"handle={ev['handle_s'] * 1e3:.1f}ms "
                f"first_cycle={ev['first_cycle_s'] * 1e3:.1f}ms")
    else:
        if health is not None:
            health.phase("train")
        with attention.counting_paths() as attn_paths:
            result = run_training(loss_fn, params0, job.data_fn, loop_cfg,
                                  lr_fn=job.lr_fn, log=say, health=health,
                                  tracer=tracer)
    if health is not None:
        health.phase("finalize")
    if result.executor_stats is not None:
        s = result.executor_stats
        say(f"[train] executor: {s.dispatches} host dispatches for "
            f"{args.steps} steps ({s.compiles} compiled cycle shapes, "
            f"{s.backend_compiles} backend compiles, "
            f"{s.fallback_steps} tail-fallback steps, "
            f"{s.invalidations} invalidations)")
        if s.cycles:
            say("[train] executor host phases, ms per cycle: "
                + _phase_line(s.phase_s, 1.0 / s.cycles)
                + "; slowest cycle: " + _phase_line(s.slowest_cycle_s, 1.0))

    say("[train] attention calls traced: "
        + " ".join(f"{k}={v}" for k, v in attn_paths.items()))
    if tracer is not None:
        tracer.counter("attention_path", attn_paths)

    moe = expert_counters(result.metrics)
    if moe:
        say("[train] expert layer, largest over steps: "
            + " ".join(f"{k}={v:.4g}" for k, v in moe.items()))
        if tracer is not None:
            tracer.counter("expert_layer", moe)

    comm_rows = None
    if tracer is not None and result.controller is not None:
        # per-level comm accounting over the whole run, carried both in
        # the trace (counter event) and the metrics JSON
        from repro.obs import meters
        ctrl = result.controller
        comm_rows = meters.level_bytes_report(
            params0, ctrl.level_sync_counts(), ctrl.cfg, topo=spec,
            outer_split=meters.outer_sync_split(ctrl.history))
        tracer.counter("comm_meters", meters.rows_as_counter(comm_rows))

    if args.ckpt and (not args.distributed or jax.process_index() == 0):
        save_checkpoint(args.ckpt, result.params, step=args.steps)
        say(f"[train] checkpoint -> {args.ckpt}")
    if args.metrics_out:
        os.makedirs(os.path.dirname(args.metrics_out) or ".", exist_ok=True)
        metrics = {"losses": result.losses,
                   "sync_fraction": result.sync_fraction,
                   "final_loss": result.final_loss,
                   "seed": args.seed}
        if result.executor_stats is not None:
            metrics["executor_stats"] = dataclasses.asdict(
                result.executor_stats)
        if report is not None:
            metrics["resilience"] = {
                "events": report.applied,
                "invalidations": report.invalidations,
                "simulated_time_s": report.simulated_time_s,
                "retunes": report.retunes,
                "reshuffles": report.reshuffles,
                "wasted_wait_s": report.wasted_wait_s}
            if live_meta is not None:
                metrics["resilience"]["live"] = live_meta
        if comm_rows is not None:
            metrics["comm_meters"] = [
                {**dataclasses.asdict(r), "total_bytes": r.total_bytes}
                for r in comm_rows]
        with open(args.metrics_out, "w") as f:
            json.dump(metrics, f)
        print(f"[train] metrics -> {args.metrics_out}")
    if health is not None:
        health.close()
    if tracer is not None:
        tracer.close()
        if not args.distributed:
            # single-process runs merge their own (only) stream so
            # --trace-out names a ready run trace; distributed runs leave
            # the merge to tools/launch_procs.py after the group exits
            from repro.obs.trace import merge_streams
            merge_streams(args.trace_out, log=say)
        say(f"[train] trace events={tracer.n_events} -> {args.trace_out}")


if __name__ == "__main__":
    main()
