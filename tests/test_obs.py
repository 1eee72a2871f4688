"""The observability tier (ISSUE 8):

  * `obs.trace` unit contract: span/instant/counter events are valid
    JSONL trace events, the null tracer writes nothing, per-process
    streams merge timestamp-sorted, Chrome export wraps without loss.
  * Executor integration: every dispatched cycle gets a span carrying
    (steps, per-level sync counts, compiles/fresh_compile/fallback);
    checkpoint saves get spans; the overlap legs get their own spans.
  * Controller decision events: plateau-driven B/W changes, membership
    flushes, and DCN rescales land in the trace with a `reason` —
    and the tracer never leaks into controller checkpoints.
  * `obs.meters`: per-level bytes-on-the-wire from level_sync_counts +
    the flat-buffer wire pricing, split by outer phase wire tier, and
    cross-checked against compiled-program collective stats.
  * Heartbeat wire format: the schema round-trips what HealthMonitor
    writes, and tolerates extra keys in both planes.
  * `tools/trace_report.py`: the cycle-cost regression recovers known
    coefficients exactly, and the drift table covers every sync level
    of the run's topology.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

from conftest import make_mlp_problem

from repro.core.daso import DasoConfig
from repro.core.schedule import DasoController
from repro.obs.trace import (NULL_TRACER, RUN_METADATA, Tracer, load_events,
                             merge_streams, stream_path, to_chrome,
                             validate_event)
from repro.obs import meters
from repro.optim.optimizers import sgd
from repro.optim.schedules import constant_lr
from repro.train.loop import TrainLoopConfig, run_training

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _report_mod():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import trace_report
    return trace_report


def _events(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


# -- tracer unit contract ------------------------------------------------------

def test_tracer_events_are_valid_jsonl(tmp_path):
    p = str(tmp_path / "t.e0p0.jsonl")
    tr = Tracer(p, proc_id=0, flush_every=4)
    with tr.span("cycle", cat="executor", steps=3):
        pass
    tr.instant("compile", cat="executor", shape_len=2)
    tr.counter("comm_meters", {"_outer.syncs": 4.0})
    tr.metadata(arch="mlp", param_bytes=123)
    tr.close()
    evs = _events(p)
    # process_name + 4 events, and nothing appended by close()
    assert len(evs) == 5 == tr.n_events
    for ev in evs:
        assert validate_event(ev) is None, ev
    names = [ev["name"] for ev in evs]
    assert names[0] == "process_name" and names[-1] == RUN_METADATA
    span = next(ev for ev in evs if ev["name"] == "cycle")
    assert span["ph"] == "X" and span["dur"] >= 0
    assert span["args"]["steps"] == 3


def test_tracer_close_is_idempotent_and_final(tmp_path):
    p = str(tmp_path / "t.e0p0.jsonl")
    tr = Tracer(p)
    tr.instant("x")
    tr.close()
    n = len(_events(p))
    tr.close()
    tr.instant("after_close")  # dropped, not an error
    assert len(_events(p)) == n


def test_null_tracer_is_api_complete_noop():
    # no stream, but its spans are usable as context managers (profiler
    # annotations) and take args after the fact like a Tracer's
    with NULL_TRACER.span("cycle", steps=1) as sp:
        sp.set_metadata(compiles=0)
        with NULL_TRACER.span("stage"):
            pass
    NULL_TRACER.instant("x")
    NULL_TRACER.counter("c", {"v": 1.0})
    NULL_TRACER.metadata(a=1)
    NULL_TRACER.close()
    assert NULL_TRACER.enabled is False
    assert NULL_TRACER.n_events == 0


@pytest.mark.parametrize("ev,frag", [
    ("nope", "not an object"),
    ({"ph": "X", "ts": 0, "pid": 0}, "missing required key 'name'"),
    ({"name": "", "ph": "i", "ts": 0, "pid": 0}, "non-empty"),
    ({"name": "a", "ph": "Z", "ts": 0, "pid": 0}, "unknown phase"),
    ({"name": "a", "ph": "i", "ts": -1, "pid": 0}, "non-negative"),
    ({"name": "a", "ph": "X", "ts": 0, "pid": 0}, "dur"),
    ({"name": "a", "ph": "X", "ts": 0, "pid": 0, "dur": -5}, "dur"),
    ({"name": "a", "ph": "i", "ts": 0, "pid": 0, "args": [1]}, "args"),
])
def test_validate_event_rejects(ev, frag):
    err = validate_event(ev)
    assert err is not None and frag in err


def test_validate_event_tolerates_extra_keys():
    ev = {"name": "a", "ph": "i", "ts": 1, "pid": 0,
          "future_field": {"anything": True}}
    assert validate_event(ev) is None


def test_merge_streams_sorts_across_processes(tmp_path):
    base = str(tmp_path / "trace.jsonl")
    for proc in (0, 1):
        tr = Tracer(stream_path(base, proc), proc_id=proc)
        for i in range(3):
            tr.instant(f"p{proc}e{i}")
        tr.close()
    assert merge_streams(base) == base
    evs = _events(base)
    assert [ev["ts"] for ev in evs] == sorted(ev["ts"] for ev in evs)
    assert {ev["pid"] for ev in evs} == {0, 1}
    # load_events reads the merged file; in-memory merge when base absent
    assert load_events(base) == evs
    os.remove(base)
    assert load_events(base) == evs
    assert merge_streams(str(tmp_path / "other.jsonl")) is None


def test_stream_path_is_epoch_and_proc_tagged():
    assert stream_path("/r/t.jsonl", 3) == "/r/t.jsonl.e0p3.jsonl"
    assert stream_path("/r/t.jsonl", 1, epoch=2) == "/r/t.jsonl.e2p1.jsonl"


def test_chrome_export_wraps_all_events(tmp_path):
    p = str(tmp_path / "t.e0p0.jsonl")
    tr = Tracer(p)
    tr.instant("x")
    tr.close()
    evs = _events(p)
    doc = to_chrome(evs)
    assert doc["traceEvents"] == evs
    json.dumps(doc)  # must be serializable as a chrome trace document


# -- executor + controller integration ----------------------------------------

def _traced_run(tmp_path, **kw):
    base = str(tmp_path / "trace.jsonl")
    tr = Tracer(stream_path(base, 0), proc_id=0)
    key = jax.random.PRNGKey(3)
    params0, loss_fn, daso_data, _ = make_mlp_problem(key)
    cfg = TrainLoopConfig(strategy="daso", n_steps=kw.pop("n_steps", 24),
                          n_replicas=2, b_max=4, loss_window=50,
                          executor="macro", **kw)
    result = run_training(loss_fn, params0, daso_data, cfg,
                          optimizer=sgd(momentum=0.9),
                          lr_fn=constant_lr(0.05), log=None, tracer=tr)
    tr.close()
    merge_streams(base)
    return result, _events(base)


def test_executor_cycle_spans_carry_sync_counts(tmp_path):
    result, evs = _traced_run(tmp_path)
    cycles = [ev for ev in evs
              if ev["name"] == "cycle" and ev["ph"] == "X"]
    assert cycles
    assert sum(ev["args"]["steps"] for ev in cycles) == 24
    # the span args carry the per-level sync counts the drift fit needs
    outer = sum(ev["args"]["syncs"].get("_outer", 0) for ev in cycles)
    assert outer == result.controller.level_sync_counts()["_outer"]
    # lazy jit: compile cost lands inside the first cycle span of a shape
    assert cycles[0]["args"]["fresh_compile"] is True
    fresh = sum(ev["args"]["fresh_compile"] for ev in cycles)
    compiles = [ev for ev in evs if ev["name"] == "compile"]
    assert len(compiles) == result.executor_stats.compiles
    assert 1 <= fresh <= len(compiles)
    # no placement: the replica axis stays on one device, mean leaf-wise
    assert {ev["args"]["exchange"] for ev in compiles} == {"leafwise"}
    for ev in evs:
        assert validate_event(ev) is None, ev


def test_overlap_run_emits_exchange_leg_spans(tmp_path):
    _, evs = _traced_run(tmp_path, overlap="one_cycle")
    names = {ev["name"] for ev in evs if ev["ph"] == "X"}
    assert {"ov_compute", "ov_exchange_visible", "ov_merge"} <= names


def test_checkpoint_save_span(tmp_path):
    _, evs = _traced_run(tmp_path, ckpt_every=8,
                         ckpt_dir=str(tmp_path / "ck"))
    saves = [ev for ev in evs if ev["name"] == "checkpoint_save"]
    assert saves and all(ev["cat"] == "checkpoint" for ev in saves)
    assert saves[0]["args"]["step"] >= 0


def _plateau_controller(tracer):
    cfg = DasoConfig(n_replicas=2, global_world=4, b_max=4, warmup_steps=0,
                     cooldown_steps=0, total_steps=10_000,
                     plateau_patience=1)
    c = DasoController(cfg, loss_window=2)
    c.tracer = tracer
    return c


def test_controller_plateau_events_have_reasons(tmp_path):
    p = str(tmp_path / "t.e0p0.jsonl")
    tr = Tracer(p)
    c = _plateau_controller(tr)
    for _ in range(40):  # constant loss: every window is a plateau
        c.observe_loss(1.0)
    c.notify_membership_change(step=80, n_active=3)
    c.notify_dcn_scale(0.25, step=81)
    c.notify_dcn_scale(1.0, step=82)
    tr.close()
    evs = _events(p)
    bw = [ev for ev in evs if ev["name"] == "bw_change"]
    reasons = {ev["args"]["reason"] for ev in bw}
    # B halves 4->2->1 then resets: both reason codes appear
    assert reasons == {"plateau_halve", "plateau_reset"}
    halve = next(ev for ev in bw if ev["args"]["reason"] == "plateau_halve")
    assert halve["args"]["b_to"] == halve["args"]["b_from"] // 2
    assert all(ev["cat"] == "schedule" for ev in bw)
    mem = next(ev for ev in evs if ev["name"] == "membership_change")
    assert mem["args"] == {"reason": "plateau_stats_flushed", "step": 80,
                           "n_active": 3}
    dcn = [ev["args"]["reason"] for ev in evs if ev["name"] == "dcn_scale"]
    assert dcn == ["dcn_degraded", "dcn_recovered"]


def test_controller_tracer_never_enters_checkpoints(tmp_path):
    tr = Tracer(str(tmp_path / "t.e0p0.jsonl"))
    c = _plateau_controller(tr)
    for _ in range(6):
        c.observe_loss(1.0)
    sd = c.state_dict()
    assert "tracer" not in sd
    json.dumps(sd)  # checkpoint payload must stay JSON-serializable
    c2 = _plateau_controller(None)
    c2.tracer = None
    c2.load_state_dict(sd)  # and load never expects one
    tr.close()


# -- meters: per-level communication accounting -------------------------------

def _history(modes):
    return [(i, m, 4, 1) for i, m in enumerate(modes)]


def test_outer_sync_split():
    h = _history(["blocking", "local", "send", "send_receive+host",
                  "ov_sync~2", "hard_avg", "local"])
    assert meters.outer_sync_split(h) == {"blocking": 2, "nonblocking": 3}
    assert meters.outer_sync_split([]) == {"blocking": 0, "nonblocking": 0}


@pytest.mark.parametrize("name", ["gossip", "downpour"])
def test_meters_account_baseline_strategy_traffic(name):
    """Every exchange the gossip/downpour controllers emit lands in the
    outer meter row — exchange tokens price at the nonblocking tier,
    warm-up/cool-down at the blocking tier, and the row's sync count
    equals the history's non-local step count (no orphan bytes)."""
    n_steps = 20
    key = jax.random.PRNGKey(11)
    params0, loss_fn, daso_data, _ = make_mlp_problem(key)
    cfg = TrainLoopConfig(strategy=name, n_steps=n_steps, n_replicas=2,
                          local_world=2, b_max=4, lr=0.1, loss_window=10)
    res = run_training(loss_fn, params0, daso_data, cfg, log=None)
    ctl = res.controller
    n_exchanges = sum(1 for (_, m, _, _) in ctl.history if m != "local")
    assert n_exchanges > 0
    split = meters.outer_sync_split(ctl.history)
    # the strategy's own exchange token (gossip~s / push) is classified
    # nonblocking; the warm-up/cool-down averages blocking; nothing falls
    # through unpriced
    assert split["nonblocking"] > 0 and split["blocking"] > 0
    assert split["blocking"] + split["nonblocking"] == n_exchanges
    counts = ctl.level_sync_counts()
    assert counts == {"_outer": n_exchanges}
    rows = meters.level_bytes_report(res.params, counts, ctl.cfg,
                                     outer_split=split)
    assert sum(r.syncs for r in rows) == n_exchanges
    assert all(r.bytes_per_sync > 0 for r in rows)
    flat = meters.rows_as_counter(rows)
    priced = sum(v for k, v in flat.items() if k.endswith(".syncs"))
    assert priced == n_exchanges


def test_level_bytes_report_splits_outer_by_wire_tier():
    from repro.core.compression import transfer_bytes
    from repro.topo import TopologySpec
    params = {"w": jnp.ones((8, 4)), "b": jnp.ones((4,))}
    cfg = DasoConfig(n_replicas=4, global_world=4)  # bf16 blocking, f32 async
    spec = TopologySpec.load("chip:1 x host:2 x pod:2")
    counts = {"_outer": 5, "host": 9}
    rows = meters.level_bytes_report(
        params, counts, cfg, topo=spec,
        outer_split={"blocking": 2, "nonblocking": 3})
    by = {(r.level, r.variant): r for r in rows}
    blk = by[("_outer", "blocking")]
    nb = by[("_outer", "nonblocking")]
    assert (blk.syncs, nb.syncs) == (2, 3)
    assert blk.wire_format == "bf16" and nb.wire_format == "f32"
    assert blk.bytes_per_sync == transfer_bytes(params, wire_format="bf16")
    assert nb.bytes_per_sync == 2 * blk.bytes_per_sync
    assert blk.total_bytes == 2 * blk.bytes_per_sync
    inner = by[("host", "")]
    assert (inner.syncs, inner.group_size) == (9, 2)
    # forced wire format: one outer row, no split
    import dataclasses
    forced = dataclasses.replace(cfg, wire_format="f32")
    rows2 = meters.level_bytes_report(params, counts, forced, topo=spec,
                                      outer_split={"blocking": 2,
                                                   "nonblocking": 3})
    assert [r for r in rows2 if r.level == "_outer"][0].syncs == 5


def test_level_bytes_report_keeps_orphan_levels():
    cfg = DasoConfig(n_replicas=2, global_world=2)
    rows = meters.level_bytes_report({"w": jnp.ones((4,))},
                                     {"_outer": 3, "host": 7}, cfg)
    orphan = [r for r in rows if r.level == "host"][0]
    assert (orphan.syncs, orphan.group_size) == (7, 0)


def test_rows_as_counter_flattens():
    r = meters.LevelMeter("_outer", 3, "bf16", 4, 100, variant="blocking")
    flat = meters.rows_as_counter([r])
    assert flat == {"_outer.blocking.syncs": 3.0,
                    "_outer.blocking.bytes_per_sync": 100.0,
                    "_outer.blocking.total_bytes": 300.0}


def test_crosscheck_hlo_picks_matching_variant():
    rows = [meters.LevelMeter("_outer", 2, "bf16", 4, 544,
                              variant="blocking"),
            meters.LevelMeter("_outer", 3, "f32", 4, 1088,
                              variant="nonblocking"),
            meters.LevelMeter("chip", 9, "f32", 2, 1088)]
    hlo = {"all-reduce@pod": {"bytes": 2176, "count": 2},   # 1088/op
           "all-reduce@chip": {"bytes": 9792, "count": 9},  # 1088/op
           "_total": {"bytes": 0, "count": 0}}              # ignored
    verdicts = {v["level"]: v for v in meters.crosscheck_hlo(rows, hlo)}
    # auto axis map: chip -> chip, _outer -> the unclaimed axis (pod)
    assert verdicts["_outer"]["axis"] == "pod"
    assert verdicts["_outer"]["variant"] == "nonblocking"
    assert verdicts["_outer"]["ok"] is True
    assert verdicts["chip"]["ok"] is True
    # a mispriced meter fails the check
    bad = [meters.LevelMeter("chip", 9, "f32", 2, 2000)]
    v = meters.crosscheck_hlo(bad, hlo)[0]
    assert v["ok"] is False and v["rel_err"] > 0.05


def test_crosscheck_hlo_reports_unmatched_levels():
    rows = [meters.LevelMeter("_outer", 0, "f32", 2, 100)]
    v = meters.crosscheck_hlo(rows, {})[0]
    assert v["ok"] is None and v["hlo_bytes"] is None


# -- heartbeat wire-format schema ---------------------------------------------

def test_heartbeat_schema_roundtrip(tmp_path):
    from repro.resilience.runtime import (HealthConfig, HealthMonitor,
                                          read_heartbeat,
                                          validate_heartbeat)
    cfg = HealthConfig(run_dir=str(tmp_path), epoch=2, watchdog_s=60.0)
    mon = HealthMonitor(cfg, proc_id=1).start()
    mon.phase("train")
    mon.cycle_done(12)
    mon.close()
    doc = read_heartbeat(str(tmp_path), 2, 1)
    assert doc is not None
    assert validate_heartbeat(doc) is None
    assert doc["phase"] == "done" and doc["step"] == 12
    assert (doc["proc"], doc["epoch"]) == (1, 2)
    # extra keys are tolerated in BOTH directions: a newer writer's beat
    # still validates under this reader's schema
    doc["future_key"] = {"x": 1}
    assert validate_heartbeat(doc) is None


@pytest.mark.parametrize("mutate,frag", [
    (lambda d: d.pop("phase"), "missing required key 'phase'"),
    (lambda d: d.update(phase=""), "bad value for 'phase'"),
    (lambda d: d.update(proc=-1), "bad value for 'proc'"),
    (lambda d: d.update(step="4"), "bad value for 'step'"),
    (lambda d: d.update(t=-1.0), "bad value for 't'"),
])
def test_heartbeat_schema_rejects(mutate, frag):
    from repro.resilience.runtime import validate_heartbeat
    doc = {"proc": 0, "epoch": 0, "phase": "train", "step": 3, "t": 1.5}
    assert validate_heartbeat(doc) is None
    mutate(doc)
    err = validate_heartbeat(doc)
    assert err is not None and frag in err
    assert "not an object" in validate_heartbeat([doc])


def test_health_monitor_phase_events_reach_trace(tmp_path):
    from repro.resilience.runtime import HealthConfig, HealthMonitor
    tr = Tracer(str(tmp_path / "t.e0p0.jsonl"))
    cfg = HealthConfig(run_dir=str(tmp_path / "hb"), watchdog_s=60.0)
    mon = HealthMonitor(cfg, proc_id=0, tracer=tr).start()
    mon.phase("train")
    mon.close()
    tr.close()
    phases = [ev["args"]["phase"] for ev in _events(tr.path)
              if ev["name"] == "phase"]
    assert phases == ["train", "done"]


# -- trace_report: cycle-cost fit and drift table -----------------------------

def _cycle_span(steps, syncs, dur_s, **flags):
    return {"name": "cycle", "cat": "executor", "ph": "X", "ts": 0,
            "dur": int(dur_s * 1e6), "pid": 0, "tid": 0,
            "args": {"start_step": 0, "steps": steps, "syncs": syncs,
                     "fresh_compile": False, "fallback": False, **flags}}


def _synthetic_trace(t_step=0.010, t_outer=0.040, t_chip=0.005):
    """Cycle spans whose durations obey the fit model EXACTLY, with
    enough sync-count variation to determine every coefficient."""
    def dur(steps, syncs):
        return (steps * t_step + syncs.get("_outer", 0) * t_outer
                + syncs.get("chip", 0) * t_chip)
    cycles = [(4, {"_outer": 1, "chip": 4}), (4, {"_outer": 0, "chip": 4}),
              (2, {"_outer": 1, "chip": 0}), (8, {"_outer": 2, "chip": 8}),
              (1, {"_outer": 0, "chip": 1})]
    evs = [{"name": RUN_METADATA, "cat": "meta", "ph": "i", "s": "p",
            "ts": 0, "pid": 0, "tid": 0,
            "args": {"arch": "mlp", "topology": "chip:2 x pod:2",
                     "param_bytes": 4 * 1024 ** 2, "b_max": 4,
                     "wire_format": "bf16", "n_replicas": 2,
                     "local_world": 2}}]
    # a compile cycle with an absurd duration: must be excluded, not fit
    evs.append(_cycle_span(4, {"_outer": 1, "chip": 4}, 60.0,
                           fresh_compile=True))
    evs.extend(_cycle_span(s, sy, dur(s, sy)) for s, sy in cycles)
    return evs


def test_fit_cycle_costs_recovers_exact_coefficients():
    tr = _report_mod()
    fit = tr.fit_cycle_costs(_synthetic_trace())
    assert fit["samples"] == 5 and fit["excluded"] == 1
    assert fit["t_step_s"] == pytest.approx(0.010, rel=1e-6)
    assert fit["levels"]["_outer"] == pytest.approx(0.040, rel=1e-6)
    assert fit["levels"]["chip"] == pytest.approx(0.005, rel=1e-6)
    assert fit["residual_frac"] == pytest.approx(0.0, abs=1e-9)
    assert "note" not in fit


def test_fit_cycle_costs_underdetermined_is_flagged():
    tr = _report_mod()
    evs = [_cycle_span(4, {"_outer": 1}, 0.05)]
    fit = tr.fit_cycle_costs(evs)
    assert fit["t_step_s"] is None and "note" in fit
    assert tr.fit_cycle_costs([]) is None


def test_drift_table_covers_every_sync_level():
    tr = _report_mod()
    evs = _synthetic_trace()
    drift = tr.drift_table(evs)
    assert drift is not None
    # "chip:2 x pod:2" has exactly one sync level above the gradient
    # all-reduce: the pod exchange, keyed "_outer" in the fit
    levels = {row["level"]: row for row in drift}
    assert "pod" in levels
    pod = levels["pod"]
    assert pod["model_sync_s"] > 0
    assert pod["measured_sync_s"] == pytest.approx(0.040, rel=1e-6)
    assert pod["drift_x"] == pytest.approx(
        pod["measured_sync_s"] / pod["model_sync_s"], rel=1e-9)
    # every topology sync level appears even if unmeasured, and fit
    # levels the spec does not name are appended rather than dropped
    assert all(row["measured_sync_s"] is not None or row["period"]
               for row in drift)
    assert any(row["level"] == "chip" for row in drift)


def test_drift_table_requires_metadata():
    tr = _report_mod()
    assert tr.drift_table([_cycle_span(4, {"_outer": 1}, 0.05)]) is None


def test_build_report_end_to_end(tmp_path):
    tr = _report_mod()
    _, evs = _traced_run(tmp_path)
    rep = tr.build_report(evs)
    assert rep["schema_errors"] == []
    assert rep["summary"]["executor"]["spans"] > 0
    assert "_tracer" not in rep["summary"]
    assert rep["cycle_fit"]["samples"] >= 0
    json.dumps(rep)  # --json output contract
