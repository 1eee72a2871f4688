#!/usr/bin/env python
"""Perf-regression gate over the committed BENCH_*.json records.

CI regenerates each record from scratch (bench smoke) and then runs this
gate against the version committed in the repo: instead of merely
uploading artifacts, the job FAILS when a fresh record regresses past
tolerance. Three kinds of checks per record, declared in POLICIES:

  * exact     — structural facts that must never move (collective counts,
                schedule shapes, zero resume deltas). Always hard.
  * bounds    — machine-independent absolute bounds (byte ratios, quality
                deltas): `(min, max)`, either side None.
  * baseline  — machine-RELATIVE comparison against the committed value:
                `("higher"|"lower", rel_tol)` — a fresh "higher is better"
                metric must be >= committed * (1 - rel_tol). Tolerances
                are wide because CI runners differ from the machines that
                produced the committed records; the gated metrics are
                same-machine ratios (fused-vs-per-leaf speedup, degraded
                exchange cost), which travel much better than wall-clock.

Usage (what .github/workflows/ci.yml runs):

    cp BENCH_exchange.json /tmp/baseline/          # before the bench rm
    python -m benchmarks.run --only exchange --quick
    python tools/check_bench.py --baseline-dir /tmp/baseline \
        --fresh-dir . --records BENCH_exchange.json

Exit status 0 = no regression; 1 = any check failed (each failure is
printed). To see the gate catch a regression, tamper with a fresh value:
`python tools/check_bench.py --self-test` does exactly that in-memory.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys

# key -> ("exact", value-from-baseline?) | ("bounds", (lo, hi))
#     | ("bounds_strict", (lo, hi)) | ("baseline", (direction, rel_tol))
#     | ("custom", check-name)
# "exact" with None compares against the BASELINE record's value. Step-count
# -dependent values must NOT use "exact"/None: CI regenerates records with
# --quick (shorter runs) while the committed baselines are full runs.
# "bounds" is inclusive; "bounds_strict" fails AT the bound too — for
# invariants like "recovery took measurable time" (> 0) and "the hierarchy
# still pays off" (< 1).
CUSTOM_CHECKS = {
    # every level of the 3-level schedule actually synced
    "sync_counts_positive": lambda v: (
        None if isinstance(v, dict) and v and all(c > 0 for c in v.values())
        else f"expected positive per-level sync counts, got {v!r}"),
}

POLICIES = {
    "BENCH_exchange.json": {
        "all_reduce_ops_fused": ("exact", 1),
        "all_reduce_ops_per_leaf": ("bounds", (2, None)),
        "int8_vs_bf16_bytes": ("bounds_strict", (None, 0.52)),
        # fused arena must stay a win over per-leaf on the same machine
        "fused_speedup_f32": ("baseline", ("higher", 0.5)),
        "fused_speedup_bf16": ("baseline", ("higher", 0.5)),
    },
    "BENCH_resilience.json": {
        "resume_param_delta": ("exact", 0.0),
        "resume_loss_delta": ("exact", 0.0),
        "invalidations_per_membership_event": ("exact", 1.0),
        "loss_delta_k1": ("bounds", (-0.5, 0.5)),
        "loss_delta_k2": ("bounds", (-0.5, 0.5)),
        "recovery_s_mean": ("bounds_strict", (0.0, None)),
        "degraded_exchange_cost_ratio": ("baseline", ("higher", 0.25)),
        # live fault plane (real SIGKILL + supervised regroup): recovered
        # params must equal the simulated oracle EXACTLY, detection must
        # land inside the watchdog budget, and each recovery phase must
        # have measurable (nonzero) cost
        "live_oracle_param_delta": ("exact", 0.0),
        "live_detect_within_budget": ("exact", 1.0),
        "live_detect_s": ("bounds_strict", (0.0, None)),
        "live_regroup_s": ("bounds_strict", (0.0, None)),
        "live_resume_s": ("bounds_strict", (0.0, None)),
    },
    "BENCH_overlap.json": {
        # at least one macro-cycle actually ran the overlap dispatch path
        "overlap_cycles": ("bounds_strict", (0, None)),
        # the headline claim: the overlap executor hides >= 30% of the
        # measured blocking exchange time on the real 2-process gloo
        # runtime (visible-after-compute vs blocked-before-compute legs)
        "overlap_hidden_fraction": ("bounds", (0.3, None)),
        # serial_exchange changes host waiting, never numerics
        "loss_delta_overlap_vs_serial": ("exact", 0.0),
        # one-cycle-stale merge may move the loss, but boundedly
        "loss_delta_overlap_vs_off": ("bounds", (-0.5, 0.5)),
        # analytic model: overlap never prices above the blocking schedule
        "model_step_ratio_overlap_vs_blocking": ("bounds_strict", (None, 1.0)),
    },
    "BENCH_strategies.json": {
        # the whole registered family ran, stayed finite, and trained
        "n_strategies": ("exact", 6.0),
        "registry_covers_all": ("exact", 1.0),
        "all_finite": ("exact", 1.0),
        "trains_all": ("exact", 1.0),
        # macro executor == per-step reference across every strategy
        "macro_vs_per_step_max_delta": ("bounds", (None, 1e-4)),
        # gossip's single partner copy must strictly undercut the sync
        # ring, in wire bytes AND modeled step time; the periodic family
        # amortizes its ring over B, so it must undercut sync too
        "bytes_per_step_gossip_vs_sync": ("bounds_strict", (None, 1.0)),
        "bytes_per_step_easgd_vs_sync": ("bounds_strict", (None, 1.0)),
        "bytes_per_step_downpour_vs_sync": ("bounds_strict", (None, 1.0)),
        "model_step_ratio_gossip_vs_sync": ("bounds_strict", (None, 1.0)),
        "model_step_ratio_daso_vs_sync": ("bounds_strict", (None, 1.0)),
    },
    "BENCH_tuning.json": {
        # the self-tuning headline: a tuned run that DISCOVERS a DCN
        # degradation by probing must finish strictly cheaper on the
        # simulated clock than a static run that never learns of it
        "tuned_vs_static_sim_time_ratio": ("bounds_strict", (None, 1.0)),
        # ...and discover it within K <= 3 probe cycles of the event
        "adapt_cycles": ("bounds", (None, 3)),
        "retune_events": ("bounds_strict", (0, None)),
        # autotune on a healthy cluster (measured == nominal) is a
        # bit-exact no-op: the probe never perturbs numerics
        "noop_retune_param_delta": ("exact", 0.0),
        "noop_retune_loss_delta": ("exact", 0.0),
        # skew-sorted groups waste strictly less inner-barrier wait
        "reshuffle_wait_ratio": ("bounds_strict", (None, 1.0)),
    },
    "BENCH_topology.json": {
        "two_level_param_delta": ("exact", 0.0),
        "two_level_loss_delta": ("exact", 0.0),
        "three_level_inner_periods": ("exact", None),
        "three_level_sync_counts": ("custom", "sync_counts_positive"),
        # hierarchy must keep paying off when the DCN degrades
        "analytic_step_ratio_3v2_degraded_dcn": ("bounds_strict", (None, 1.0)),
        "analytic_step_ratio_3v2": ("baseline", ("lower", 0.25)),
    },
}


def check_record(name: str, fresh: dict, baseline: dict, *,
                 expect_quick: bool = False) -> list:
    failures = []
    if expect_quick and fresh.get("config", {}).get("quick") is not True:
        failures.append(f"{name}: fresh record was not generated with "
                        "--quick (a crashed quick bench must not be "
                        "papered over by a stale full-mode record)")
    fd, bd = fresh.get("derived", {}), baseline.get("derived", {})
    for key, (kind, arg) in POLICIES[name].items():
        if key not in fd:
            failures.append(f"{name}: fresh record lacks {key!r}")
            continue
        v = fd[key]
        if kind == "exact":
            want = bd.get(key) if arg is None else arg
            if v != want:
                failures.append(f"{name}: {key} = {v!r}, expected {want!r}")
        elif kind in ("bounds", "bounds_strict"):
            lo, hi = arg
            strict = kind == "bounds_strict"
            if lo is not None and (v <= lo if strict else v < lo):
                failures.append(f"{name}: {key} = {v} "
                                f"{'<=' if strict else '<'} floor {lo}")
            if hi is not None and (v >= hi if strict else v > hi):
                failures.append(f"{name}: {key} = {v} "
                                f"{'>=' if strict else '>'} ceiling {hi}")
        elif kind == "custom":
            err = CUSTOM_CHECKS[arg](v)
            if err is not None:
                failures.append(f"{name}: {key}: {err}")
        elif kind == "baseline":
            if key not in bd:
                failures.append(f"{name}: baseline lacks {key!r}")
                continue
            direction, tol = arg
            ref = bd[key]
            if direction == "higher" and v < ref * (1 - tol):
                failures.append(
                    f"{name}: {key} regressed: {v:.4g} < committed "
                    f"{ref:.4g} * (1 - {tol}) — perf regression")
            if direction == "lower" and v > ref * (1 + tol):
                failures.append(
                    f"{name}: {key} regressed: {v:.4g} > committed "
                    f"{ref:.4g} * (1 + {tol}) — perf regression")
    return failures


def self_test() -> int:
    """Prove the gate fails on an injected regression (run locally and in
    CI once per change to this file)."""
    base = {"derived": {
        "all_reduce_ops_fused": 1, "all_reduce_ops_per_leaf": 112,
        "int8_vs_bf16_bytes": 0.51, "fused_speedup_f32": 1.79,
        "fused_speedup_bf16": 1.70}}
    ok = check_record("BENCH_exchange.json", copy.deepcopy(base), base)
    if ok:
        print("self-test: clean record unexpectedly failed:", ok)
        return 1
    bad = copy.deepcopy(base)
    bad["derived"]["fused_speedup_f32"] = 0.6   # injected perf regression
    bad["derived"]["all_reduce_ops_fused"] = 3  # injected structural break
    fails = check_record("BENCH_exchange.json", bad, base)
    if len(fails) != 2:
        print("self-test: injected regression not caught:", fails)
        return 1
    print("self-test OK: injected regression caught:")
    for f in fails:
        print("  ", f)
    return 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--baseline-dir", default=None,
                    help="directory holding the committed records")
    ap.add_argument("--fresh-dir", default=".",
                    help="directory holding the regenerated records")
    ap.add_argument("--records", nargs="+", default=sorted(POLICIES),
                    help="which BENCH_*.json files to gate")
    ap.add_argument("--expect-quick", action="store_true",
                    help="require fresh records to carry config.quick == "
                         "true (CI regenerates with --quick; this catches "
                         "a stale full-mode record standing in for a "
                         "crashed bench)")
    ap.add_argument("--self-test", action="store_true",
                    help="verify the gate catches an injected regression")
    args = ap.parse_args()
    if args.self_test:
        sys.exit(self_test())
    if args.baseline_dir is None:
        ap.error("--baseline-dir is required (or use --self-test)")

    failures = []
    for name in args.records:
        if name not in POLICIES:
            failures.append(f"no gate policy for {name!r} "
                            f"(known: {sorted(POLICIES)})")
            continue
        fresh_p = os.path.join(args.fresh_dir, name)
        base_p = os.path.join(args.baseline_dir, name)
        try:
            with open(fresh_p) as f:
                fresh = json.load(f)
        except OSError as e:
            failures.append(f"{name}: fresh record unreadable: {e}")
            continue
        try:
            with open(base_p) as f:
                baseline = json.load(f)
        except OSError as e:
            failures.append(f"{name}: committed baseline unreadable: {e}")
            continue
        fails = check_record(name, fresh, baseline,
                             expect_quick=args.expect_quick)
        status = "FAIL" if fails else "ok"
        print(f"[check_bench] {name}: {status} "
              f"({len(POLICIES[name])} checks)")
        failures.extend(fails)
    for f in failures:
        print("  REGRESSION:", f)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
