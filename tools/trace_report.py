#!/usr/bin/env python
"""Run-trace inspector: validate, export to Chrome/Perfetto, and print the
measured-vs-model drift table.

Input is a merged run trace written by ``--trace-out`` (or the un-merged
``PATH.e*p*.jsonl`` streams of a crashed/aborted run — they are merged in
memory). Three outputs:

  * **summary** — event counts and total span time per category
    (executor / schedule / resilience / checkpoint).
  * **Chrome export** (``--chrome out.json``) — wraps the events in a
    ``{"traceEvents": [...]}`` document that chrome://tracing and
    https://ui.perfetto.dev load directly (Open trace file).
  * **drift table** (default) — regresses per-level sync costs out of the
    cycle spans and compares them against `benchmarks/comm_model.py`
    predictions for the run's topology. Each non-compile cycle span obeys

        dur ≈ n_steps * t_step + Σ_level n_syncs_level * t_level

    with (n_steps, n_syncs) carried in the span args, so a least-squares
    fit over all cycles yields the measured per-step compute time and the
    measured marginal cost of one sync at EVERY level — exactly the
    readings the ROADMAP's self-tuning controller needs, and the numbers
    the analytic model must be confronted with. Fresh-compile and
    fallback cycles are excluded (their duration is dominated by XLA).

Usage:

    python tools/trace_report.py runs/trace.jsonl
    python tools/trace_report.py runs/trace.jsonl --chrome trace_ui.json
    python tools/trace_report.py runs/trace.jsonl --validate
    python tools/trace_report.py runs/trace.jsonl --json report.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)                      # benchmarks.comm_model
sys.path.insert(0, os.path.join(_REPO, "src"))  # repro

from repro.obs.trace import (RUN_METADATA, load_events, to_chrome,  # noqa: E402
                             validate_event)


def validate(events: List[dict]) -> List[str]:
    """Schema errors over a whole trace (empty list = valid)."""
    errors = []
    for i, ev in enumerate(events):
        err = validate_event(ev)
        if err is not None:
            errors.append(f"event {i}: {err}")
    return errors


def run_metadata(events: List[dict]) -> Optional[dict]:
    """The run_metadata args (first occurrence — every process emits an
    identical copy)."""
    for ev in events:
        if ev.get("name") == RUN_METADATA:
            return ev.get("args") or {}
    return None


def summarize(events: List[dict]) -> Dict[str, dict]:
    """Per-category event counts and total span seconds."""
    out: Dict[str, dict] = {}
    for ev in events:
        cat = ev.get("cat", "?")
        agg = out.setdefault(cat, {"events": 0, "spans": 0, "span_s": 0.0})
        agg["events"] += 1
        if ev.get("ph") == "X":
            agg["spans"] += 1
            agg["span_s"] += ev.get("dur", 0) / 1e6
    return out


def fit_cycle_costs(events: List[dict]) -> Optional[dict]:
    """Least-squares decomposition of cycle durations into per-step and
    per-level-sync costs.

    Every clean cycle span (no fresh compile, no fallback) is one sample
    of ``dur = steps * t_step + Σ n_syncs_l * t_l``; samples from all
    processes pool into one fit (each process dispatches the same cycles,
    so they are repeated measurements of the same costs). Returns
    ``{"t_step_s", "levels": {name: t_sync_s}, "samples", "excluded",
    "residual_frac"}`` or None when no clean cycle carries sync args.
    Negative coefficients are clamped to 0 in the output (a level whose
    syncs are fully hidden by overlap can fit slightly negative) — the
    raw value is kept under "raw"."""
    rows = []
    for ev in events:
        if ev.get("name") != "cycle" or ev.get("ph") != "X":
            continue
        args = ev.get("args") or {}
        if "syncs" not in args or "steps" not in args:
            continue
        rows.append((args, ev.get("dur", 0) / 1e6,
                     args.get("fresh_compile") or args.get("fallback")))
    if not rows:
        return None
    levels = sorted({name for args, _, _ in rows
                     for name in args["syncs"]})
    clean = [(a, d) for a, d, excl in rows if not excl]
    excluded = len(rows) - len(clean)
    if len(clean) < 1 + len(levels):
        return {"t_step_s": None, "levels": {}, "samples": len(clean),
                "excluded": excluded, "residual_frac": None,
                "note": f"{len(clean)} clean cycle(s) cannot determine "
                        f"{1 + len(levels)} coefficients"}
    X = np.array([[a["steps"]] + [a["syncs"].get(n, 0) for n in levels]
                  for a, _ in clean], dtype=float)
    y = np.array([d for _, d in clean])
    coef, _, rank, _ = np.linalg.lstsq(X, y, rcond=None)
    resid = float(np.abs(X @ coef - y).sum() / max(y.sum(), 1e-12))
    fit = {"t_step_s": max(float(coef[0]), 0.0),
           "levels": {n: max(float(c), 0.0)
                      for n, c in zip(levels, coef[1:])},
           "raw": {"t_step_s": float(coef[0]),
                   **{n: float(c) for n, c in zip(levels, coef[1:])}},
           "samples": len(clean), "excluded": excluded,
           "residual_frac": resid, "rank": int(rank)}
    if rank < 1 + len(levels):
        fit["note"] = ("rank-deficient fit: some sync counts never vary "
                       "independently across cycles")
    return fit


def _spec_from_meta(meta: dict):
    """The run's TopologySpec: the explicit spec from metadata, or the
    implicit 2-level chip/pod shape of a --nodes run (default per-depth
    bandwidths — the same defaults the model would have used)."""
    from repro.topo import TopologySpec
    if meta.get("topology"):
        return TopologySpec.load(meta["topology"])
    return TopologySpec.load(
        f"chip:{meta.get('local_world', 1)} x pod:{meta.get('n_replicas', 2)}")


def drift_table(events: List[dict], *,
                fit: Optional[dict] = None) -> Optional[List[dict]]:
    """Measured-vs-model rows, one per sync level of the run's topology.

    Measured values come from `fit_cycle_costs`; model values from
    `benchmarks.comm_model.topology_level_costs` under the run's wire
    format and parameter bytes (run_metadata). Levels whose measured
    coefficient is unavailable (zero syncs recorded, or a rank-deficient
    fit) still get a row with ``measured_s=None`` — coverage over every
    sync level is the point. Level 0 (the intra-replica gradient
    all-reduce) is not a sync level: it rides inside t_step."""
    from benchmarks.comm_model import topology_level_costs

    meta = run_metadata(events)
    if meta is None or not meta.get("param_bytes"):
        return None
    if fit is None:
        fit = fit_cycle_costs(events)
    spec = _spec_from_meta(meta)
    wire = meta.get("wire_format") or "bf16"
    model_rows = topology_level_costs(spec, float(meta["param_bytes"]),
                                      b_max=meta.get("b_max", 4),
                                      wire_format=wire)
    measured = dict(fit["levels"]) if fit else {}
    # the fit keys sync levels by controller name: "_outer" for the
    # outermost, the level's own name for inner levels
    out = []
    for row in model_rows[1:]:  # skip level 0: per-step, not per-sync
        key = "_outer" if row["name"] == spec.outer.name else row["name"]
        m = measured.pop(key, None)
        out.append({"level": row["name"], "members": row["members"],
                    "wire": row["wire"], "period": row["period"],
                    "model_sync_s": row["sync_s"],
                    "measured_sync_s": m,
                    "drift_x": (m / row["sync_s"]
                                if m is not None and row["sync_s"] > 0
                                else None)})
    for key, m in measured.items():  # fit levels the spec no longer names
        out.append({"level": key, "members": None, "wire": None,
                    "period": None, "model_sync_s": None,
                    "measured_sync_s": m, "drift_x": None})
    return out


def build_report(events: List[dict]) -> dict:
    """Everything the CLI prints, as one JSON-serializable dict (the
    benchmarks and the CI trace-smoke lane consume this via --json)."""
    errors = validate(events)
    fit = fit_cycle_costs(events)
    drift = drift_table(events, fit=fit)
    return {"n_events": len(events),
            "schema_errors": errors,
            "metadata": run_metadata(events),
            "summary": summarize(events),
            "cycle_fit": fit,
            "drift": drift}


def _fmt_s(v) -> str:
    return "      --" if v is None else f"{v * 1e3:8.3f}"


def print_report(rep: dict, *, out=sys.stdout) -> None:
    p = lambda *a: print(*a, file=out)
    meta = rep["metadata"] or {}
    p(f"trace: {rep['n_events']} events, "
      f"{len(rep['schema_errors'])} schema error(s)")
    if meta:
        p(f"run: arch={meta.get('arch')} strategy={meta.get('strategy')} "
          f"steps={meta.get('steps')} procs={meta.get('procs')} "
          f"topology={meta.get('topology') or 'implicit'}")
    p("\nper-category:")
    for cat, agg in sorted(rep["summary"].items()):
        p(f"  {cat:<11} {agg['events']:>5} events  "
          f"{agg['spans']:>4} spans  {agg['span_s']:8.3f} s")
    fit = rep["cycle_fit"]
    if fit:
        p(f"\ncycle fit: {fit['samples']} clean cycles "
          f"({fit['excluded']} compile/fallback excluded), "
          f"t_step={_fmt_s(fit['t_step_s'])} ms, "
          f"residual={fit['residual_frac']:.1%}"
          if fit.get("residual_frac") is not None else
          f"\ncycle fit: {fit.get('note', 'unavailable')}")
        if fit.get("note") and fit.get("residual_frac") is not None:
            p(f"  note: {fit['note']}")
    if rep["drift"]:
        p("\ndrift table (per-level sync cost, measured vs comm_model):")
        p(f"  {'level':<10} {'members':>7} {'wire':>5} {'period':>6} "
          f"{'model ms':>9} {'meas ms':>9} {'drift':>7}")
        for row in rep["drift"]:
            drift = (f"{row['drift_x']:6.2f}x" if row["drift_x"] is not None
                     else "     --")
            p(f"  {row['level']:<10} {str(row['members']):>7} "
              f"{str(row['wire']):>5} {str(row['period']):>6} "
              f"{_fmt_s(row['model_sync_s'])} "
              f"{_fmt_s(row['measured_sync_s'])} {drift}")
        p("  (drift > 1: the wire is slower than modeled — recalibrate "
          "ClusterModel bandwidths; ~1: the model holds)")
    elif rep["metadata"] is None:
        p("\nno run_metadata event: drift table unavailable (trace written "
          "without --trace-out's entry-point metadata?)")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", help="merged run trace (or the base path of "
                                  "un-merged .e*p*.jsonl streams)")
    ap.add_argument("--chrome", metavar="OUT",
                    help="export a chrome://tracing / Perfetto-loadable "
                         "trace-event JSON document")
    ap.add_argument("--json", metavar="OUT",
                    help="write the full report (summary+fit+drift) as "
                         "JSON")
    ap.add_argument("--validate", action="store_true",
                    help="exit non-zero if any event fails the schema")
    args = ap.parse_args()

    events = load_events(args.trace)
    rep = build_report(events)
    print_report(rep)
    if args.chrome:
        with open(args.chrome, "w") as f:
            json.dump(to_chrome(events), f)
        print(f"chrome trace -> {args.chrome} "
              f"(load in chrome://tracing or ui.perfetto.dev)")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rep, f, indent=1)
        print(f"report -> {args.json}")
    if args.validate and rep["schema_errors"]:
        for e in rep["schema_errors"][:20]:
            print(f"SCHEMA: {e}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
