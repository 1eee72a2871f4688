"""Benchmark harness entry: one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows.

  python -m benchmarks.run [--only fig6,fig9,...] [--quick]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: fig6,fig7,fig8,fig9,micro,exchange,"
                         "resilience,topology,overlap,roofline,"
                         "strategies,tuning")
    ap.add_argument("--quick", action="store_true",
                    help="shorter convergence runs")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    def want(tag):
        return only is None or tag in only

    from benchmarks import (figures, microbench, overlap, resilience,
                            roofline, strategies, topology, tuning)

    print("name,us_per_call,derived")
    if want("fig6"):
        figures.fig6_imagenet_scaling(emit)
    if want("fig8"):
        figures.fig8_second_workload_scaling(emit)
    if want("fig7"):
        figures.fig7_accuracy_parity(emit, n_steps=40 if args.quick else 120)
    if want("fig9"):
        figures.fig9_quality_parity(emit, n_steps=60 if args.quick else 150)
    if want("micro"):
        microbench.emit_rows(emit)
    if want("exchange"):
        microbench.emit_exchange_rows(emit, quick=args.quick)
    if want("resilience"):
        resilience.emit_rows(emit, quick=args.quick)
    if want("topology"):
        topology.emit_rows(emit, quick=args.quick)
    if want("overlap"):
        overlap.emit_rows(emit, quick=args.quick)
    if want("roofline"):
        roofline.emit_rows(emit)
    if want("strategies"):
        strategies.emit_rows(emit, quick=args.quick)
    if want("tuning"):
        tuning.emit_rows(emit, quick=args.quick)


if __name__ == "__main__":
    main()
