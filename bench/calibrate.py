#!/usr/bin/env python3
"""Readings that set the limits of `correct`, on the chip at a cell's own
size, all in one process:

    python3 bench/calibrate.py --workload <cell> --seeds 12 --faults 3 \
        [--first-seed N] [--out readings.json] [--rehearsal]

It refuses any device but a TPU of a kind in `bench/peaks.json`;
`--rehearsal` lets it run on whatever JAX has (a CPU at a test's size),
and every row records the platform and device kind it was read on.

For each seed, the program's first DASO cycle (the harness's own set-up
path) against the plain reference: the lower readings. For the first
`--faults` seeds also the control, the program's own float8 path (the
configuration's `torch_dtype` set to float8_e4m3fn; a crash is recorded
as such), the reference computed in float8 (`control_ref`, the control
of a cell whose program has no float8 path that runs), and the reference
with each planted fault a training cell can have: a step that returns its
state unchanged, half of the batch left out, the exchange between
replicas left out. The benchmark's own runs never run this."""
import argparse
import copy
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import checks, harness, reference, spec, traffic  # noqa: E402

FAULTS = ("unchanged", "half_batch", "no_exchange")


def calibrate(cell, devices, n_seeds, n_faults, first_seed, log=print):
    """Rows of readings per seed, and their range per kind of run."""
    cfg, tr = cell.config, cell.traffic
    on = {"platform": devices[0].platform,
          "device_kind": devices[0].device_kind}
    programs = {"program": harness.build_program(cell)}
    if n_faults:
        # the control: the program's own lower-precision path, its
        # parameters and arithmetic in float8 (ArchConfig param and compute
        # dtype), against the reference of the configuration as stated
        fp8 = dataclasses.replace(cell, config=dict(
            cfg, torch_dtype=reference.F8.dtype.name))
        programs["control"] = (fp8,) + harness.build_program(fp8)
    schedule = copy.deepcopy(programs["program"][0].controller)
    rows = []
    for i in range(n_seeds):
        seed = first_seed + 7919 * i
        host_pool = traffic.make_pool(cfg["vocab_size"], tr, seed)
        runs = {}
        for kind in ("program", "control")[:1 + (i < n_faults)]:
            run_cell, (strategy, ex) = ((cell, programs[kind])
                                        if kind == "program" else
                                        (programs[kind][0],
                                         programs[kind][1:]))
            # every seed starts from step 0 of a fresh DASO schedule
            strategy.controller = copy.deepcopy(schedule)
            feed = harness.Feed(harness.device_pool(host_pool, devices[0]),
                                tr["b_max"])
            runner = harness.Runner(run_cell, strategy, ex, feed, seed)
            try:
                runner.call(0, tr["b_max"])
            except Exception as e:  # noqa: BLE001 - a control may crash
                if kind == "program":
                    raise
                # a control that crashes has failed and sets no upper end
                runs[kind] = {"crashed": f"{type(e).__name__}: {e}"[:500]}
                continue
            finally:
                runner.carry = None
            runs[kind] = runner.prog
            del runner, feed
        ref = reference.run(cfg, tr, seed, host_pool, tr["b_max"],
                            devices=list(devices))
        row = dict(on, seed=seed, losses={"reference": ref["losses"]})
        for kind, got in runs.items():
            if "crashed" in got:
                row[kind] = got
                continue
            row[kind] = checks.readings(got, ref)
            row["losses"][kind] = got["losses"]
        if i < n_faults:
            ctl = reference.run(cfg, tr, seed, host_pool, tr["b_max"],
                                numerics="fp8", devices=list(devices))
            row["control_ref"] = checks.readings(ctl, ref)
            row["losses"]["control_ref"] = ctl["losses"]
            for fault in FAULTS:
                bad = reference.run(cfg, tr, seed, host_pool, tr["b_max"],
                                    fault=fault, devices=list(devices))
                row[fault] = checks.readings(bad, ref)
                row["losses"][fault] = bad["losses"]
        rows.append(row)
        log(json.dumps(row))
    summary = {}
    for kind in ("program", "control", "control_ref") + FAULTS:
        got = [r[kind] for r in rows if kind in r and "crashed" not in r[kind]]
        if got:
            summary[kind] = {k: {"min": min(g[k] for g in got),
                                 "max": max(g[k] for g in got)}
                             for k in checks.CHECKS}
    return rows, summary


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--out")
    ap.add_argument("--rehearsal", action="store_true",
                    help="run on a device that is not a TPU; its readings "
                         "set no limit")
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    sys.path.insert(0, os.path.join(spec.ROOT, "src"))
    import jax
    jax.config.update("jax_compilation_cache_dir", harness.CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if args.rehearsal:
        devices = jax.devices()[:cell.chips]
    else:
        try:
            devices, _ = harness.check_devices(cell.chips, spec.load_peaks())
        except spec.BenchError as e:
            sys.exit(f"calibrate: {e} (--rehearsal runs anyway)")
    rows, summary = calibrate(cell, devices, args.seeds, args.faults,
                              args.first_seed)
    on = {"platform": devices[0].platform,
          "device_kind": devices[0].device_kind}
    print(json.dumps(dict(on, summary=summary)), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(dict(on, workload=args.workload, rows=rows,
                           summary=summary), f, indent=1)


if __name__ == "__main__":
    main()
