"""Discovery by name: `BENCHMARK.json` names the cells, and each
configuration, traffic mix, limit set and per-layer metric sits in a file
of its own under `bench/`. Adding one of them adds files and edits no
code."""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class BenchError(Exception):
    """A run that must end without a result (exit code non-zero)."""


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchError(f"missing file {os.path.relpath(path, ROOT)}") \
            from None


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file, as run
    traffic: dict         # the traffic file's parameters
    limits: dict          # {check name: limit}
    end_to_end: tuple     # BENCHMARK.json entries reported with --trace 0
    per_layer: tuple      # ... and with --trace 1


def applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell `name` of `<root>/BENCHMARK.json` with its files."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    bdir = os.path.join(root, "bench")
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_load_json(os.path.join(root, cfg_entry["file"])),
        traffic=_load_json(os.path.join(bdir, "traffic",
                                        w["traffic"] + ".json")),
        limits=_load_json(os.path.join(bdir, "limits", name + ".json"))
        ["limits"],
        end_to_end=tuple(m for m in bench["end_to_end"] if applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if applies(m, name)))


def load_peaks(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "bench", "peaks.json"))["devices"]


def metric_reader(name: str, root: str = ROOT):
    """`read(run) -> float | None` of `bench/metrics/<name>.py`."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    if not os.path.isfile(path):
        raise BenchError(f"per-layer metric {name!r} has no reader "
                         f"bench/metrics/{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
