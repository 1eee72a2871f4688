"""A new cell, configuration or per-layer metric is picked up from its
files alone, by the name BENCHMARK.json gives it."""
import json
import os
import shutil

from bench import spec

NEW_CELL = "tiny-new.daso-b4.1chip"


def _copy_with_new_cell(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(spec.ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-new", "source": "a test", "file":
        "bench/configs/tiny-new.json", "reduced": [], "why": "a test"})
    bench["workloads"].append({
        "name": NEW_CELL, "config": "tiny-new", "traffic": "tiny-new",
        "chips": 1, "why": "a test"})
    bench["per_layer"].append({
        "name": "new_metric", "unit": "s", "better": "lower",
        "source": "device_trace", "layer": "device",
        "moves": "setup_s", "workloads": [NEW_CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    data = os.path.join(os.path.dirname(__file__), "data")
    shutil.copy(os.path.join(data, "tiny.json"),
                root / "bench" / "configs" / "tiny-new.json")
    shutil.copy(os.path.join(data, "tiny-traffic.json"),
                root / "bench" / "traffic" / "tiny-new.json")
    shutil.copy(os.path.join(data, "tiny-limits.json"),
                root / "bench" / "limits" / f"{NEW_CELL}.json")
    (root / "bench" / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    return str(root)


def test_new_cell_config_and_metric_found_by_name(tmp_path):
    root = _copy_with_new_cell(tmp_path)
    cell = spec.load_cell(NEW_CELL, root)
    assert cell.config["hidden_size"] == 64
    assert cell.traffic["seq_len"] == 64
    assert set(cell.limits) >= {"loss_gap", "mom_gap"}
    names = [m["name"] for m in cell.per_layer]
    assert "new_metric" in names
    assert spec.metric_reader("new_metric", root)(None) == 42.0
    # the metric lists only the new cell
    old = spec.load_cell(json.load(open(os.path.join(
        root, "BENCHMARK.json")))["workloads"][0]["name"], root)
    assert "new_metric" not in [m["name"] for m in old.per_layer]


def test_every_benchmark_cell_loads():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        for m in cell.per_layer:
            assert callable(spec.metric_reader(m["name"]))
