"""A tiny cell built from bench/tests/data, for runs on the CPU."""
import json
import os

from bench import spec

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def tiny_cell(config="tiny.json", limits="tiny-limits.json"):
    bench = load(os.path.join(spec.ROOT, "BENCHMARK.json"))
    return spec.Cell(
        name="tiny", chips=1,
        config=load(config), traffic=load("tiny-traffic.json"),
        limits=load(limits)["limits"],
        end_to_end=tuple(bench["end_to_end"]),
        per_layer=tuple(bench["per_layer"]))
