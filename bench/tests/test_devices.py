"""No path falls back: a CPU, an unknown device kind or too few chips
end the run with no result, and so does a checkout that holds only the
benchmark."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness, spec
from bench.spec import BenchError


class FakeDevice:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_refuses_the_cpu():
    with pytest.raises(BenchError, match="not a TPU"):
        harness.check_devices(1, spec.load_peaks())


@pytest.mark.parametrize("kind,count,chips,match", [
    ("TPU v9 imaginary", 1, 1, "not in bench/peaks.json"),
    ("TPU v5 lite", 1, 4, "needs 4"),
])
def test_refuses_unknown_kind_and_too_few_chips(monkeypatch, kind, count,
                                                chips, match):
    import jax
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [FakeDevice("tpu", kind)] * count)
    with pytest.raises(BenchError, match=match):
        harness.check_devices(chips, spec.load_peaks())


def test_known_kind_passes(monkeypatch):
    import jax
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [FakeDevice("tpu", "TPU v5 lite")] * 4)
    devs, peak = harness.check_devices(4, spec.load_peaks())
    assert len(devs) == 4 and peak["bf16_flops_per_s"] == 197e12


def _run(root, workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(root, "bench", "run.py"),
         "--workload", workload, "--seed", str(2 ** 33 + 1), "--seconds",
         "1", "--trace", "0"], cwd=root, env=env, capture_output=True,
        text=True, timeout=300)


def _no_result(proc):
    last = (proc.stdout.strip().splitlines() or [""])[-1]
    try:
        json.loads(last)
    except ValueError:
        return True
    return False


def test_run_on_cpu_exits_nonzero_without_a_result():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    proc = _run(spec.ROOT, cell)
    assert proc.returncode != 0 and _no_result(proc), proc.stderr[-2000:]
    assert "not a TPU" in proc.stderr


def test_benchmark_alone_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(spec.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(tmp_path / "BENCHMARK.json") as f:
        cell = json.load(f)["workloads"][0]["name"]
    proc = _run(str(tmp_path), cell)
    assert proc.returncode != 0 and _no_result(proc), proc.stderr[-2000:]
    assert "no program" in proc.stderr


def test_calibration_refuses_the_cpu_without_rehearsal():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, os.path.join(spec.ROOT, "bench", "calibrate.py"),
         "--workload", cell, "--seeds", "1", "--faults", "0"],
        cwd=spec.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and "not a TPU" in proc.stderr, proc.stderr
