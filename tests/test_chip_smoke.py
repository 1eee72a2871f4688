"""chip_smoke.py: its CPU rehearsal passes every phase, and without that
option it refuses the CPU (no fallback) with a non-zero exit."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import subprocess_env

SCRIPT = os.path.join(os.path.dirname(__file__), "..", "chip_smoke.py")


def _run(*args, script=SCRIPT, timeout=600):
    r = subprocess.run([sys.executable, script, *args], capture_output=True,
                       text=True, timeout=timeout, env=subprocess_env(1))
    lines = r.stdout.strip().splitlines()
    return r, (json.loads(lines[-1]) if lines else None)


@pytest.mark.parametrize("extra,count", [((), 1), (("--four-chips",), 4)])
def test_cpu_rehearsal_passes(tmp_path, extra, count):
    r, last = _run("--cpu-rehearsal", "--out", str(tmp_path), *extra)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert last["ok"] is True
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] == count
    assert "[equivalence]" in r.stdout


def test_refuses_the_cpu_without_rehearsal(tmp_path):
    r, last = _run("--out", str(tmp_path), timeout=300)
    assert r.returncode != 0
    assert last["ok"] is False and "not 'tpu'" in last["error"]


def test_fails_outside_a_checkout(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, alone)
    r, last = _run(script=str(alone), timeout=300)
    assert r.returncode != 0
    assert last["ok"] is False
