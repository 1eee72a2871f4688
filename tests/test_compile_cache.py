"""launch/compile_cache.py: the persistent compilation cache lives where
JAX_COMPILATION_CACHE_DIR says, else at the checkout's fixed
`.jax_compile_cache/`, and a CPU-pinned process keeps none. Each case runs
in a fresh process, so that no cache setting leaks into the test worker."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from conftest import subprocess_env

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT_DIR = os.path.join(ROOT, ".jax_compile_cache")

SCRIPT = """
    import json, sys
    from repro.launch.compile_cache import enable_compile_cache
    got = enable_compile_cache()
    import jax
    if sys.argv[1] == "compile":
        import jax.numpy as jnp
        jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))) \\
            .block_until_ready()
    print(json.dumps({"returned": got,
                      "configured": jax.config.jax_compilation_cache_dir}))
"""


def _entries(path):
    return sorted(os.listdir(path)) if os.path.isdir(path) else []


def _run(env, action):
    env = dict(env, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(SCRIPT),
                        action], capture_output=True, text=True,
                       timeout=120, env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", ["env_dir", "checkout", "cpu_pinned"])
def test_cache_directory(case, tmp_path):
    env = subprocess_env(1)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if case == "env_dir":
        # set: JAX's own setting is left alone, and a compile writes there
        # and not into the checkout
        want = str(tmp_path / "cache")
        env["JAX_COMPILATION_CACHE_DIR"] = want
        before = _entries(CHECKOUT_DIR)
        out = _run(env, "compile")
        assert _entries(want), "no cache entry written"
        assert _entries(CHECKOUT_DIR) == before
    elif case == "checkout":
        # unset, not pinned to the CPU (as on the chip): the fixed path.
        # Nothing is compiled, so no backend is initialised
        env["JAX_PLATFORMS"] = ""
        want = CHECKOUT_DIR
        out = _run(env, "config")
    else:
        want = None
        out = _run(env, "compile")
    assert out == {"returned": want, "configured": want}
