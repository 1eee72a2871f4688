import os
import sys

# Make `repro` importable regardless of how pytest is invoked.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# The property tests import `hypothesis`. On minimal containers without it
# (and without network for `pip install -e .[test]`), register the
# deterministic fallback shim so the tier-1 suite still collects and runs.
try:
    import hypothesis  # noqa: F401
except ModuleNotFoundError:
    sys.path.insert(0, os.path.dirname(__file__))
    import _hypothesis_fallback as _shim  # noqa: F401

    _module = type(sys)("hypothesis")
    _module.given = _shim.given
    _module.settings = _shim.settings
    _module.strategies = _shim
    sys.modules["hypothesis"] = _module
    sys.modules["hypothesis.strategies"] = _shim

def make_mlp_problem(key, R=2, per=16, d=8):
    """Shared tiny-MLP training problem for the loop/executor tests.
    Returns (params0, loss_fn, daso_data, sync_data); daso batches carry the
    leading replica axis R. Random init: all-zeros would zero every gradient
    (tanh(0) kills the w2 grad and, through w2=0, the w1 grad) and nothing
    would train."""
    import jax
    import jax.numpy as jnp

    w1 = jax.random.normal(key, (d, 16)) * 0.5
    k1, k2 = jax.random.split(jax.random.fold_in(key, 7))
    params0 = {"w1": jax.random.normal(k1, (d, 16)) * 0.3,
               "w2": jax.random.normal(k2, (16, 1)) * 0.3}

    def loss_fn(params, batch):
        h = jnp.tanh(batch["x"] @ params["w1"])
        pred = h @ params["w2"]
        return jnp.mean((pred - batch["y"]) ** 2), {}

    def daso_data(step):
        k = jax.random.fold_in(key, step)
        x = jax.random.normal(k, (R, per, d))
        y = jnp.tanh(x @ w1).sum(-1, keepdims=True) * 0.3
        return {"x": x, "y": y}

    def sync_data(step):
        b = daso_data(step)
        return {k2_: v.reshape((-1,) + v.shape[2:]) for k2_, v in b.items()}

    return params0, loss_fn, daso_data, sync_data


def make_exchange(n_replicas, global_world=None, *, membership=None,
                  group_perm=None, device_local=False, **cfg):
    """The `Exchange` of a DasoConfig over `n_replicas` replicas and
    `global_world` devices (default: one per replica) with the other
    DasoConfig fields in `cfg`, for tests that call the exchange
    primitives directly."""
    from repro.core.daso import DasoConfig, Exchange

    dcfg = DasoConfig(n_replicas=n_replicas,
                      global_world=global_world or n_replicas, **cfg)
    return Exchange.of(dcfg, membership=membership, group_perm=group_perm,
                       device_local=device_local)


# NOTE: XLA_FLAGS / device-count overrides are intentionally NOT set here —
# smoke tests must see the real single CPU device. Multi-device distributed
# tests spawn subprocesses through the helpers below, which build the JAX
# environment EXPLICITLY (platform + device count are always set, never
# silently inherited) so a local `pytest` run behaves exactly like CI.



def subprocess_env(devices: int = 1, extra: dict = None) -> dict:
    """Environment for a spawned JAX subprocess: JAX_PLATFORMS is pinned
    to cpu and XLA_FLAGS to the forced host device count — never
    inherited from the developer's shell — so a local `pytest` run
    behaves exactly like CI. One definition, shared with the process
    launcher (launch.distributed.forced_cpu_env)."""
    from repro.launch.distributed import forced_cpu_env

    env = forced_cpu_env(devices)
    if extra:
        env.update(extra)
    return env


def run_subprocess(script: str, devices: int = 8, timeout: int = 900,
                   extra_env: dict = None) -> str:
    """Run an inline python script in a fresh process on `devices` forced
    CPU devices; assert success and return stdout. Coordinator port races
    are handled at the source — `launch.distributed.initialize` retries
    transient connect/bind failures with backoff — so any failure here is
    real and surfaces immediately."""
    import subprocess
    import sys
    import textwrap

    r = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                       capture_output=True, text=True, timeout=timeout,
                       env=subprocess_env(devices, extra_env))
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout
