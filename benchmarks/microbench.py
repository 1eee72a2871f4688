"""Measured (wall-clock) microbenchmarks of the DASO step variants on an
8-virtual-device (2 pods x 2 data x 2 model) CPU mesh, via subprocess so the
main process keeps one device. Times are real; they validate the *relative*
cost ordering (local < send < blocking), not TPU magnitudes.

Also benchmarks the compiled macro-cycle executor (core/executor.py) against
the per-step path on a cycling-phase schedule: same numerics, host dispatches
per B=4 cycle reduced from B+1 step launches to 1 compiled program."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_SCRIPT = """
import time
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.daso import DasoConfig, daso_train_step, replicate_params
from repro.optim.optimizers import sgd

def loss_fn(params, batch):
    h = jnp.tanh(batch["x"] @ params["w1"])
    pred = h @ params["w2"]
    return jnp.mean((pred - batch["y"]) ** 2), {}

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
R, per, d, h = 2, 32, 256, 512
key = jax.random.PRNGKey(0)
params0 = {"w1": jax.random.normal(key, (d, h)) * 0.05,
           "w2": jax.random.normal(key, (h, d)) * 0.05}
opt = sgd(momentum=0.9)
cfg = DasoConfig(n_replicas=R, global_world=8)
shp = NamedSharding(mesh, P("pod"))
shb = NamedSharding(mesh, P("pod", "data"))
p = jax.tree.map(lambda x: jax.device_put(x, shp), replicate_params(params0, R))
o = jax.tree.map(lambda x: jax.device_put(x, shp),
                 replicate_params(opt.init(params0), R))
infl = jax.tree.map(lambda x: x, p)
batch = {"x": jax.device_put(jax.random.normal(key, (R, per, d)), shb),
         "y": jax.device_put(jax.random.normal(key, (R, per, d)), shb)}
for mode in ("local", "send", "receive", "blocking"):
    step = jax.jit(daso_train_step(loss_fn, opt, cfg, mode=mode, staleness=1))
    out = step(p, o, infl, batch, 0.01)
    jax.block_until_ready(out)
    n = 30
    t0 = time.perf_counter()
    for _ in range(n):
        p2, o2, infl2, m = step(p, o, infl, batch, 0.01)
    jax.block_until_ready((p2, o2, infl2))
    dt = (time.perf_counter() - t0) / n * 1e6
    print(f"CSV daso_step_{mode} {dt:.1f} mesh=2x2x2")
"""


_CYCLE_SCRIPT = """
import time
import jax, jax.numpy as jnp
from repro.core.daso import DasoConfig
from repro.core.executor import MacroCycleExecutor, make_strategy
from repro.core.schedule import DasoController
from repro.optim.optimizers import sgd

def loss_fn(params, batch):
    h = jnp.tanh(batch["x"] @ params["w1"])
    pred = h @ params["w2"]
    return jnp.mean((pred - batch["y"]) ** 2), {}

# deliberately tiny: this benchmark isolates the host-dispatch overhead the
# macro-cycle executor removes (small step times = controller-dominated
# wall-clock, the regime the tentpole targets)
R, per, d, h, B = 2, 8, 64, 64, 4
key = jax.random.PRNGKey(0)
params0 = {"w1": jax.random.normal(key, (d, h)) * 0.05,
           "w2": jax.random.normal(key, (h, d)) * 0.05}
def data_fn(step):
    k = jax.random.fold_in(key, step)
    return {"x": jax.random.normal(k, (R, per, d)),
            "y": jax.random.normal(k, (R, per, d))}
# pure cycling phase (no warm-up/cool-down), frozen B/W: every cycle is the
# same (send, receive, local, local) shape
cfg = DasoConfig(n_replicas=R, global_world=8, b_max=B)
strat = make_strategy("daso", loss_fn, sgd(momentum=0.9), cfg,
                      controller=DasoController(cfg, loss_window=10**9))
ex = MacroCycleExecutor(strat)
plan = strat.plan_cycle(0, 32)
assert len(plan) == B, plan.shape
steps = list(range(B))
batches = jax.tree.map(lambda *xs: jnp.stack(xs),
                       *[data_fn(t) for t in steps])
lrs = jnp.asarray([0.01] * B, jnp.float32)
stepwise = [jax.jit(strat.step_fn(m, s)) for m, s in plan.shape]

# warm both paths (compile), threading the carry (run_cycle donates it)
carry = strat.init_carry(params0)
carry, _ = ex.run_cycle(carry, plan, batches, lrs)
for i, fn in enumerate(stepwise):
    carry, _ = fn(carry, jax.tree.map(lambda x, j=i: x[j], batches), lrs[i])
jax.block_until_ready(carry)

# Both timed loops reproduce what the host loop really does per step/cycle:
# dispatch + blocking metrics readback (the controller consumes the loss).
n = 30
ex.stats.dispatches = 0
t0 = time.perf_counter()
for _ in range(n):
    carry, m = ex.run_cycle(carry, plan, batches, lrs)
    _ = float(m["loss"][0])        # one readback per cycle
jax.block_until_ready(carry)
t_macro = (time.perf_counter() - t0) / n * 1e6
d_macro = ex.stats.dispatches / n  # = 1: one fused program per cycle

t0 = time.perf_counter()
for _ in range(n):
    for i, fn in enumerate(stepwise):
        carry, m = fn(carry, jax.tree.map(lambda x, j=i: x[j], batches),
                      lrs[i])
        _ = float(m["loss"])       # one readback per step
jax.block_until_ready(carry)
t_step = (time.perf_counter() - t0) / n * 1e6
# per cycle the old loop pays B step launches plus the blocking metrics
# round-trip that separates cycles: the issue's "B+1" host dispatches
d_step = len(stepwise) + 1

print(f"CSV daso_macro_cycle_compiled {t_macro:.1f} "
      f"host_dispatches_per_cycle={d_macro:.0f} (B={B})")
print(f"CSV daso_macro_cycle_stepwise {t_step:.1f} "
      f"host_dispatches_per_cycle=B+1={d_step} "
      f"({len(stepwise)} step launches + blocking metrics round-trip)")
print(f"CSV daso_macro_cycle_speedup {t_step / max(t_macro, 1e-9):.3f} "
      f"host_dispatches_per_cycling_cycle: B+1={d_step} -> {d_macro:.0f}")
"""


_EXCHANGE_SCRIPT = """
import json
import os
import time
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.daso import (replica_mean, replica_mean_per_leaf,
                             replicate_params)
from repro.core.compression import transfer_bytes
from repro.launch.hlo_stats import collective_stats

QUICK = os.environ.get("BENCH_QUICK") == "1"
OUT = os.environ.get("BENCH_EXCHANGE_OUT", "BENCH_exchange.json")

# A transformer-ish pytree: many leaves of mixed sizes (16 blocks x 7
# leaves -> 112 leaves, ~525k params). The per-leaf path pays one
# cross-replica all-reduce + wire cast per leaf; the fused arena path
# pays exactly one, whatever this count is.
R = 2
n_blocks = 8 if QUICK else 16
dims = (32, 64) if QUICK else (64, 128)
key = jax.random.PRNGKey(0)
tree = {}
for l in range(n_blocks):
    k = jax.random.fold_in(key, l)
    d, f = dims
    tree[f"layer{l}"] = {
        "wq": jax.random.normal(jax.random.fold_in(k, 0), (d, d)),
        "wk": jax.random.normal(jax.random.fold_in(k, 1), (d, d)),
        "wv": jax.random.normal(jax.random.fold_in(k, 2), (d, d)),
        "wo": jax.random.normal(jax.random.fold_in(k, 3), (d, d)),
        "w_up": jax.random.normal(jax.random.fold_in(k, 4), (d, f)),
        "w_down": jax.random.normal(jax.random.fold_in(k, 5), (f, d)),
        "scale": jax.random.normal(jax.random.fold_in(k, 6), (d,)),
    }
n_leaves = len(jax.tree.leaves(tree))
n_params = sum(x.size for x in jax.tree.leaves(tree))

from repro.launch.mesh import make_mesh
mesh = make_mesh((2,), ("pod",))
mesh_shape = {"pod": 2}
sh = NamedSharding(mesh, P("pod"))
params = jax.tree.map(lambda x: jax.device_put(x, sh),
                      replicate_params(tree, R))
params = jax.tree.map(
    lambda x: x + jnp.arange(R, dtype=x.dtype).reshape(
        (R,) + (1,) * (x.ndim - 1)), params)

def bench(name, fn, *args, wire_format=None, impl=None):
    jitted = jax.jit(fn)
    out = jitted(*args)
    jax.block_until_ready(out)
    hlo = jitted.lower(*args).compile().as_text()
    stats = collective_stats(hlo, mesh_shape)
    ar = sum(v["count"] for k, v in stats.items()
             if isinstance(v, dict) and k.startswith("all-reduce"))
    n = 10 if QUICK else 30
    t0 = time.perf_counter()
    for _ in range(n):
        out = jitted(*args)
    jax.block_until_ready(out)
    us = (time.perf_counter() - t0) / n * 1e6
    rec = {"name": name, "impl": impl, "wire_format": wire_format,
           "us_per_exchange": us, "all_reduce_ops": ar}
    if wire_format:
        rec["transfer_bytes"] = transfer_bytes(tree,
                                               wire_format=wire_format)
    results.append(rec)
    print(f"CSV exchange_{name} {us:.1f} "
          f"all_reduce_ops={ar} wire={wire_format} impl={impl}")
    return us

results = []
for wf, wd in (("f32", None), ("bf16", jnp.bfloat16)):
    bench(f"per_leaf_{wf}",
          lambda p, wd=wd: replica_mean_per_leaf(p, wd), params,
          wire_format=wf, impl="per_leaf")
for wf in ("f32", "bf16", "int8"):
    bench(f"fused_{wf}",
          lambda p, wf=wf: replica_mean(p, wire_format=wf), params,
          wire_format=wf, impl="fused")

by = {r["name"]: r for r in results}
tb = {r["wire_format"]: r["transfer_bytes"] for r in results
      if r.get("transfer_bytes")}
derived = {
    "fused_speedup_f32": by["per_leaf_f32"]["us_per_exchange"]
    / by["fused_f32"]["us_per_exchange"],
    "fused_speedup_bf16": by["per_leaf_bf16"]["us_per_exchange"]
    / by["fused_bf16"]["us_per_exchange"],
    "all_reduce_ops_per_leaf": by["per_leaf_f32"]["all_reduce_ops"],
    "all_reduce_ops_fused": by["fused_f32"]["all_reduce_ops"],
    "int8_vs_bf16_bytes": tb["int8"] / tb["bf16"],
}
record = {"benchmark": "exchange",
          "config": {"n_replicas": R, "n_leaves": n_leaves,
                     "n_params": int(n_params), "quick": QUICK,
                     "mesh": "pod=2"},
          "results": results, "derived": derived}
with open(OUT, "w") as f:
    json.dump(record, f, indent=2)
print(f"CSV exchange_speedup_f32 {derived['fused_speedup_f32']:.3f} "
      f"all_reduce_ops {by['per_leaf_f32']['all_reduce_ops']} -> "
      f"{by['fused_f32']['all_reduce_ops']} (leaves={n_leaves})")
print(f"CSV exchange_int8_vs_bf16_bytes "
      f"{derived['int8_vs_bf16_bytes']:.3f} json={OUT}")
"""


def _run_sub(emit, script, fail_tag, *, devices=8, extra_env=None):
    env = dict(os.environ)
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra_env or {})
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                       capture_output=True, text=True, timeout=600, env=env)
    if r.returncode != 0:
        emit(fail_tag, 0.0, r.stderr[-200:])
        return
    for line in r.stdout.splitlines():
        if line.startswith("CSV "):
            _, name, us, derived = line.split(" ", 3)
            emit(name, float(us), derived)


def emit_rows(emit):
    _run_sub(emit, _SCRIPT, "daso_step_microbench_FAILED")
    # single device: the virtual-node replica axis needs no mesh, and the
    # host-dispatch overhead being measured is device-count independent
    _run_sub(emit, _CYCLE_SCRIPT, "daso_macro_cycle_FAILED", devices=1)


def emit_exchange_rows(emit, *, quick=False):
    """Fused flat-buffer exchange vs the legacy per-leaf path, across wire
    formats, on a 2-device (pod) mesh. Writes the perf record to
    $BENCH_EXCHANGE_OUT (default ./BENCH_exchange.json)."""
    _run_sub(emit, _EXCHANGE_SCRIPT, "exchange_microbench_FAILED",
             devices=2, extra_env={"BENCH_QUICK": "1" if quick else "0"})
