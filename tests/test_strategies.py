"""Cross-strategy conformance suite (ISSUE 9 headline artifact).

One parameterized battery over EVERY registered strategy — the paper's
daso family plus the baseline expansion (core/baselines.py: gossip /
easgd / downpour) — so any future strategy inherits the full test
surface by registering:

  * macro-cycle executor == per-step reference path (losses, params,
    mode history);
  * checkpoint save/resume is bit-exact with the uninterrupted run
    (TrainState round-trips each strategy's carry layout + controller);
  * membership-mask fault plans run through the resilience supervisor
    (crash + rejoin; cache invalidations; membership timeline);
  * one-collective-or-zero HLO contract on a replica-sharded mesh:
    exchange steps lower to exactly one parameter-scale all-reduce over
    the replica axis — except gossip, whose pairwise exchange must
    contain NO all-reduce (data movement only);
  * 2-process SPMD runs are bit-exact with the 1-process oracle
    (gossip in tier-1; easgd/downpour on the nightly/slow tier).

Plus the satellite property tests (gossip mean preservation, EASGD
closed-form center) and the get_strategy error-path regression.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from conftest import make_exchange, make_mlp_problem, subprocess_env
from repro.core.baselines import gossip_mix
from repro.core.daso import DasoConfig
from repro.core.executor import (get_strategy, list_strategies,
                                 make_strategy, run_compiled_training)
from repro.core.simulator import run_per_step_training
from repro.optim.optimizers import sgd
from repro.optim.schedules import constant_lr

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")
LAUNCHER = os.path.join(REPO, "tools", "launch_procs.py")

ALL = ("sync", "daso", "local_sgd", "gossip", "easgd", "downpour")
REPLICA = tuple(s for s in ALL if s != "sync")
NEW = ("gossip", "easgd", "downpour")


def test_every_registered_strategy_is_covered():
    """The battery's strategy list IS the registry (minus hier_daso,
    which needs a topology spec and has its own suite in
    test_topology.py). A strategy registered without joining ALL fails
    here, so the conformance surface cannot silently shrink."""
    import repro.topo.strategy  # noqa: F401  (registers "hier_daso")
    assert set(list_strategies()) - {"hier_daso"} == set(ALL)


def _cfg(n_steps, R=2, b_max=4, **kw):
    return DasoConfig(n_replicas=R, global_world=4 * R, b_max=b_max,
                      warmup_steps=n_steps // 10,
                      cooldown_steps=n_steps // 10,
                      total_steps=n_steps, **kw)


def _make(name, loss_fn, n_steps, *, R=2, loss_window=10, **cfg_kw):
    opt = sgd(momentum=0.9, weight_decay=1e-4)
    if name == "sync":
        return make_strategy("sync", loss_fn, opt)
    cfg = _cfg(n_steps, R=R, **cfg_kw)
    cls = get_strategy(name)
    return make_strategy(name, loss_fn, opt, cfg,
                         controller=cls.make_controller(
                             cfg, loss_window=loss_window))


# ------------------------------------------------ macro == per-step ----------

@pytest.mark.parametrize("name", ALL)
def test_macro_matches_per_step(name):
    n_steps = 30
    key = jax.random.PRNGKey(0)
    params0, loss_fn, daso_data, sync_data = make_mlp_problem(key)
    data_fn = sync_data if name == "sync" else daso_data

    macro = _make(name, loss_fn, n_steps)
    ref = _make(name, loss_fn, n_steps)
    rm = run_compiled_training(macro, params0, data_fn, constant_lr(0.1),
                               n_steps)
    rp = run_per_step_training(ref, params0, data_fn, constant_lr(0.1),
                               n_steps)
    assert len(rm.losses) == len(rp.losses) == n_steps
    np.testing.assert_allclose(rm.losses, rp.losses, rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(rm.params), jax.tree.leaves(rp.params)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6)
    if macro.controller is not None:
        assert ([h[1] for h in macro.controller.history]
                == [h[1] for h in ref.controller.history])


@pytest.mark.parametrize("name", NEW)
def test_new_strategies_schedule_shape(name):
    """The periodic schedule: blocking warm-up/cool-down, one exchange
    token every B cycling steps, locals between — and gossip's partner
    shift rotates between exchanges."""
    n_steps = 40
    key = jax.random.PRNGKey(1)
    params0, loss_fn, daso_data, _ = make_mlp_problem(key, R=4)
    strat = _make(name, loss_fn, n_steps, R=4)
    run_compiled_training(strat, params0, daso_data, constant_lr(0.05),
                          n_steps)
    modes = [h[1] for h in strat.controller.history]
    warm = n_steps // 10
    assert modes[:warm] == ["blocking"] * warm
    assert modes[-warm:] == ["blocking"] * warm
    cycling = modes[warm:-warm]
    token = {"gossip": "gossip~", "easgd": "elastic",
             "downpour": "push"}[name]
    exchanges = [m for m in cycling if m.startswith(token)]
    assert exchanges, cycling
    assert all(m.startswith(token) or m == "local" for m in cycling)
    # B=4 periodicity: exchange every 4th cycling step
    assert [m.startswith(token) for m in cycling[:8]] \
        == [True, False, False, False] * 2
    if name == "gossip":
        # R=4: shifts rotate 1,2,3,1,... so the ring mixes globally
        shifts = [int(m.split("~")[1]) for m in exchanges]
        assert shifts[:3] == [1, 2, 3]
    assert 0.0 < strat.sync_fraction() < 1.0


# ------------------------------------------------ checkpoint resume ----------

@pytest.mark.parametrize("name", ALL)
def test_checkpoint_resume_bit_exact(name, tmp_path):
    from repro.train.loop import TrainLoopConfig, run_training

    n_steps = 24
    key = jax.random.PRNGKey(2)
    params0, loss_fn, daso_data, sync_data = make_mlp_problem(key)
    data_fn = sync_data if name == "sync" else daso_data

    def loop_cfg(**kw):
        return TrainLoopConfig(strategy=name, n_steps=n_steps, n_replicas=2,
                               local_world=2, b_max=4, lr=0.1,
                               loss_window=10, **kw)

    full = run_training(loss_fn, params0, data_fn, loop_cfg(), log=None)
    ck = run_training(loss_fn, params0, data_fn,
                      loop_cfg(ckpt_every=8, ckpt_dir=str(tmp_path)),
                      log=None)
    assert full.losses == ck.losses
    saved = sorted(os.listdir(tmp_path))
    assert saved, "no checkpoint written"
    resumed = run_training(
        loss_fn, params0, data_fn,
        loop_cfg(resume_from=str(tmp_path / saved[0])), log=None)
    # bit-exact: the resumed run replays the identical schedule + numerics
    assert resumed.losses == full.losses
    for a, b in zip(jax.tree.leaves(resumed.params),
                    jax.tree.leaves(full.params)):
        np.testing.assert_array_equal(a, b)


def test_checkpoint_rejects_strategy_mismatch(tmp_path):
    from repro.train.loop import TrainLoopConfig, run_training

    key = jax.random.PRNGKey(3)
    params0, loss_fn, daso_data, _ = make_mlp_problem(key)
    cfg = TrainLoopConfig(strategy="gossip", n_steps=12, n_replicas=2,
                          local_world=2, ckpt_every=4,
                          ckpt_dir=str(tmp_path))
    run_training(loss_fn, params0, daso_data, cfg, log=None)
    saved = sorted(os.listdir(tmp_path))[0]
    bad = TrainLoopConfig(strategy="easgd", n_steps=12, n_replicas=2,
                          local_world=2, resume_from=str(tmp_path / saved))
    with pytest.raises(ValueError, match="gossip"):
        run_training(loss_fn, params0, daso_data, bad, log=None)


# ------------------------------------------------ fault plans ----------------

@pytest.mark.parametrize("name", REPLICA)
def test_fault_plan_crash_rejoin(name):
    from repro.resilience.faults import FaultPlan
    from repro.resilience.supervisor import run_with_faults

    n_steps = 32
    key = jax.random.PRNGKey(4)
    params0, loss_fn, daso_data, _ = make_mlp_problem(key, R=4)
    strat = _make(name, loss_fn, n_steps, R=4)
    plan = FaultPlan.from_dicts([
        {"step": 8, "kind": "crash", "replica": 3},
        {"step": 16, "kind": "rejoin", "replica": 3}])
    report = run_with_faults(strat, params0, daso_data, constant_lr(0.05),
                             n_steps, plan)
    assert len(report.result.losses) == n_steps
    assert np.all(np.isfinite(report.result.losses))
    assert report.invalidations == 2
    masks = [m for (_, m) in report.membership_timeline]
    assert masks == [(1.0, 1.0, 1.0, 1.0), (1.0, 1.0, 1.0, 0.0),
                     (1.0, 1.0, 1.0, 1.0)]
    # the final params come from an ACTIVE replica and are finite
    for leaf in jax.tree.leaves(report.result.params):
        assert np.all(np.isfinite(leaf))


def test_fault_plan_rejects_sync():
    from repro.resilience.faults import FaultPlan
    from repro.resilience.supervisor import run_with_faults

    key = jax.random.PRNGKey(5)
    params0, loss_fn, _, sync_data = make_mlp_problem(key)
    strat = _make("sync", loss_fn, 8)
    plan = FaultPlan()
    with pytest.raises(ValueError, match="replica-axis"):
        run_with_faults(strat, params0, sync_data, constant_lr(0.05), 8,
                        plan)


# ------------------------------------------------ HLO contract ---------------

_HLO_SCRIPT = """
import json
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.daso import DasoConfig
from repro.core.executor import get_strategy, make_strategy
from repro.launch.hlo_stats import collective_stats
from repro.optim.optimizers import sgd

def loss_fn(params, batch):
    pred = batch["x"] @ params["w"]
    return jnp.mean((pred - batch["y"]) ** 2), {}

from repro.launch.mesh import make_mesh
mesh = make_mesh((2,), ("pod",))
mesh_shape = {"pod": 2}
R, per, d = 2, 4, 256   # w: 256x4 f32 = 4 KiB >> the 1 KiB floor
cfg = DasoConfig(n_replicas=R, global_world=4 * R, b_max=4,
                 warmup_steps=2, cooldown_steps=2, total_steps=20)
opt = sgd(momentum=0.9, weight_decay=1e-4)
key = jax.random.PRNGKey(0)
params0 = {"w": jax.random.normal(key, (d, 4)) * 0.1}
shp = NamedSharding(mesh, P("pod"))
sc = NamedSharding(mesh, P())
batch = {"x": jax.device_put(jnp.ones((R, per, d)), shp),
         "y": jax.device_put(jnp.ones((R, per, 4)), shp)}
lr = jnp.asarray(0.1)

CASES = [("daso", "local", 0), ("daso", "send", 1), ("daso", "blocking", 1),
         ("local_sgd", "hard_avg", 1),
         ("gossip", "local", 0), ("gossip", "gossip~1", 0),
         ("gossip", "blocking", 1),
         ("easgd", "elastic", 1), ("easgd", "blocking", 1),
         ("downpour", "push", 1), ("downpour", "blocking", 1)]

out = []
for name, mode, want_ar in CASES:
    cls = get_strategy(name)
    strat = make_strategy(name, loss_fn, opt, cfg,
                          controller=cls.make_controller(cfg))
    carry = jax.device_put(strat.init_carry(params0),
                           jax.tree.map(lambda _: shp, strat.init_carry(
                               params0)))
    step = strat.step_fn(mode, 1)
    shardings = (jax.tree.map(lambda _: shp, carry),
                 {"x": shp, "y": shp}, sc)
    lowered = jax.jit(step, in_shardings=shardings).lower(carry, batch, lr)
    stats = collective_stats(lowered.compile().as_text(), mesh_shape,
                             min_bytes=1024)
    ar = sum(v["count"] for k, v in stats.items()
             if k.startswith("all-reduce@") and isinstance(v, dict))
    total = stats["_total_count"]
    out.append({"strategy": name, "mode": mode, "want_ar": want_ar,
                "all_reduce": ar, "total": total})
print("VERDICTS " + json.dumps(out))
"""


def test_hlo_one_collective_or_zero():
    """Every exchange step compiles to exactly ONE parameter-scale
    all-reduce over the replica axis; local steps to zero; gossip's
    pairwise exchange to zero all-reduces (its partner copy is data
    movement — permute/gather family — never a reduction)."""
    env = dict(os.environ)
    env.update(subprocess_env(devices=2))
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c",
                        textwrap.dedent(_HLO_SCRIPT)],
                       capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("VERDICTS ")][0]
    verdicts = json.loads(line[len("VERDICTS "):])
    assert len(verdicts) == 11
    for v in verdicts:
        assert v["all_reduce"] == v["want_ar"], v
        if v["mode"] == "gossip~1":
            # the exchange still moves parameter-scale data across the
            # replica axis — just not through a reduction
            assert v["total"] >= 1, v
        if v["mode"] == "local":
            assert v["total"] == 0, v


# ------------------------------------------------ 2-proc SPMD ----------------

def _launch_equivalence(tmp_path, name, steps=14):
    """N-process vs 1-process bit-exactness through the real launcher,
    2-level topology (R=2 replicas, one per process)."""
    base = ["--arch", "llama3.2-1b", "--tiny",
            "--topology", "chip:1 x host:2", "--per-node-batch", "2",
            "--seq-len", "16", "--b-max", "4", "--seed", "0",
            "--strategy", name, "--steps", str(steps)]
    out = {}
    for n in (1, 2):
        m = str(tmp_path / f"metrics_{name}_{n}.json")
        cmd = [sys.executable, LAUNCHER, "--procs", str(n),
               "--timeout", "600", "--"] + base + ["--metrics-out", m]
        env = subprocess_env(devices=1)
        env.pop("XLA_FLAGS")  # the harness sets per-child device counts
        r = subprocess.run(cmd, capture_output=True, text=True, timeout=660,
                           env=env, cwd=REPO)
        assert r.returncode == 0, (f"{name} procs={n} failed:\n"
                                   f"{r.stdout}\n{r.stderr}")
        with open(m) as f:
            out[n] = json.load(f)
    assert out[1]["losses"] == out[2]["losses"], (
        f"{name}: loss traces diverge between process layouts")
    assert out[1]["final_loss"] == out[2]["final_loss"]
    assert out[1]["sync_fraction"] == out[2]["sync_fraction"]


def test_two_process_gossip_bit_exact(tmp_path):
    """Gossip has no reduction at all, so layout invariance needs no
    deterministic-reduce fallback — the strongest SPMD check of the
    family, kept in tier-1."""
    _launch_equivalence(tmp_path, "gossip")


@pytest.mark.slow
@pytest.mark.parametrize("name", ["easgd", "downpour"])
def test_two_process_baseline_bit_exact(name, tmp_path):
    """EASGD / DOWNPOUR exchanges are masked all-reduces pinned by
    deterministic_reduce on distributed runs. @slow: tier-1 keeps the
    gossip flagship; CI's strategy-matrix and nightly lanes run these."""
    _launch_equivalence(tmp_path, name, steps=12)


# ------------------------------------------------ property tests -------------

@settings(max_examples=20, deadline=None)
@given(r=st.integers(2, 5), n_rounds=st.integers(1, 8), seed=st.integers(0, 99))
def test_gossip_preserves_global_mean(r, n_rounds, seed):
    """Satellite: pairwise gossip preserves the exact global parameter
    mean across ANY permutation (shift) schedule. Dyadic-rational inputs
    (eighths) keep every f32 add/halve exact, so the mean is compared
    bit-exactly in f64."""
    rng = np.random.default_rng(seed)
    shifts = rng.integers(1, r, size=n_rounds)
    tree = {"w": jnp.asarray(rng.integers(-64, 64, size=(r, 5, 3)),
                             jnp.float32) / 8.0,
            "b": jnp.asarray(rng.integers(-64, 64, size=(r, 7)),
                             jnp.float32) / 8.0}
    want = {k: np.mean(np.asarray(v, np.float64), axis=0)
            for k, v in tree.items()}
    for s in shifts:
        tree = gossip_mix(tree, ex=make_exchange(r, wire_format="f32"),
                          shift=int(s))
    got = {k: np.mean(np.asarray(v, np.float64), axis=0)
           for k, v in tree.items()}
    for k in want:
        np.testing.assert_array_equal(got[k], want[k])


@settings(max_examples=10, deadline=None)
@given(alpha=st.sampled_from([0.25, 0.125, 0.0625]),
       b_max=st.integers(1, 4),
       grad=st.sampled_from([0.5, -0.25, 1.5]))
def test_easgd_center_closed_form(alpha, b_max, grad):
    """Satellite: for a constant gradient, EASGD's center equals the
    closed-form moving-average recursion, bit-exactly. R=2 with identical
    replica rows makes the masked mean exact ((x+x)/2 == x), so a scalar
    np.float32 mirror of the step builder's arithmetic reproduces params
    and center to the last bit."""
    R, n_steps, lr = 2, 16, 0.25
    cfg = DasoConfig(n_replicas=R, global_world=4 * R, b_max=b_max,
                     warmup_steps=0, cooldown_steps=0, total_steps=n_steps,
                     wire_format="f32")

    def loss_fn(params, batch):
        # d(loss)/dw = grad, constant in w
        return jnp.sum(params["w"]) * grad, {}

    cls = get_strategy("easgd")
    strat = make_strategy("easgd", loss_fn,
                          sgd(momentum=0.0, weight_decay=0.0), cfg,
                          alpha=alpha, controller=cls.make_controller(cfg))
    params0 = {"w": jnp.asarray([1.0], jnp.float32)}
    carry = strat.init_carry(params0)
    batch = {"x": jnp.zeros((R, 1, 1))}
    for t in range(n_steps):
        mode, stale = strat.next_mode(t)
        carry, _ = strat.step_fn(mode, stale)(carry, batch,
                                              jnp.asarray(lr, jnp.float32))

    # scalar f32 mirror (rows are identical, so mean == row value)
    a32, beta32 = np.float32(alpha), np.float32(alpha * R)
    p = c = np.float32(1.0)
    g, lr32 = np.float32(grad), np.float32(lr)
    last_ex = -10 ** 9
    for t in range(n_steps):
        p = np.float32(p - lr32 * g)
        if t - last_ex >= b_max:  # PeriodicController's B-spacing rule
            last_ex = t
            m = p
            p = np.float32((np.float32(1.0) - a32) * p + a32 * c)
            c = np.float32((np.float32(1.0) - beta32) * c + beta32 * m)
    params_rows, _, center_rows = carry
    np.testing.assert_array_equal(
        np.asarray(params_rows["w"]), np.full((R, 1), p, np.float32))
    np.testing.assert_array_equal(
        np.asarray(center_rows["w"]), np.full((R, 1), c, np.float32))


# ------------------------------------------------ error path -----------------

def test_get_strategy_suggests_closest():
    """Satellite regression: the KeyError lists the registered names
    sorted and suggests the closest match."""
    with pytest.raises(KeyError) as ei:
        get_strategy("gosip")
    msg = str(ei.value)
    assert str(sorted(list_strategies())) in msg
    assert "did you mean 'gossip'?" in msg
    with pytest.raises(KeyError) as ei:
        get_strategy("qqqqqq")
    assert "did you mean" not in str(ei.value)
    # list_strategies stays the sorted registry view
    assert list_strategies() == sorted(list_strategies())


def test_new_strategies_reject_overlap_and_tiny_worlds():
    key = jax.random.PRNGKey(6)
    _, loss_fn, _, _ = make_mlp_problem(key)
    opt = sgd()
    cfg = DasoConfig(n_replicas=2, global_world=8, b_max=4, overlap="one_cycle")
    for name in NEW:
        with pytest.raises(ValueError, match="overlap"):
            make_strategy(name, loss_fn, opt, cfg)
    cfg1 = DasoConfig(n_replicas=1, global_world=4, b_max=4)
    for name in NEW:
        with pytest.raises(ValueError, match="n_replicas"):
            make_strategy(name, loss_fn, opt, cfg1)
    with pytest.raises(ValueError, match="alpha"):
        make_strategy("easgd", loss_fn, opt,
                      DasoConfig(n_replicas=4, global_world=16, b_max=4),
                      alpha=0.5)
