"""Resilience subsystem: elastic-membership exchange equivalence against a
survivors-only oracle (plus the one-collective HLO contract under a mask),
frozen ghost rows, rejoin re-seeding, controller fault adaptation, the
fault-plan DSL, supervisor end-to-end crash/rejoin runs, and the
acceptance-criterion deterministic resume (macro AND per_step executors)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import make_exchange
from conftest import make_mlp_problem as _mlp_problem

from repro.core import flatbuf
from repro.core.daso import (DasoConfig, Exchange, daso_train_step,
                             freeze_inactive, global_receive, replica_mean,
                             replica_mean_per_leaf)
from repro.core.executor import MacroCycleExecutor, make_strategy
from repro.core.schedule import DasoController
from repro.optim.optimizers import sgd
from repro.optim.schedules import constant_lr
from repro.resilience.faults import FaultEvent, FaultPlan
from repro.resilience.membership import reseed_carry
from repro.resilience.supervisor import run_with_faults
from repro.train.loop import TrainLoopConfig, run_training

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _tree(key, R=4):
    k = jax.random.split(key, 3)
    return {"w": jax.random.normal(k[0], (R, 5, 3)),
            "nested": {"b": jax.random.normal(k[1], (R, 7)),
                       "s": jax.random.normal(k[2], (R, 1))}}


# ------------------------------------------------ elastic-merge oracle --

@pytest.mark.parametrize("wire_format", ["f32", "bf16"])
@pytest.mark.parametrize("mask", [(1.0, 1.0, 0.0, 1.0),
                                  (0.0, 1.0, 0.0, 1.0),
                                  (1.0, 0.0, 0.0, 0.0)])
def test_masked_fused_mean_matches_survivor_oracle(wire_format, mask):
    """Acceptance: the membership-weighted fused exchange equals a pure-jnp
    mean computed over the surviving replicas only, broadcast to every row."""
    tree = _tree(jax.random.PRNGKey(0))
    got = replica_mean(tree, ex=make_exchange(4, membership=mask),
                       wire=wire_format)
    alive = [i for i, m in enumerate(mask) if m]

    def oracle(x):
        wd = jnp.bfloat16 if wire_format == "bf16" else x.dtype
        sub = x[jnp.asarray(alive)].astype(wd)
        # reciprocal-multiply like the arena path (x/n and x*(1/n) differ
        # at the ULP in f32; the contract is the weighting, not the op)
        m = (jnp.sum(sub, axis=0, dtype=wd)
             * jnp.asarray(1.0 / len(alive), wd)).astype(x.dtype)
        return jnp.broadcast_to(m[None], x.shape)

    want = jax.tree.map(oracle, tree)
    tol = dict(rtol=1e-7, atol=1e-7) if wire_format == "f32" \
        else dict(rtol=1e-2, atol=1e-2)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **tol)


def test_masked_per_leaf_matches_fused():
    """The legacy per-leaf path applies the identical membership weighting."""
    tree = _tree(jax.random.PRNGKey(1))
    mask = (1.0, 0.0, 1.0, 1.0)
    fused = replica_mean(tree, ex=make_exchange(4, membership=mask),
                         wire="f32")
    per_leaf = replica_mean_per_leaf(tree, "f32", mask=mask)
    for a, b in zip(jax.tree.leaves(fused), jax.tree.leaves(per_leaf)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_masked_int8_mean_close_to_survivor_oracle():
    """The int8 tier stays within quantization distance of the survivor
    oracle under a mask."""
    tree = _tree(jax.random.PRNGKey(2))
    mask = (1.0, 1.0, 0.0, 1.0)
    ex = make_exchange(4, membership=mask)
    got = replica_mean(tree, ex=ex, wire="int8")
    want = replica_mean(tree, ex=ex, wire="f32")
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=0.05)


def test_dynamic_p_receive_matches_survivor_oracle():
    """Eq. (1) under elastic membership runs with the effective world size
    P_eff = P * n_active / R, and dropped rows stay frozen."""
    key = jax.random.PRNGKey(3)
    params = _tree(key)
    inflight = jax.tree.map(lambda x: x * 0.5, params)
    mask, R, P = (1.0, 0.0, 1.0, 1.0), 4, 16
    p_eff = P * 3 / R
    ex = make_exchange(R, P, membership=mask)
    assert ex.p_eff == p_eff
    got = global_receive(params, inflight, ex=ex, staleness=2)

    def oracle(x, s):
        merged = (4.0 * x + p_eff * s) / (4.0 + p_eff)
        col = jnp.asarray(mask).reshape((R,) + (1,) * (x.ndim - 1))
        return jnp.where(col > 0, merged, x)

    want = jax.tree.map(oracle, params, inflight)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_one_collective_holds_under_membership_mask():
    """Acceptance: the PR-2 one-collective-per-sync HLO contract survives
    elastic membership — the mask multiply fuses, it must not add or split
    collectives. 2-virtual-device pod mesh in a subprocess."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    script = """
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.daso import DasoConfig, Exchange, blocking_sync
        from repro.launch.hlo_stats import collective_stats

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2,), ("pod",))
        sh = NamedSharding(mesh, P("pod"))
        tree = {f"w{i}": jax.ShapeDtypeStruct((2, 32, 3 + i), jnp.float32)
                for i in range(6)}
        mask = (1.0, 0.0)
        for wf in ("f32", "bf16", "int8"):
            ex = Exchange.of(DasoConfig(n_replicas=2, global_world=2,
                                        wire_format=wf), membership=mask)
            fn = lambda t, ex=ex: blocking_sync(t, ex=ex)
            hlo = jax.jit(fn, in_shardings=({k: sh for k in tree},)).lower(
                tree).compile().as_text()
            stats = collective_stats(hlo, {"pod": 2})
            n = sum(v["count"] for k, v in stats.items()
                    if isinstance(v, dict) and k.startswith("all-reduce"))
            assert n == 1, (wf, n)
        print("MASKED ONE COLLECTIVE OK")
    """
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                       capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "MASKED ONE COLLECTIVE OK" in r.stdout


# ----------------------------------------------------- frozen ghosts --

def test_elastic_step_freezes_dead_rows():
    """A dropped replica's params/opt rows are ghosts: every step variant
    leaves them bit-identical while active rows train."""
    key = jax.random.PRNGKey(4)
    params0, loss_fn, daso_data, _ = _mlp_problem(key, R=4)
    cfg = DasoConfig(n_replicas=4, global_world=16, b_max=4)
    opt = sgd(momentum=0.9)
    mask = (1.0, 1.0, 0.0, 1.0)
    from repro.core.daso import replicate_params
    params = replicate_params(params0, 4)
    opt_state = replicate_params(opt.init(params0), 4)
    inflight = jax.tree.map(jnp.array, params)
    batch = daso_data(0)
    for mode in ("local", "send", "receive", "blocking", "hard_avg"):
        step = jax.jit(daso_train_step(
            loss_fn, opt, cfg, mode=mode, staleness=1,
            ex=Exchange.of(cfg, membership=mask)))
        p2, o2, _, m = step(params, opt_state, inflight, batch, 0.1)
        for a, b in zip(jax.tree.leaves(p2), jax.tree.leaves(params)):
            np.testing.assert_array_equal(np.asarray(a[2]), np.asarray(b[2]))
            assert not np.allclose(np.asarray(a[0]), np.asarray(b[0]))
        for a, b in zip(jax.tree.leaves(o2), jax.tree.leaves(opt_state)):
            np.testing.assert_array_equal(np.asarray(a[2]), np.asarray(b[2]))
        # reported loss averages active replicas only
        lr_ = np.asarray(m["loss_per_replica"])
        np.testing.assert_allclose(
            float(m["loss"]), float((lr_[0] + lr_[1] + lr_[3]) / 3),
            rtol=1e-6)


def test_freeze_inactive_identity_without_mask():
    new = {"w": jnp.ones((2, 3))}
    assert freeze_inactive(new, {"w": jnp.zeros((2, 3))}, None) is new


def test_reseed_carry_bootstraps_joiner_from_donor_mean():
    key = jax.random.PRNGKey(5)
    carry = (_tree(key), {"mu": _tree(jax.random.fold_in(key, 1))})
    donor_mask = (1.0, 1.0, 0.0, 1.0)
    out = reseed_carry(carry, donor_mask, [2])
    for x, y in zip(jax.tree.leaves(carry), jax.tree.leaves(out)):
        x, y = np.asarray(x), np.asarray(y)
        want = (x[0] + x[1] + x[3]) / 3
        np.testing.assert_allclose(y[2], want, rtol=1e-6, atol=1e-7)
        np.testing.assert_array_equal(y[[0, 1, 3]], x[[0, 1, 3]])
    with pytest.raises(ValueError, match="donor and joiner"):
        reseed_carry(carry, (1.0,) * 4, [2])


# ------------------------------------------------- membership guards --

def test_normalize_membership_validation():
    assert flatbuf.normalize_membership(None, 4) is None
    assert flatbuf.normalize_membership((1, 1, 1, 1), 4) is None
    assert flatbuf.normalize_membership([1, 0, 1, 1], 4) == (1.0, 0.0, 1.0,
                                                            1.0)
    with pytest.raises(ValueError, match="entries"):
        flatbuf.normalize_membership((1.0, 0.0), 4)
    with pytest.raises(ValueError, match="no active"):
        flatbuf.normalize_membership((0.0,) * 4, 4)
    with pytest.raises(ValueError, match="0/1"):
        flatbuf.normalize_membership((0.5, 1.0), 2)


# --------------------------------------------- controller adaptation --

def test_controller_membership_change_flushes_plateau_stats():
    cfg = DasoConfig(n_replicas=4, global_world=16, b_max=4)
    c = DasoController(cfg, loss_window=5)
    for _ in range(3):
        c.observe_loss(1.0)
    assert c.window_remaining() == 2
    c.notify_membership_change(3, 3)
    assert c.window_remaining() == 5  # window discarded
    assert c.events == [(3, "membership", 3.0)]
    # a post-fault loss bump must not immediately count toward the
    # plateau patience (baseline restarted)
    b0 = c.b
    for _ in range(5):
        c.observe_loss(10.0)
    assert c.b == b0


def test_controller_dcn_scale_stretches_b():
    cfg = DasoConfig(n_replicas=4, global_world=16, b_max=4)
    c = DasoController(cfg, loss_window=5)
    c.notify_dcn_scale(0.25, step=7)
    assert c.b == 16 and c.w == 4       # b_max/scale, W = B/4
    c.notify_dcn_scale(0.001, step=8)
    assert c.b == 16                    # capped at 4*b_max
    c.notify_dcn_scale(1.0, step=9)
    assert c.b == 4 and c.w == 1        # clamped back to b_max
    with pytest.raises(ValueError):
        c.notify_dcn_scale(0.0)


# ---------------------------------------------------- fault-plan DSL --

def test_fault_plan_json_roundtrip_and_queries():
    plan = FaultPlan.from_dicts([
        {"step": 20, "kind": "rejoin", "replica": 1},
        {"step": 5, "kind": "crash", "replica": 1},
        {"step": 8, "kind": "straggle", "replica": 0, "factor": 3.0},
        {"step": 10, "kind": "degrade_dcn", "factor": 0.5},
        {"step": 15, "kind": "restore_dcn"},
    ])
    plan.validate(4)
    assert [e.step for e in plan.events] == [5, 8, 10, 15, 20]  # sorted
    assert FaultPlan.from_json(plan.to_json()) == plan
    assert plan.boundaries() == [5, 8, 10, 15, 20]
    assert plan.next_boundary_after(8) == 10
    assert plan.next_boundary_after(20) is None
    assert plan.membership_at(4, 4) == (1.0,) * 4
    assert plan.membership_at(5, 4) == (1.0, 0.0, 1.0, 1.0)
    assert plan.membership_at(20, 4) == (1.0,) * 4
    assert plan.dcn_scale_at(12) == 0.5 and plan.dcn_scale_at(15) == 1.0
    assert plan.slowdowns_at(9, 4) == (3.0, 1.0, 1.0, 1.0)


def test_fault_plan_validation_rejects_incoherent_scripts():
    with pytest.raises(ValueError, match="already down"):
        FaultPlan.from_dicts([{"step": 1, "kind": "crash", "replica": 0},
                              {"step": 2, "kind": "crash",
                               "replica": 0}]).validate(2)
    with pytest.raises(ValueError, match="already active"):
        FaultPlan.from_dicts([{"step": 1, "kind": "rejoin",
                               "replica": 0}]).validate(2)
    with pytest.raises(ValueError, match="no active"):
        FaultPlan.from_dicts([{"step": 1, "kind": "crash", "replica": 0},
                              {"step": 2, "kind": "crash",
                               "replica": 1}]).validate(2)
    with pytest.raises(ValueError, match="outside"):
        FaultPlan.from_dicts([{"step": 1, "kind": "crash",
                               "replica": 9}]).validate(2)
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultEvent(step=1, kind="meteor")
    with pytest.raises(ValueError, match="bandwidth fraction"):
        FaultEvent(step=1, kind="degrade_dcn", factor=2.0)


# ------------------------------------------------- supervisor e2e -----

def _daso_strategy(loss_fn, n_steps, R=4, loss_window=10):
    cfg = DasoConfig(n_replicas=R, global_world=4 * R, b_max=4,
                     warmup_steps=n_steps // 10,
                     cooldown_steps=n_steps // 10, total_steps=n_steps)
    return make_strategy("daso", loss_fn, sgd(momentum=0.9), cfg,
                         controller=DasoController(cfg,
                                                   loss_window=loss_window))


def test_supervisor_crash_rejoin_end_to_end():
    key = jax.random.PRNGKey(6)
    params0, loss_fn, daso_data, _ = _mlp_problem(key, R=4)
    n_steps = 40
    plan = FaultPlan.from_dicts([
        {"step": 10, "kind": "crash", "replica": 3},
        {"step": 14, "kind": "degrade_dcn", "factor": 0.25},
        {"step": 22, "kind": "restore_dcn"},
        {"step": 26, "kind": "rejoin", "replica": 3},
    ])
    strat = _daso_strategy(loss_fn, n_steps)
    ex = MacroCycleExecutor(strat)
    report = run_with_faults(strat, params0, daso_data, constant_lr(0.1),
                             n_steps, plan, executor=ex, t_compute_s=0.1,
                             exchange_cost_fn=lambda n, s: 0.05 / s)
    res = report.result
    assert len(res.losses) == n_steps
    assert np.all(np.isfinite(res.losses))
    assert res.final_loss < res.losses[0]          # it still trains
    # every membership event invalidated the compiled-cycle cache
    assert report.invalidations == 2
    assert ex.stats.invalidations == 2
    assert [mask for _, mask in report.membership_timeline] == \
        [(1.0,) * 4, (1.0, 1.0, 1.0, 0.0), (1.0,) * 4]
    assert [(e["step"], e["kind"]) for e in report.applied] == \
        [(10, "crash"), (14, "degrade_dcn"), (22, "restore_dcn"),
         (26, "rejoin")]
    # recovery cost recorded for both membership events
    assert len(report.recovery_s()) == 2
    assert all(t > 0 for t in report.recovery_s())
    # simulated clock: 40 steps of compute + degraded exchanges > fault-free
    assert report.simulated_time_s > 40 * 0.1
    # fault-free comparison run: losses should end in the same regime
    strat2 = _daso_strategy(loss_fn, n_steps)
    clean = run_with_faults(strat2, params0, daso_data, constant_lr(0.1),
                            n_steps, FaultPlan())
    assert abs(clean.result.final_loss - res.final_loss) < 0.5


def test_finalize_params_skips_dead_replica_rows():
    """Regression: with replica 0 crashed (and never rejoined), the final
    params must come from an ACTIVE replica, not row 0's frozen ghost."""
    key = jax.random.PRNGKey(8)
    params0, loss_fn, daso_data, _ = _mlp_problem(key, R=4)
    strat = _daso_strategy(loss_fn, 20)
    strat.set_membership([0.0, 1.0, 1.0, 1.0])
    carry = strat.init_carry(params0)
    # make every row distinct so the selected row is identifiable
    carry = (jax.tree.map(
        lambda x: x + jnp.arange(4.0).reshape((4,) + (1,) * (x.ndim - 1)),
        carry[0]),) + carry[1:]
    out = strat.finalize_params(carry)
    for leaf, src in zip(jax.tree.leaves(out), jax.tree.leaves(carry[0])):
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(src[1]))
    # end-to-end: crash replica 0 mid-run, no rejoin — reported params are
    # the survivors' trained state (they keep improving), not the ghost
    plan = FaultPlan.from_dicts([{"step": 8, "kind": "crash", "replica": 0}])
    strat2 = _daso_strategy(loss_fn, 40)
    rep = run_with_faults(strat2, params0, daso_data, constant_lr(0.1), 40,
                          plan)
    eval_batch = daso_data(999)
    flat = {k: v.reshape((-1,) + v.shape[2:]) for k, v in eval_batch.items()}
    final_loss = float(loss_fn(rep.result.params, flat)[0])
    init_loss = float(loss_fn(params0, flat)[0])
    assert final_loss < 0.5 * init_loss  # trained well past the early ghost


def test_supervisor_matches_plain_executor_without_faults():
    """An empty fault plan must be a no-op wrapper: identical losses and
    params to run_compiled_training."""
    from repro.core.executor import run_compiled_training

    key = jax.random.PRNGKey(7)
    params0, loss_fn, daso_data, _ = _mlp_problem(key, R=2)
    n_steps = 24
    a = _daso_strategy(loss_fn, n_steps, R=2)
    b = _daso_strategy(loss_fn, n_steps, R=2)
    rep = run_with_faults(a, params0, daso_data, constant_lr(0.1), n_steps,
                          FaultPlan())
    ref = run_compiled_training(b, params0, daso_data, constant_lr(0.1),
                                n_steps)
    np.testing.assert_allclose(np.asarray(rep.result.losses, np.float32),
                               np.asarray(ref.losses, np.float32),
                               rtol=1e-6, atol=1e-7)
    for x, y in zip(jax.tree.leaves(rep.result.params),
                    jax.tree.leaves(ref.params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-6, atol=1e-7)


# ------------------------------------------- deterministic resume -----

@pytest.mark.parametrize("executor", ["macro", "per_step"])
def test_deterministic_resume_matches_uninterrupted(executor, tmp_path):
    """Acceptance: a run interrupted at step k and resumed from the
    TrainState checkpoint reproduces the uninterrupted run's losses and
    final params allclose at f32 — for both executors. (On this setup the
    match is in fact bit-exact.)"""
    key = jax.random.PRNGKey(0)
    params0, loss_fn, daso_data, _ = _mlp_problem(key)
    n_steps = 40
    base = TrainLoopConfig(strategy="daso", n_steps=n_steps, n_replicas=2,
                           loss_window=10, executor=executor)
    fresh = run_training(loss_fn, params0, daso_data, base, log=None)

    ckpt = TrainLoopConfig(**{**base.__dict__, "ckpt_every": 10,
                              "ckpt_dir": str(tmp_path)})
    run_training(loss_fn, params0, daso_data, ckpt, log=None)
    states = sorted(os.listdir(tmp_path))
    assert states, "no TrainState checkpoints written"
    mid = states[min(1, len(states) - 1)]
    k = int(mid.split("_")[1])
    assert 0 < k < n_steps

    resume = TrainLoopConfig(**{**base.__dict__,
                                "resume_from": str(tmp_path / mid)})
    resumed = run_training(loss_fn, params0, daso_data, resume, log=None)
    # full loss trace (prefix stitched from the checkpoint) matches
    np.testing.assert_allclose(np.asarray(resumed.losses, np.float32),
                               np.asarray(fresh.losses, np.float32),
                               rtol=1e-6, atol=1e-7)
    for a, b in zip(jax.tree.leaves(resumed.params),
                    jax.tree.leaves(fresh.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-6, atol=1e-7)
    # schedule-identical, not just numerically close
    assert [h[1] for h in resumed.controller.history] == \
        [h[1] for h in fresh.controller.history]
