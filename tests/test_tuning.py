"""Self-tuning topology (src/repro/topo/probe.py + controller.retune +
group reshuffling): probe determinism under deterministic reduction, the
retune no-op contract (measured == annotated must change NOTHING, down to
bit-exact training), straggler-aware reshuffle invariants (exact global
mean under any permutation; skew-sorting never increases inner-barrier
wait), checkpoint persistence of tuned periods (TrainState v3, v2 loads
as static), and the supervisor end-to-end acceptance: an injected DCN
degradation is discovered by probing and retuned within K cycles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import make_exchange, make_mlp_problem
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.checkpoint.io import (TRAIN_STATE_VERSION, TrainState,
                                 load_train_state, save_train_state)
from repro.core.daso import level_group_mean, normalize_group_perm
from repro.core.executor import MacroCycleExecutor
from repro.core.schedule import HierDasoController
from repro.optim.optimizers import sgd
from repro.optim.schedules import constant_lr
from repro.resilience.faults import FaultPlan
from repro.resilience.runtime import heartbeat_skew
from repro.resilience.supervisor import run_with_faults
from repro.topo import (TopologySpec, build_topology_strategy,
                        daso_config_from, derive_inner_periods,
                        make_controller)
from repro.topo import probe
from repro.topo.strategy import HierDasoStrategy

SPEC3 = TopologySpec.parse("chip:2 x host:2@50e9 x pod:2@25e9")  # R = 4


# ------------------------------------------------------------- probe --

def test_active_probe_deterministic_checksums():
    """Two probe rounds under deterministic reduction produce bit-identical
    reduction checksums — the probe never perturbs numerics, only timing."""
    a = probe.active_probe(SPEC3, rounds=2, deterministic=True)
    b = probe.active_probe(SPEC3, rounds=2, deterministic=True)
    assert a.checksums == b.checksums
    assert set(a.costs) == set(b.costs)
    assert all(t > 0 for t in a.costs.values())
    # targets: every non-degenerate inner level plus the outer key
    assert set(a.costs) == {"host", probe.OUTER_KEY}


def test_annotated_costs_are_pure_bandwidth():
    costs = probe.annotated_level_costs(SPEC3, param_bytes=100e9)
    assert costs["host"] == pytest.approx(100e9 / 50e9)
    assert costs[probe.OUTER_KEY] == pytest.approx(100e9 / 25e9)


@pytest.mark.parametrize("topo_str", [
    "chip:4 x pod:2",
    "chip:2 x host:2@50e9 x pod:2@25e9",
    "chip:2 x host:2@600e9 x rack:2@50e9 x pod:2@25e9",
])
def test_retuned_periods_identity_on_annotated_costs(topo_str):
    """The no-op invariant: re-deriving periods from the spec's own
    annotated costs reproduces the static lowering exactly."""
    spec = TopologySpec.parse(topo_str)
    costs = probe.annotated_level_costs(spec)
    assert probe.derive_retuned_periods(spec, costs) == \
        derive_inner_periods(spec)


# ------------------------------------------------------------ retune --

def _hier_controller(spec=SPEC3, total_steps=64):
    cfg = daso_config_from(spec, total_steps=total_steps)
    ctl = make_controller(spec, cfg, loss_window=10 ** 9)
    assert isinstance(ctl, HierDasoController)
    return ctl


def test_retune_noop_when_measured_matches_annotated():
    """measured == annotated changes nothing: same b/w, same periods, no
    events, retune returns False."""
    ctl = _hier_controller()
    ann = probe.annotated_level_costs(SPEC3)
    before = (ctl.b, ctl.w, dict(ctl.inner_periods), list(ctl.events))
    assert ctl.retune(dict(ann), annotated=ann) is False
    assert (ctl.b, ctl.w, dict(ctl.inner_periods), list(ctl.events)) == before


def test_retune_slow_outer_stretches_b_and_logs_event():
    ctl = _hier_controller()
    b0 = ctl.b
    ann = probe.annotated_level_costs(SPEC3)
    meas = dict(ann)
    meas[probe.OUTER_KEY] = ann[probe.OUTER_KEY] * 4.0  # DCN 4x slower
    assert ctl.retune(meas, annotated=ann, step=8) is True
    assert ctl.b > b0
    kinds = [k for (_, k, _) in ctl.events]
    assert "retune" in kinds and "dcn_scale" in kinds


def test_retune_rederives_inner_periods_from_cost_ratio():
    """An inner level measured faster relative to the outer gets a longer
    period (it can afford to sync more often per outer exchange — B_l
    tracks b_max * t_l / t_outer)."""
    ctl = _hier_controller()
    assert ctl.inner_periods == {"host": 2}  # static lowering at 50/25 GB/s
    ann = probe.annotated_level_costs(SPEC3)
    meas = dict(ann)
    meas["host"] = ann["host"] / 2.0      # host link measured 2x faster
    meas[probe.OUTER_KEY] = ann[probe.OUTER_KEY] * 2.0  # outer 2x slower
    assert ctl.retune(meas, annotated=ann, step=4) is True
    assert ctl.inner_periods["host"] == 1  # t_l/t_outer shrank 4x -> B_l=1
    assert ("retune_periods" in [k for (_, k, _) in ctl.events])


def test_retune_respects_pinned_periods():
    """An explicit `@period` annotation in the spec is an operator override
    the tuner must not fight."""
    spec = TopologySpec.parse("chip:2 x host:2@50e9%2 x pod:2@25e9")
    cfg = daso_config_from(spec, total_steps=64)
    ctl = make_controller(spec, cfg, loss_window=10 ** 9)
    assert ctl.pinned_periods == ("host",)
    ann = probe.annotated_level_costs(spec)
    meas = dict(ann)
    meas["host"] = ann["host"] / 8.0
    meas[probe.OUTER_KEY] = ann[probe.OUTER_KEY] * 2.0
    ctl.retune(meas, annotated=ann, step=4)
    assert ctl.inner_periods["host"] == 2  # pinned, untouched


# -------------------------------------------------------- reshuffle --

@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10 ** 6), group_size=st.sampled_from([2, 4]),
       masked=st.booleans())
def test_permuted_group_mean_preserves_global_mean(seed, group_size, masked):
    """Property: for ANY regrouping permutation, the per-group mean
    preserves the exact global (membership-weighted) mean — groups
    partition the rows, and each group mean preserves its own sum."""
    R = 8
    rng = np.random.default_rng(seed)
    perm = tuple(int(i) for i in rng.permutation(R))
    tree = {"w": jnp.asarray(rng.normal(size=(R, 5)), jnp.float32)}
    mask = tuple(1.0 if (not masked or i != 3) else 0.0 for i in range(R))
    out = level_group_mean(tree, group_size, ex=make_exchange(
        R, membership=mask, group_perm=perm, deterministic_reduce=True))
    ref = level_group_mean(tree, group_size, ex=make_exchange(
        R, membership=mask, deterministic_reduce=True))
    w_in = np.asarray(tree["w"], np.float64)
    m = np.asarray(mask, np.float64)[:, None]
    want = (w_in * m).sum(0) / m.sum()
    for got in (out, ref):
        g = np.asarray(got["w"], np.float64)
        np.testing.assert_allclose((g * m).sum(0) / m.sum(), want,
                                   rtol=1e-6, atol=1e-6)


def test_permuted_group_mean_matches_permute_then_mean_oracle():
    """slot-order semantics: permute rows -> contiguous group mean ->
    inverse-permute equals the fused path bit-for-bit."""
    R, g = 8, 2
    rng = np.random.default_rng(0)
    perm = (3, 0, 6, 1, 7, 2, 5, 4)
    x = jnp.asarray(rng.normal(size=(R, 4, 3)), jnp.float32)
    ex = make_exchange(R, group_perm=perm, deterministic_reduce=True)
    out = level_group_mean({"w": x}, g, ex=ex)["w"]
    xp = np.asarray(x)[list(perm)]
    mp = xp.reshape(R // g, g, 4, 3).mean(1, keepdims=True)
    mp = np.broadcast_to(mp, (R // g, g, 4, 3)).reshape(R, 4, 3)
    inv = np.argsort(perm)
    np.testing.assert_array_equal(np.asarray(out), mp[inv])


def test_identity_perm_normalizes_to_fast_path():
    assert normalize_group_perm((0, 1, 2, 3), 4) is None
    assert normalize_group_perm(None, 4) is None
    with pytest.raises(ValueError):
        normalize_group_perm((0, 0, 1, 2), 4)


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 10 ** 6))
def test_skew_permutation_never_increases_wasted_wait(seed):
    """Property: sorting replicas by slowdown into groups can only shrink
    the inner-barrier wait (like waits with like)."""
    rng = np.random.default_rng(seed)
    R, g = 8, 2
    slow = [float(s) for s in rng.uniform(1.0, 3.0, size=R)]
    mask = [1.0] * R
    perm = probe.skew_permutation(slow)
    before = probe.wasted_wait_s(slow, mask, g, None, 1.0)
    after = probe.wasted_wait_s(slow, mask, g, perm, 1.0)
    assert after <= before + 1e-9


def test_heartbeat_skew_normalizes_to_fastest():
    before = {0: {"step": 0, "t": 0.0}, 1: {"step": 0, "t": 0.0}}
    after = {0: {"step": 10, "t": 1.0}, 1: {"step": 5, "t": 1.0}}
    skew = heartbeat_skew(before, after)
    assert skew[0] == pytest.approx(1.0)   # fastest
    assert skew[1] == pytest.approx(2.0)   # half the rate -> 2x slowdown


# ------------------------------------------------------ persistence --

def test_controller_state_dict_persists_tuned_periods():
    ctl = _hier_controller()
    ann = probe.annotated_level_costs(SPEC3)
    meas = dict(ann)
    meas["host"] = ann["host"] / 2.0
    meas[probe.OUTER_KEY] = ann[probe.OUTER_KEY] * 2.0
    ctl.retune(meas, annotated=ann, step=4)
    tuned = dict(ctl.inner_periods)
    sd = ctl.state_dict()
    assert sd["inner_periods"] == tuned
    fresh = _hier_controller()
    fresh.load_state_dict(sd)
    assert fresh.inner_periods == tuned
    for t in range(4, 24):
        assert fresh.mode_for_step(t) == ctl.mode_for_step(t)
    # v2 dict (no inner_periods key) loads as static: lowered defaults stand
    sd_v2 = {k: v for k, v in sd.items() if k != "inner_periods"}
    legacy = _hier_controller()
    legacy.load_state_dict(sd_v2)
    assert legacy.inner_periods == {"host": 2}


def test_train_state_resume_restores_tuned_periods(tmp_path):
    """Satellite fix: load_train_state mid-retune must hand back the TUNED
    periods, not the static lowering — and the round-trip is exact."""
    ctl = _hier_controller()
    ann = probe.annotated_level_costs(SPEC3)
    meas = dict(ann)
    meas[probe.OUTER_KEY] = ann[probe.OUTER_KEY] * 4.0
    meas["host"] = ann["host"] / 2.0
    ctl.retune(meas, annotated=ann, step=8)
    carry = ({"w": jnp.ones((4, 3))},)
    state = TrainState(step=8, carry=carry, controller=ctl.state_dict(),
                       membership=[1.0] * 4, strategy="hier_daso")
    save_train_state(str(tmp_path), state)
    loaded = load_train_state(str(tmp_path))
    assert loaded.version == TRAIN_STATE_VERSION >= 3
    resumed = _hier_controller()
    resumed.load_state_dict(loaded.controller)
    assert resumed.inner_periods == ctl.inner_periods
    assert (resumed.b, resumed.w) == (ctl.b, ctl.w)
    assert resumed.state_dict() == ctl.state_dict()


# -------------------------------------------------- supervisor e2e --

def _hier_problem(key, n_steps, spec=SPEC3):
    params0, loss_fn, daso_data, _ = make_mlp_problem(key, R=spec.n_replicas)
    cfg = daso_config_from(spec, warmup_steps=2, cooldown_steps=2,
                           total_steps=n_steps)
    strat = build_topology_strategy(loss_fn, sgd(momentum=0.9), spec, cfg,
                                    loss_window=10 ** 9)
    assert isinstance(strat, HierDasoStrategy)
    return strat, params0, daso_data


def test_autotune_without_faults_is_bit_exact_noop():
    """Acceptance: autotune on a healthy cluster (measured == nominal by
    construction of the cost model) must not perturb training at all."""
    key = jax.random.PRNGKey(11)
    n_steps = 24
    cost = lambda n, s: 0.05 / s  # noqa: E731
    runs = []
    for autotune_every in (0, 1):
        strat, params0, data = _hier_problem(key, n_steps)
        rep = run_with_faults(strat, params0, data, constant_lr(0.1),
                              n_steps, FaultPlan(), t_compute_s=0.01,
                              exchange_cost_fn=cost,
                              autotune_every=autotune_every)
        runs.append(rep)
    assert runs[1].retunes == [] and runs[1].reshuffles == 0
    np.testing.assert_array_equal(
        np.asarray(runs[0].result.losses, np.float32),
        np.asarray(runs[1].result.losses, np.float32))
    for a, b in zip(jax.tree.leaves(runs[0].result.params),
                    jax.tree.leaves(runs[1].result.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_supervisor_discovers_dcn_degradation_within_k_cycles():
    """Acceptance: with oracle notification OFF (the autotune default), an
    injected DCN degradation is discovered by the probe and the schedule
    retuned within K <= 3 cycles of the event."""
    key = jax.random.PRNGKey(12)
    n_steps = 48
    degrade_step = 8
    plan = FaultPlan.from_dicts([
        {"step": degrade_step, "kind": "degrade_dcn", "factor": 0.25},
    ])
    strat, params0, data = _hier_problem(key, n_steps)
    ex = MacroCycleExecutor(strat)
    b0 = strat.controller.b
    rep = run_with_faults(strat, params0, data, constant_lr(0.1), n_steps,
                          plan, executor=ex, t_compute_s=0.01,
                          exchange_cost_fn=lambda n, s: 0.05 / s,
                          autotune_every=1)
    assert np.all(np.isfinite(rep.result.losses))
    sched = [r for r in rep.retunes if r["schedule_changed"]]
    assert sched, "probe never discovered the degradation"
    # adapt latency in cycles: first schedule-changing probe at or after
    # the degrade step, within K=3 cycle boundaries
    first = sched[0]
    degrade_cycle = min(r["cycle"] for r in rep.retunes
                        if r["step"] >= degrade_step) \
        if rep.retunes else None
    assert first["step"] >= degrade_step
    assert first["cycle"] - (degrade_cycle or first["cycle"]) <= 3
    assert strat.controller.b > b0          # schedule actually stretched
    assert ex.stats.invalidations >= 1      # retune recompiled the cycle
    kinds = [k for (_, k, _) in strat.controller.events]
    assert "retune" in kinds


def test_supervisor_reshuffles_on_straggler_skew():
    """A straggler inside one inner group triggers a probe-round reshuffle
    that pairs it with the other slow replica, shrinking wasted wait."""
    key = jax.random.PRNGKey(13)
    n_steps = 32
    plan = FaultPlan.from_dicts([
        {"step": 4, "kind": "straggle", "replica": 1, "factor": 3.0},
        {"step": 4, "kind": "straggle", "replica": 3, "factor": 3.0},
    ])
    strat, params0, data = _hier_problem(key, n_steps)
    rep = run_with_faults(strat, params0, data, constant_lr(0.1), n_steps,
                          plan, t_compute_s=0.01,
                          exchange_cost_fn=lambda n, s: 0.05 / s,
                          autotune_every=1)
    assert rep.reshuffles >= 1
    # slot order groups the two fast and the two slow replicas together
    perm = strat.group_perm
    assert perm is not None
    slow = {1, 3}
    groups = [set(perm[i:i + 2]) for i in range(0, 4, 2)]
    assert slow in groups
    # identical plan without reshuffling wastes strictly more wait
    strat2, params0b, data2 = _hier_problem(key, n_steps)
    rep2 = run_with_faults(strat2, params0b, data2, constant_lr(0.1),
                           n_steps, plan, t_compute_s=0.01,
                           exchange_cost_fn=lambda n, s: 0.05 / s,
                           autotune_every=1, reshuffle=False)
    assert rep2.reshuffles == 0
    assert rep.wasted_wait_s < rep2.wasted_wait_s


def test_reshuffled_training_stays_finite_and_trains():
    """End-to-end numerics under a live regrouping: losses finite and
    improving (the global mean is preserved, so training is unharmed)."""
    key = jax.random.PRNGKey(14)
    n_steps = 40
    plan = FaultPlan.from_dicts([
        {"step": 6, "kind": "straggle", "replica": 0, "factor": 2.5},
        {"step": 6, "kind": "straggle", "replica": 2, "factor": 2.5},
    ])
    strat, params0, data = _hier_problem(key, n_steps)
    rep = run_with_faults(strat, params0, data, constant_lr(0.1), n_steps,
                          plan, t_compute_s=0.01,
                          exchange_cost_fn=lambda n, s: 0.05 / s,
                          autotune_every=2)
    assert len(rep.result.losses) == n_steps
    assert np.all(np.isfinite(rep.result.losses))
    assert rep.result.final_loss < rep.result.losses[0]
