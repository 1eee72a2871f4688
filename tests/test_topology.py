"""Topology subsystem (src/repro/topo/): spec round-trip properties,
lowering structure, the 2-level bit-exactness acceptance contract (a
2-level spec must reproduce legacy training losses/params EXACTLY, both
executors), 3-level end-to-end training, per-level group-mean semantics,
topology-node fault addressing, and the per-level one-collective HLO
contract (subprocess, forced multi-device mesh)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import make_exchange, make_mlp_problem
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import flatbuf
from repro.core.daso import DasoConfig, level_group_mean
from repro.core.executor import make_strategy, run_compiled_training
from repro.core.schedule import (DasoController, HierDasoController,
                                 join_mode, split_mode)
from repro.core.simulator import run_per_step_training
from repro.optim.optimizers import sgd
from repro.optim.schedules import constant_lr
from repro.resilience.faults import FaultEvent, FaultPlan
from repro.topo import (Level, TopologySpec, build_topology_strategy,
                        daso_config_from, derive_inner_periods,
                        make_controller)
from repro.topo.strategy import HierDasoStrategy

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# ------------------------------------------------------------ spec parsing --

@settings(max_examples=30)
@given(n_levels=st.integers(2, 5),
       seed=st.integers(0, 10 ** 6))
def test_spec_roundtrips_str_and_json(n_levels, seed):
    """Property: any spec survives to_str -> parse and to_json -> from_json
    exactly (== on the frozen dataclasses, floats included)."""
    import random
    rng = random.Random(seed)
    pool = ["chip", "gpu", "host", "rack", "pod", "dc", "zone", "l8"]
    names = rng.sample(pool, n_levels)
    levels = tuple(
        Level(name=names[i], fanout=rng.randint(1, 8),
              bandwidth=rng.choice([1e9, 25e9, 50e9, 600e9, 1.5e10]),
              latency=rng.choice([0.0, 1e-6, 3e-5]),
              period=rng.choice([None, 1, 2, 4, 8]))
        for i in range(n_levels))
    spec = TopologySpec(levels)
    assert TopologySpec.parse(spec.to_str()) == spec
    assert TopologySpec.from_json(spec.to_json()) == spec
    assert TopologySpec.load(spec.to_str()) == spec
    assert TopologySpec.load(spec.to_json()) == spec


def test_spec_grammar_defaults_and_errors():
    spec = TopologySpec.parse("chip:4 × host:2@5e10/1e-5%3, pod:2")
    assert [lvl.name for lvl in spec.levels] == ["chip", "host", "pod"]
    assert spec.level("host").period == 3
    assert spec.level("host").bandwidth == 5e10
    # omitted fields take per-depth defaults
    assert spec.level("chip").bandwidth == 600e9
    assert spec.level("pod").bandwidth == 25e9
    with pytest.raises(ValueError):
        TopologySpec.parse("chip:4")              # one level
    with pytest.raises(ValueError):
        TopologySpec.parse("chip:4 x chip:2")     # duplicate names
    with pytest.raises(ValueError):
        TopologySpec.parse("chip:0 x pod:2")      # bad fanout
    with pytest.raises(ValueError):
        TopologySpec.parse("Chip:4 x pod:2")      # bad name
    with pytest.raises(ValueError):
        Level("pod", 2, -1.0, 0.0)                # bad bandwidth


def test_spec_structure_and_groups():
    spec = TopologySpec.parse("chip:4 x host:2 x pod:3")
    assert spec.local_world == 4
    assert spec.n_replicas == 6
    assert spec.world == 24
    assert spec.group_size("host") == 2
    assert spec.group_size("pod") == 6
    assert spec.inner_names() == ("host",)
    assert spec.mesh_axis_names() == ("pod", "host", "chip")
    assert spec.mesh_shape() == (3, 2, 4)
    with pytest.raises(ValueError):
        spec.group_size("chip")  # level 0 is not a replica group


def test_spec_names_containing_x_and_digits():
    """Separator/addressing edge cases: 'x' inside a level name must not
    split the spec, and a level name ending in a digit stays addressable
    in node paths."""
    spec = TopologySpec.parse("proxy:4 x box:2 x pod:2")
    assert [lvl.name for lvl in spec.levels] == ["proxy", "box", "pod"]
    assert TopologySpec.parse(spec.to_str()) == spec
    spec2 = TopologySpec.parse("chip:2 × tier2:2 × pod:2")
    assert spec2.replicas_of("pod1/tier21") == (3,)
    assert TopologySpec.parse(spec2.to_str()) == spec2


def test_fanout_one_intermediate_level_is_elided():
    """A degenerate (group-size-1) intermediate level is legal but its
    sync is a no-op: the schedule elides it and training runs clean."""
    spec = TopologySpec.parse("chip:4 x host:1 x pod:2")
    assert derive_inner_periods(spec, b_max=4) == {}
    key = jax.random.PRNGKey(5)
    params0, loss_fn, daso_data, _ = make_mlp_problem(key, R=2)
    cfg = daso_config_from(spec, warmup_steps=2, cooldown_steps=2,
                           total_steps=16)
    strat = build_topology_strategy(loss_fn, sgd(momentum=0.9), spec, cfg,
                                    loss_window=10 ** 9)
    res = run_compiled_training(strat, params0, daso_data,
                                constant_lr(0.1), 16)
    assert np.all(np.isfinite(res.losses))
    assert all("host" not in h[1] for h in res.controller.history)
    # the analytic model elides the same level instead of crashing
    from benchmarks.comm_model import topology_level_costs
    rows = topology_level_costs(spec, 1e8)
    assert [r["name"] for r in rows] == ["chip", "pod"]


def test_replicas_of_node_paths():
    spec = TopologySpec.parse("chip:2 x host:2 x pod:3")
    assert spec.replicas_of("pod0") == (0, 1)
    assert spec.replicas_of("pod2") == (4, 5)
    assert spec.replicas_of("pod1/host1") == (3,)
    with pytest.raises(ValueError):
        spec.replicas_of("host0")          # must start outermost
    with pytest.raises(ValueError):
        spec.replicas_of("pod3")           # index out of range
    with pytest.raises(ValueError):
        spec.replicas_of("pod0/chip1")     # level 0 not addressable
    with pytest.raises(ValueError):
        spec.replicas_of("pod0/banana1")   # unknown level


# --------------------------------------------------------------- schedule --

def test_derived_inner_periods_track_bandwidth_ratio():
    spec = TopologySpec.parse("chip:4 x host:2@50e9 x pod:2@25e9")
    assert derive_inner_periods(spec, b_max=4) == {"host": 2}
    # explicit %period wins over the derived value
    spec2 = TopologySpec.parse("chip:4 x host:2@50e9%1 x pod:2@25e9")
    assert derive_inner_periods(spec2, b_max=4) == {"host": 1}
    # a level as slow as the outermost syncs at b_max
    spec3 = TopologySpec.parse("chip:4 x host:2@25e9 x pod:2@25e9")
    assert derive_inner_periods(spec3, b_max=4) == {"host": 4}


def test_hier_controller_mode_tokens():
    spec = TopologySpec.parse("chip:4 x host:2 x pod:2")
    cfg = daso_config_from(spec, warmup_steps=2, cooldown_steps=2,
                           total_steps=20)
    c = make_controller(spec, cfg, loss_window=10 ** 9)
    assert isinstance(c, HierDasoController)
    modes = [c.mode_for_step(t)[0] for t in range(12)]
    # warm-up blocking steps elide inner syncs (already a full-world sync)
    assert modes[0] == modes[1] == "blocking"
    # cycling: host (B_l = 2) ticks on every second step
    for t, m in enumerate(modes[2:], start=2):
        outer, inner = split_mode(m)
        assert inner == (("host",) if (t + 1) % 2 == 0 else ())
    # history records the joined tokens and both tallies see them
    counts = c.level_sync_counts()
    assert counts["host"] == sum(1 for m in modes if "host" in m)
    assert join_mode("send", ("host",)) == "send+host"
    assert split_mode("send+host,rack") == ("send", ("host", "rack"))
    assert split_mode("local") == ("local", ())


def test_two_level_controller_is_plain_daso_controller():
    """Lowering a 2-level spec must give the unmodified legacy controller,
    so its histories are byte-identical to pre-topology runs."""
    spec = TopologySpec.two_level(local_world=4, n_replicas=4)
    cfg = daso_config_from(spec)
    c = make_controller(spec, cfg)
    assert type(c) is DasoController


# ---------------------------------------------------------- group mean ------

def _tree(key, R):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"a": jax.random.normal(k1, (R, 3, 2)),
            "b": {"w": jax.random.normal(k2, (R, 5)),
                  "n": jnp.arange(R * 4, dtype=jnp.int32).reshape(R, 4)}}


@settings(max_examples=15, deadline=None)
@given(groups=st.integers(2, 4), per=st.integers(1, 3),
       seed=st.integers(0, 100))
def test_level_group_mean_matches_per_group_oracle(groups, per, seed):
    """Property: the fused arena group mean equals an explicit per-group
    jnp mean for every leaf, any group structure."""
    R = groups * per
    tree = _tree(jax.random.PRNGKey(seed), R)
    got = level_group_mean(tree, per, ex=make_exchange(R))

    def oracle(x):
        xr = x.reshape((groups, per) + x.shape[1:])
        if jnp.issubdtype(x.dtype, jnp.floating):
            m = xr.astype(jnp.float32).mean(axis=1, keepdims=True)
            m = m.astype(x.dtype)
        else:
            m = jnp.round(
                xr.astype(jnp.float32).mean(axis=1, keepdims=True)
            ).astype(x.dtype)
        return jnp.broadcast_to(m, xr.shape).reshape(x.shape)

    want = jax.tree.map(oracle, tree)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-6, atol=1e-6)


def test_level_group_mean_membership_mask():
    """Masked group mean averages only each group's active rows; a fully
    dead group contributes zeros (its rows are frozen ghosts upstream)."""
    R, g = 4, 2
    x = {"w": jnp.arange(R * 2, dtype=jnp.float32).reshape(R, 2)}
    mask = flatbuf.normalize_membership((1.0, 0.0, 1.0, 1.0), R)
    got = level_group_mean(x, g, ex=make_exchange(R, membership=mask))["w"]
    # group 0 = rows {0,1}, only row 0 active -> mean = row0
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(x["w"][0]))
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(x["w"][0]))
    # group 1 = rows {2,3}, both active -> plain mean
    want = np.asarray((x["w"][2] + x["w"][3]) / 2)
    np.testing.assert_allclose(np.asarray(got[2]), want)
    np.testing.assert_allclose(np.asarray(got[3]), want)
    # group size == R degenerates to the full replica mean
    full = level_group_mean(x, R, ex=make_exchange(R))["w"]
    np.testing.assert_allclose(np.asarray(full[0]),
                               np.asarray(x["w"].mean(0)))
    with pytest.raises(ValueError):
        level_group_mean(x, 3, ex=make_exchange(R))  # R=4 not divisible
    # inner levels cross at f32 whatever the outermost wire tier
    i8 = make_exchange(R, membership=mask, wire_format="int8")
    np.testing.assert_array_equal(
        np.asarray(level_group_mean(x, g, ex=i8)["w"]), np.asarray(got))


# ----------------------------------------------- 2-level bit-exactness ------

@pytest.mark.parametrize("executor", ["macro", "per_step"])
def test_two_level_spec_bit_exact_with_legacy(executor):
    """ACCEPTANCE: a 2-level topology spec reproduces current training
    losses BIT-exactly (== on floats, array_equal on params) on both
    executors — via the lowered stock strategy AND via the hier_daso
    machinery forced onto the 2-level spec."""
    key = jax.random.PRNGKey(0)
    params0, loss_fn, daso_data, _ = make_mlp_problem(key, R=4)
    n_steps = 40
    opt = sgd(momentum=0.9, weight_decay=1e-4)
    spec = TopologySpec.parse("chip:4 x pod:4")
    legacy_cfg = DasoConfig(n_replicas=4, global_world=16, b_max=4,
                            warmup_steps=4, cooldown_steps=4,
                            total_steps=n_steps)
    assert daso_config_from(spec, warmup_steps=4, cooldown_steps=4,
                            total_steps=n_steps) == legacy_cfg

    def run(strategy):
        runner = (run_compiled_training if executor == "macro"
                  else run_per_step_training)
        return runner(strategy, params0, daso_data, constant_lr(0.1),
                      n_steps)

    legacy = run(make_strategy(
        "daso", loss_fn, opt, legacy_cfg,
        controller=DasoController(legacy_cfg, loss_window=10)))
    lowered = run(build_topology_strategy(
        loss_fn, opt, spec,
        daso_config_from(spec, warmup_steps=4, cooldown_steps=4,
                         total_steps=n_steps), loss_window=10))
    forced_hier = run(HierDasoStrategy(
        loss_fn, opt, legacy_cfg, topo=spec,
        controller=DasoController(legacy_cfg, loss_window=10)))

    for got in (lowered, forced_hier):
        assert got.losses == legacy.losses
        for a, b in zip(jax.tree.leaves(got.params),
                        jax.tree.leaves(legacy.params)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        assert [h[1] for h in got.controller.history] == \
               [h[1] for h in legacy.controller.history]


# --------------------------------------------------- 3-level end-to-end -----

def test_three_level_trains_on_both_executors():
    """A 3-level spec trains end-to-end, the macro path matches the
    per-step reference, and the schedule actually exercised the
    intermediate level."""
    key = jax.random.PRNGKey(1)
    params0, loss_fn, daso_data, _ = make_mlp_problem(key, R=4)
    n_steps = 40
    opt = sgd(momentum=0.9, weight_decay=1e-4)
    spec = TopologySpec.parse("chip:4 x host:2 x pod:2")

    def mk():
        cfg = daso_config_from(spec, warmup_steps=4, cooldown_steps=4,
                               total_steps=n_steps)
        return build_topology_strategy(loss_fn, opt, spec, cfg,
                                       loss_window=10)

    macro = run_compiled_training(mk(), params0, daso_data,
                                  constant_lr(0.1), n_steps)
    ref = run_per_step_training(mk(), params0, daso_data,
                                constant_lr(0.1), n_steps)
    assert np.all(np.isfinite(macro.losses))
    assert macro.final_loss < macro.losses[0]
    np.testing.assert_allclose(np.asarray(macro.losses, np.float32),
                               np.asarray(ref.losses, np.float32),
                               rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(macro.params),
                    jax.tree.leaves(ref.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-6)
    counts = macro.controller.level_sync_counts()
    assert counts.get("host", 0) > 0
    assert [h[1] for h in macro.controller.history] == \
           [h[1] for h in ref.controller.history]


def test_topology_via_train_loop_config():
    """TrainLoopConfig.topology threads a spec end-to-end (the launcher
    surface), deriving R/world from the fanouts."""
    from repro.train.loop import TrainLoopConfig, build_strategy, run_training

    key = jax.random.PRNGKey(2)
    params0, loss_fn, daso_data, _ = make_mlp_problem(key, R=4)
    cfg = TrainLoopConfig(strategy="daso", n_steps=24,
                          topology="chip:2 x host:2 x pod:2",
                          loss_window=10, log_every=1000)
    strat = build_strategy(loss_fn, cfg, sgd())
    assert isinstance(strat, HierDasoStrategy)
    assert strat.cfg.n_replicas == 4 and strat.cfg.global_world == 8
    res = run_training(loss_fn, params0, daso_data, cfg, log=None)
    assert np.all(np.isfinite(res.losses))
    with pytest.raises(ValueError):
        build_strategy(loss_fn, TrainLoopConfig(
            strategy="sync", topology="chip:2 x pod:2"), sgd())
    with pytest.raises(ValueError):
        build_strategy(loss_fn, TrainLoopConfig(strategy="hier_daso"),
                       sgd())


# ------------------------------------------------------- faults on nodes ----

def test_fault_plan_topology_node_resolution():
    spec = TopologySpec.parse("chip:2 x host:2 x pod:2")
    plan = FaultPlan((FaultEvent(step=6, kind="crash", node="pod1"),
                      FaultEvent(step=9, kind="straggle", node="pod0/host1",
                                 factor=2.0),
                      FaultEvent(step=12, kind="rejoin", node="pod1")))
    with pytest.raises(ValueError):
        plan.validate(4)  # unresolved node events must be rejected
    concrete = plan.resolve(spec)
    concrete.validate(4)
    assert [(e.step, e.kind, e.replica) for e in concrete.events] == \
        [(6, "crash", 2), (6, "crash", 3), (9, "straggle", 1),
         (12, "rejoin", 2), (12, "rejoin", 3)]
    # wire format round-trips the node field
    assert FaultPlan.from_json(plan.to_json()) == plan
    with pytest.raises(ValueError):
        FaultEvent(step=0, kind="crash")  # neither replica nor node
    with pytest.raises(ValueError):
        FaultEvent(step=0, kind="crash", replica=1, node="pod0")  # both
    with pytest.raises(ValueError):
        FaultEvent(step=0, kind="degrade_dcn", node="pod0", factor=0.5)


def test_supervisor_resolves_node_faults_on_two_level_lowered_strategy():
    """A 2-level spec lowers to the stock DasoStrategy, but the lowering
    stamps the spec on it so the supervisor still auto-resolves
    node-addressed fault plans (the docs/topologies.md promise)."""
    from repro.core.executor import DasoStrategy
    from repro.resilience.supervisor import run_with_faults

    key = jax.random.PRNGKey(6)
    params0, loss_fn, daso_data, _ = make_mlp_problem(key, R=4)
    spec = TopologySpec.parse("chip:4 x pod:4")
    cfg = daso_config_from(spec, total_steps=16)
    strat = build_topology_strategy(loss_fn, sgd(momentum=0.9), spec, cfg,
                                    loss_window=10 ** 9)
    assert type(strat) is DasoStrategy and strat.topo == spec
    plan = FaultPlan((FaultEvent(step=4, kind="crash", node="pod3"),))
    report = run_with_faults(strat, params0, daso_data, constant_lr(0.1),
                             16, plan)
    assert np.all(np.isfinite(report.result.losses))
    assert dict(report.membership_timeline)[4] == (1.0, 1.0, 1.0, 0.0)


def test_supervisor_replays_node_fault_on_three_level_topology():
    """Crash a whole pod (2 of 4 replicas) mid-run through the supervisor;
    training survives, membership timeline shows the subtree drop, and the
    run stays finite."""
    from repro.resilience.supervisor import run_with_faults

    key = jax.random.PRNGKey(3)
    params0, loss_fn, daso_data, _ = make_mlp_problem(key, R=4)
    spec = TopologySpec.parse("chip:2 x host:2 x pod:2")
    cfg = daso_config_from(spec, warmup_steps=2, cooldown_steps=2,
                           total_steps=30)
    strat = build_topology_strategy(loss_fn, sgd(momentum=0.9), spec, cfg,
                                    loss_window=10 ** 9)
    plan = FaultPlan((FaultEvent(step=8, kind="crash", node="pod1"),
                      FaultEvent(step=20, kind="rejoin", node="pod1")))
    report = run_with_faults(strat, params0, daso_data, constant_lr(0.1),
                             30, plan)
    assert np.all(np.isfinite(report.result.losses))
    masks = dict(report.membership_timeline)  # last mask per step wins
    assert masks[8] == (1.0, 1.0, 0.0, 0.0)
    assert masks[20] == (1.0, 1.0, 1.0, 1.0)
    # one invalidation per expanded per-replica event (2 crash + 2 rejoin);
    # recompiles still only happen at the next dispatched cycle
    assert report.invalidations == 4


# --------------------------------------------------- comm-model lowering ----

def test_topology_comm_model_levels():
    from benchmarks.comm_model import topology_level_costs, topology_step_s

    spec = TopologySpec.parse("chip:4 x host:2@50e9 x pod:2@25e9")
    rows = topology_level_costs(spec, 4e8, b_max=4, ib_eff=0.1)
    assert [r["name"] for r in rows] == ["chip", "host", "pod"]
    assert rows[0]["period"] == 1 and rows[0]["wire"] == "f32"
    assert rows[1]["period"] == 2
    assert rows[2]["period"] == 4 and rows[2]["wire"] == "bf16"
    # bf16 outermost carries half the bytes of the f32 inner tiers
    assert rows[2]["bytes_per_sync"] == rows[1]["bytes_per_sync"] / 2
    # per-step amortization divides by the period
    assert rows[1]["step_s"] == pytest.approx(rows[1]["sync_s"] / 2)
    t = topology_step_s(spec, 4e8, t_compute_s=0.1, ib_eff=0.1)
    assert t > 0.1  # compute plus strictly positive comm terms
    # an outer %period pin changes the derived inner periods exactly as
    # the executed schedule does (lower.daso_config_from's override)
    pinned = TopologySpec.parse("chip:4 x host:2@50e9 x pod:2@25e9%8")
    rows_p = topology_level_costs(pinned, 4e8, b_max=4, ib_eff=0.1)
    assert rows_p[1]["period"] == 4 and rows_p[2]["period"] == 8


# ----------------------------------------------------- HLO contract ---------

def test_hlo_exactly_one_collective_per_syncing_level():
    """ACCEPTANCE (per-level one-collective contract): on a topology-lowered
    mesh with one axis per level, each step variant emits exactly one
    parameter-scale collective per level it syncs — none for `local`, one
    spanning the host axis for `local+host`, and for `send+host` one @host
    plus one spanning the full replica (pod+host) group."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    script = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.daso import DasoConfig, daso_train_step
        from repro.launch.hlo_stats import collective_stats
        from repro.launch.mesh import make_topology_mesh
        from repro.optim.optimizers import sgd
        from repro.topo import TopologySpec

        def loss_fn(params, batch):
            pred = batch["x"] @ params["w"]
            return jnp.mean((pred - batch["y"]) ** 2), {}

        spec = TopologySpec.parse("chip:2 x host:2 x pod:2")
        mesh = make_topology_mesh(spec, model=1)
        assert mesh.axis_names == ("pod", "host", "chip", "model")
        mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
        R, per, d = spec.n_replicas, 4, 128   # w: 128x4 f32 = 2 KiB
        opt = sgd(momentum=0.0, weight_decay=0.0)
        cfg = DasoConfig(n_replicas=R, global_world=spec.world, b_max=4)
        SDS = jax.ShapeDtypeStruct
        params = {"w": SDS((R, d, 4), jnp.float32)}
        infl = params
        batch = {"x": SDS((R, per, d), jnp.float32),
                 "y": SDS((R, per, 4), jnp.float32)}
        # replica axis sharded over BOTH replica levels, batch over chip
        shp = NamedSharding(mesh, P(("pod", "host")))
        shb = NamedSharding(mesh, P(("pod", "host"), "chip"))
        sc = NamedSharding(mesh, P())
        host_g = spec.group_size("host")

        def audit(mode, inner):
            step = daso_train_step(
                loss_fn, opt, cfg, mode=mode, staleness=1,
                inner_syncs=tuple((n, spec.group_size(n)) for n in inner))
            lowered = jax.jit(step, in_shardings=(
                {"w": shp}, {}, {"w": shp},
                {"x": shb, "y": shb}, sc)).lower(
                params, {}, infl, batch, SDS((), jnp.float32))
            # parameter-scale (>= 1 KiB) collectives only: scalar metric
            # reductions (loss means) are filtered per-op by min_bytes
            stats = collective_stats(lowered.compile().as_text(),
                                     mesh_shape, min_bytes=1024)
            return {k: v["count"] for k, v in stats.items()
                    if isinstance(v, dict)}

        def span(counts, axis):
            return sum(c for k, c in counts.items() if axis in k)

        def replica_spans(counts):
            # collectives spanning replica levels; the level-0 ("chip")
            # gradient all-reduce is expected on EVERY variant and is
            # asserted separately below
            return {k: c for k, c in counts.items()
                    if "host" in k or "pod" in k}

        c_local = audit("local", ())
        assert span(c_local, "chip") >= 1, c_local  # level-0 grad sync
        assert not replica_spans(c_local), \
            f"local must not touch replica levels: {c_local}"

        c_inner = replica_spans(audit("local", ("host",)))
        assert span(c_inner, "@host") == 1, c_inner
        assert span(c_inner, "pod") == 0, c_inner

        c_send = replica_spans(audit("send", ()))
        assert span(c_send, "@pod+host") == 1, c_send
        assert span(c_send, "@host") == 0, c_send

        c_both = replica_spans(audit("send", ("host",)))
        assert c_both.get("all-reduce@host") == 1, c_both
        # after the host-level sync GSPMD knows host groups are replicated,
        # so the outer exchange decomposes to a pod-only all-reduce (the
        # hierarchical decomposition falling out of the lowering); a full
        # pod+host span is equally contract-conforming
        outer = (c_both.get("all-reduce@pod", 0)
                 + c_both.get("all-reduce@pod+host", 0))
        assert outer == 1, c_both
        assert sum(c_both.values()) == 2, c_both
        print("PER-LEVEL HLO CONTRACT OK")
    """)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=900, env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "PER-LEVEL HLO CONTRACT OK" in r.stdout
