"""Macro-cycle executor: numerics must match the per-step reference path
(allclose at f32), one compilation per distinct cycle shape, host dispatches
per cycling-phase cycle reduced to 1, strategy registry surface."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import make_mlp_problem as _mlp_problem

from repro.core.daso import DasoConfig
from repro.core.executor import (CyclePlan, MacroCycleExecutor, _group_runs,
                                 get_strategy, list_strategies, make_strategy,
                                 run_compiled_training)
from repro.core.schedule import DasoController, Mode
from repro.core.simulator import run_per_step_training
from repro.optim.optimizers import sgd
from repro.optim.schedules import constant_lr


def _daso_cfg(n_steps, R=2, b_max=4):
    return DasoConfig(n_replicas=R, global_world=4 * R, b_max=b_max,
                      warmup_steps=n_steps // 10,
                      cooldown_steps=n_steps // 10, total_steps=n_steps)


def _make(strategy_name, loss_fn, n_steps, *, loss_window=10, R=2):
    opt = sgd(momentum=0.9, weight_decay=1e-4)
    if strategy_name == "sync":
        return make_strategy("sync", loss_fn, opt)
    dcfg = _daso_cfg(n_steps, R=R)
    return make_strategy(strategy_name, loss_fn, opt, dcfg,
                         controller=DasoController(dcfg,
                                                   loss_window=loss_window))


# ------------------------------------------------------------- equivalence --

@pytest.mark.parametrize("strategy", ["daso", "sync", "local_sgd"])
def test_executor_matches_per_step_path(strategy):
    """Same seed -> allclose params and loss trace, macro vs per-step."""
    key = jax.random.PRNGKey(0)
    params0, loss_fn, daso_data, sync_data = _mlp_problem(key)
    data = sync_data if strategy == "sync" else daso_data
    lr_fn = constant_lr(0.1)
    n_steps = 60

    macro = run_compiled_training(_make(strategy, loss_fn, n_steps),
                                  params0, data, lr_fn, n_steps)
    ref = run_per_step_training(_make(strategy, loss_fn, n_steps),
                                params0, data, lr_fn, n_steps)

    np.testing.assert_allclose(np.asarray(macro.losses, np.float32),
                               np.asarray(ref.losses, np.float32),
                               rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(macro.params),
                    jax.tree.leaves(ref.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-6)
    # the schedules must be literally identical, not just numerically close
    if macro.controller is not None:
        assert [h[1] for h in macro.controller.history] == \
               [h[1] for h in ref.controller.history]


def _multi_leaf_problem(key, R=2, per=8, d=6):
    """Like the shared MLP problem but with 5 parameter leaves across 2
    nested dicts, so the fused arena genuinely coalesces leaves."""
    k = jax.random.split(key, 6)
    params0 = {"emb": jax.random.normal(k[0], (d, 12)) * 0.3,
               "mlp": {"w1": jax.random.normal(k[1], (12, 8)) * 0.3,
                       "b1": jax.random.normal(k[2], (8,)) * 0.1,
                       "w2": jax.random.normal(k[3], (8, 1)) * 0.3},
               "scale": jax.random.normal(k[4], (1,)) * 0.1}
    wtrue = jax.random.normal(k[5], (d, 1))

    def loss_fn(params, batch):
        h = jnp.tanh(batch["x"] @ params["emb"])
        h = jnp.tanh(h @ params["mlp"]["w1"] + params["mlp"]["b1"])
        pred = h @ params["mlp"]["w2"] * (1.0 + params["scale"])
        return jnp.mean((pred - batch["y"]) ** 2), {}

    def daso_data(step):
        kk = jax.random.fold_in(key, step)
        x = jax.random.normal(kk, (R, per, d))
        return {"x": x, "y": jnp.tanh(x @ wtrue) * 0.5}

    return params0, loss_fn, daso_data


@pytest.mark.parametrize("wire_format", [None, "f32", "bf16"])
def test_fused_arena_training_matches_per_leaf(wire_format):
    """Acceptance: fused flat-buffer DASO training == the legacy per-leaf
    exchange path, allclose at f32, on a multi-leaf model (the arena
    coalesces 5 leaves into one buffer; numerics must not move)."""
    key = jax.random.PRNGKey(7)
    params0, loss_fn, daso_data = _multi_leaf_problem(key)
    opt = sgd(momentum=0.9, weight_decay=1e-4)
    n_steps = 40

    def run(exchange_impl):
        dcfg = DasoConfig(n_replicas=2, global_world=8, b_max=4,
                          warmup_steps=4, cooldown_steps=4,
                          total_steps=n_steps, wire_format=wire_format,
                          exchange_impl=exchange_impl)
        strat = make_strategy("daso", loss_fn, opt, dcfg,
                              controller=DasoController(dcfg,
                                                        loss_window=10))
        ex = MacroCycleExecutor(strat)
        # without a placement the executor makes the exchange leaf-wise;
        # the fused side is the packed arena, as a mesh placement has it
        strat.set_device_local(False)
        assert strat.exchange_layout == ("arena" if exchange_impl == "fused"
                                         else "leafwise")
        return run_compiled_training(strat, params0, daso_data,
                                     constant_lr(0.1), n_steps, executor=ex)

    fused, per_leaf = run("fused"), run("per_leaf")
    np.testing.assert_allclose(np.asarray(fused.losses, np.float32),
                               np.asarray(per_leaf.losses, np.float32),
                               rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(fused.params),
                    jax.tree.leaves(per_leaf.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-6)


@pytest.mark.parametrize("wire_format", ["f32", "bf16"])
def test_leafwise_training_is_bit_identical_to_arena(wire_format):
    """An executor without a placement (the replica axis on one device)
    trains with the leaf-wise replica mean, and every loss and parameter
    is the same bits as with the packed arena."""
    key = jax.random.PRNGKey(7)
    params0, loss_fn, daso_data = _multi_leaf_problem(key)
    opt = sgd(momentum=0.9, weight_decay=1e-4)
    n_steps = 24

    def run(layout):
        dcfg = DasoConfig(n_replicas=2, global_world=8, b_max=4,
                          warmup_steps=4, cooldown_steps=4,
                          total_steps=n_steps, wire_format=wire_format)
        strat = make_strategy("daso", loss_fn, opt, dcfg,
                              controller=DasoController(dcfg,
                                                        loss_window=10))
        ex = MacroCycleExecutor(strat)
        if layout == "arena":
            strat.set_device_local(False)
        assert strat.exchange_layout == layout
        return run_compiled_training(strat, params0, daso_data,
                                     constant_lr(0.1), n_steps, executor=ex)

    arena, leafwise = run("arena"), run("leafwise")
    assert arena.losses == leafwise.losses
    for a, b in zip(jax.tree.leaves(arena.params),
                    jax.tree.leaves(leafwise.params)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def _packs_an_arena(strat) -> bool:
    """Whether the strategy's send step packs the flat-buffer arena (its
    `dynamic_update_slice` writes) on the multi-leaf problem."""
    params0, _, daso_data = _multi_leaf_problem(jax.random.PRNGKey(0))
    jaxpr = jax.make_jaxpr(strat.step_fn(Mode.SEND, 1))(
        strat.init_carry(params0), daso_data(0), jnp.float32(0.1))
    return "dynamic_update_slice" in str(jaxpr)


@pytest.mark.parametrize("knobs,told,layout", [
    ({}, False, "arena"),
    ({}, True, "leafwise"),
    ({"wire_format": "int8"}, True, "arena"),
    ({"exchange_kernels": True}, True, "arena"),
])
def test_exchange_layout_follows_the_placement(knobs, told, layout):
    """An executor without a placement makes the replica mean leaf-wise;
    a strategy never told keeps the arena, and so do the int8 wire and
    the exchange kernels. Giving the executor a placement later restores
    the arena and drops what was compiled before."""
    _, loss_fn, _ = _multi_leaf_problem(jax.random.PRNGKey(0))
    dcfg = DasoConfig(n_replicas=2, global_world=8, b_max=4, **knobs)
    strat = make_strategy("daso", loss_fn, sgd(momentum=0.9), dcfg)
    ex = MacroCycleExecutor(strat) if told else None
    assert strat.exchange_layout == layout
    assert _packs_an_arena(strat) == (layout == "arena")
    if ex is not None:
        ex.program_for(((Mode.SEND, 1),))
        ex.placement = object()   # stands for a MeshPlacement
        assert strat.exchange_layout == "arena"
        assert ex.cached_shapes == [] and ex.stats.invalidations == 1
        assert _packs_an_arena(strat)


def test_int8_wire_training_converges():
    """The beyond-paper int8 tier trains: loss stays finite and params end
    within quantization distance of the f32-wire run."""
    key = jax.random.PRNGKey(8)
    params0, loss_fn, daso_data = _multi_leaf_problem(key)
    opt = sgd(momentum=0.9, weight_decay=1e-4)
    n_steps = 24

    def run(wire_format):
        dcfg = DasoConfig(n_replicas=2, global_world=8, b_max=4,
                          warmup_steps=4, cooldown_steps=4,
                          total_steps=n_steps, wire_format=wire_format)
        strat = make_strategy("daso", loss_fn, opt, dcfg,
                              controller=DasoController(dcfg,
                                                        loss_window=10**9))
        return run_compiled_training(strat, params0, daso_data,
                                     constant_lr(0.1), n_steps)

    i8, f32 = run("int8"), run("f32")
    assert np.all(np.isfinite(i8.losses))
    assert i8.final_loss < i8.losses[0]  # it actually trains
    gap = max(float(jnp.max(jnp.abs(a - b)))
              for a, b in zip(jax.tree.leaves(i8.params),
                              jax.tree.leaves(f32.params)))
    assert gap < 0.05  # small quantization drift, not divergence


def test_executor_params0_not_consumed():
    """Donation must never eat the caller's params0 (regression: the carry
    used to alias it)."""
    key = jax.random.PRNGKey(3)
    params0, loss_fn, _, sync_data = _mlp_problem(key)
    lr_fn = constant_lr(0.1)
    before = float(jnp.sum(jnp.abs(params0["w1"])))
    run_compiled_training(_make("sync", loss_fn, 20), params0, sync_data,
                          lr_fn, 20)
    # still alive, readable, and untouched by the donated training run
    assert float(jnp.sum(jnp.abs(params0["w1"]))) == before


# ------------------------------------------------------ dispatch reduction --

def test_cycling_phase_one_dispatch_per_cycle():
    """In the cycling phase a B=4 cycle (send, receive, local, local) is one
    host dispatch instead of B+1 step-wise launches."""
    key = jax.random.PRNGKey(1)
    params0, loss_fn, daso_data, _ = _mlp_problem(key)
    n_steps = 40
    opt = sgd(momentum=0.9, weight_decay=1e-4)
    # no warm-up/cool-down: pure cycling, huge window so B/W never move
    dcfg = DasoConfig(n_replicas=2, global_world=8, b_max=4)
    strat = make_strategy("daso", loss_fn, opt, dcfg,
                          controller=DasoController(dcfg, loss_window=10**9))
    ex = MacroCycleExecutor(strat)
    res = run_compiled_training(strat, params0, daso_data,
                                constant_lr(0.1), n_steps, executor=ex)
    assert ex.stats.steps + ex.stats.fallback_steps == n_steps
    # 40 steps of (send, receive, local, local) = 10 cycles -> 10 dispatches
    assert ex.stats.cycles == n_steps // 4
    assert ex.stats.dispatches == ex.stats.cycles
    assert res.executor_stats.dispatches_per_step() == pytest.approx(0.25)


def test_compile_cache_one_program_per_shape():
    """Distinct cycle shapes compile once each; repeats hit the cache."""
    key = jax.random.PRNGKey(2)
    params0, loss_fn, daso_data, _ = _mlp_problem(key)
    n_steps = 80
    strat = _make("daso", loss_fn, n_steps, loss_window=10)
    ex = MacroCycleExecutor(strat, tail_fallback=False)
    run_compiled_training(strat, params0, daso_data, constant_lr(0.1),
                          n_steps, executor=ex)
    shapes = set(ex.cached_shapes)
    assert ex.stats.compiles == len(shapes)
    # the schedule repeats cycles, so caching must actually dedupe
    assert ex.stats.cycles > len(shapes)


def test_tail_fallback_avoids_single_use_compile():
    """A final partial cycle with an unseen shape runs per-step instead of
    paying a compilation for one use."""
    key = jax.random.PRNGKey(4)
    params0, loss_fn, daso_data, _ = _mlp_problem(key)
    opt = sgd(momentum=0.9, weight_decay=1e-4)
    dcfg = DasoConfig(n_replicas=2, global_world=8, b_max=4)
    strat = make_strategy("daso", loss_fn, opt, dcfg,
                          controller=DasoController(dcfg, loss_window=10**9))
    ex = MacroCycleExecutor(strat)
    n_steps = 42  # 10 full cycles of 4 + irregular 2-step tail
    run_compiled_training(strat, params0, daso_data, constant_lr(0.1),
                          n_steps, executor=ex)
    assert ex.stats.fallback_steps == 2
    shapes = set(ex.cached_shapes)
    assert all(len(s) == 4 for s in shapes)


# ------------------------------------------------------------ plan/registry --

def test_controller_plan_matches_mode_for_step():
    """plan_cycle must consume exactly the sequence mode_for_step yields."""
    dcfg = DasoConfig(n_replicas=4, global_world=16, b_max=4,
                      warmup_steps=6, cooldown_steps=6, total_steps=60)
    a = DasoController(dcfg, loss_window=10**9)
    b = DasoController(dcfg, loss_window=10**9)
    planned = []
    step = 0
    while step < 60:
        shape = a.plan_cycle(step, max_len=min(32, 60 - step))
        assert shape, "empty plan"
        planned.extend(shape)
        step += len(shape)
    stepwise = [b.mode_for_step(t) for t in range(60)]
    assert planned == stepwise
    assert a.history == b.history


def test_plan_respects_loss_window_boundary():
    """Cycles never span a plateau-window edge, so observe_loss feedback
    lands between compiled cycles exactly as on the per-step path."""
    dcfg = DasoConfig(n_replicas=4, global_world=16, b_max=8)
    c = DasoController(dcfg, loss_window=5)
    c.observe_loss(1.0)
    c.observe_loss(1.0)  # 3 slots left in the window
    shape = c.plan_cycle(0, max_len=32)
    assert len(shape) <= 3


def test_group_runs():
    shape = (("send", 1), ("receive", 1), ("local", 1), ("local", 1))
    assert _group_runs(shape) == [("send", 1, 0, 1), ("receive", 1, 1, 1),
                                  ("local", 1, 2, 2)]


def test_registry_surface():
    assert set(list_strategies()) >= {"daso", "sync", "local_sgd"}
    assert get_strategy("daso").name == "daso"
    with pytest.raises(KeyError):
        get_strategy("nope")


def test_local_sgd_plan_shape():
    key = jax.random.PRNGKey(5)
    _, loss_fn, _, _ = _mlp_problem(key)
    strat = _make("local_sgd", loss_fn, 40)
    plan = strat.plan_cycle(0, 32)
    assert isinstance(plan, CyclePlan)
    assert plan.shape[0][0] == Mode.HARD_AVG
    assert all(m == Mode.LOCAL for m, _ in plan.shape[1:])
    assert len(plan) == 4
