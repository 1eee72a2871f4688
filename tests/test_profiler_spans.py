"""The program's spans and scopes on the profiler's clock:

  * every span of `obs.trace` (NULL_TRACER's too) is a `repro.<name>`
    annotation on the profiler's host plane, with its args;
  * the host loop records its phases in order, nested in the cycle span,
    and times them into `ExecutorStats`;
  * the cycle span's compile count sees a jit recompile of a built shape;
  * the DASO step's device ops carry the `repro.*` named scopes.
"""
import glob

import jax
import jax.numpy as jnp
import pytest

from conftest import make_mlp_problem

from repro.core.daso import (DasoConfig, daso_train_step, local_step,
                             sync_train_step)
from repro.core.executor import (LOOP_PHASES, MacroCycleExecutor,
                                 run_compiled_training)
from repro.obs.trace import (NULL_TRACER, PROFILER_PREFIX, Tracer,
                             load_events, merge_streams, stream_path)
from repro.optim.optimizers import sgd
from repro.optim.schedules import constant_lr
from repro.resilience.faults import FaultPlan
from repro.resilience.supervisor import run_with_faults
from repro.train.loop import TrainLoopConfig, build_strategy

B = 4


def _strategy(loss_fn):
    cfg = TrainLoopConfig(strategy="daso", n_steps=10 ** 9, n_replicas=2,
                          b_max=B, warmup_frac=0.0, cooldown_frac=0.0,
                          loss_window=10 ** 9, lr=0.05)
    return build_strategy(loss_fn, cfg, sgd(momentum=0.9))


def _host_spans(log_dir):
    """[(name, start_ns, end_ns, {arg: value})] of the `repro.` events on
    the host plane of the one profile under `log_dir`, by start."""
    path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    out = [(ev.name.split("#", 1)[0], ev.start_ns, ev.end_ns,
            dict(ev.stats))
           for pl in data.planes if pl.name.startswith("/host")
           for ln in pl.lines for ev in ln.events
           if ev.name.startswith(PROFILER_PREFIX)]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def test_profile_shows_loop_phases_in_order(tmp_path):
    params0, loss_fn, daso_data, _ = make_mlp_problem(jax.random.PRNGKey(0))
    strategy = _strategy(loss_fn)
    ex = MacroCycleExecutor(strategy, tail_fallback=False)
    with jax.profiler.trace(str(tmp_path)):
        run_compiled_training(strategy, params0, daso_data,
                              constant_lr(0.05), 2 * B, executor=ex,
                              ckpt_every=B, ckpt_cb=lambda *a: None)
    spans = _host_spans(tmp_path)
    names = [s[0] for s in spans]
    cycle = ["repro.cycle", "repro.stage", "repro.dispatch", "repro.wait",
             "repro.readback"]
    after = ["repro.control", "repro.checkpoint_save"]
    assert names == (["repro.control"] + cycle + after) * 2
    # stage -> readback nest in the cycle span, one after the other
    for c in (i for i, n in enumerate(names) if n == "repro.cycle"):
        _, c0, c1, _ = spans[c]
        inner = spans[c + 1:c + 5]
        assert all(c0 <= a <= b <= c1 for _, a, b, _ in inner)
        assert all(x[2] <= y[1] for x, y in zip(inner, inner[1:]))
    # args known only after the fact reach the annotations: the first
    # dispatch compiled, the second did not
    dispatch = [s[3] for s in spans if s[0] == "repro.dispatch"]
    assert dispatch[0]["compiles"] >= 1 and dispatch[1]["compiles"] == 0
    first, second = [s[3] for s in spans if s[0] == "repro.cycle"]
    assert first["fresh_compile"] and not second["fresh_compile"]
    assert [s[3]["arrays"] for s in spans if s[0] == "repro.readback"] \
        == [2, 2]
    assert [s[3]["steps"] for s in spans if s[0] == "repro.stage"] == [B, B]


def test_tracer_spans_reach_stream_and_profile(tmp_path):
    base = str(tmp_path / "trace.jsonl")
    tr = Tracer(stream_path(base, 0))
    with jax.profiler.trace(str(tmp_path / "prof")):
        with tr.span("cycle", steps=3) as sp:
            with NULL_TRACER.span("stage", arrays=2):
                pass
            sp.set_metadata(fresh_compile=True)
    tr.close()
    merge_streams(base)
    span, = [ev for ev in load_events(base) if ev["name"] == "cycle"]
    assert span["args"] == {"steps": 3, "fresh_compile": True}
    spans = _host_spans(tmp_path / "prof")
    assert [(s[0], s[3]) for s in spans] == [
        ("repro.cycle", {"steps": 3, "fresh_compile": True}),
        ("repro.stage", {"arrays": 2})]


def test_profiler_off_spans_record_nothing(tmp_path):
    # annotations entered with no profile recording do not appear in a
    # later one
    with NULL_TRACER.span("stage"):
        with jax.profiler.trace(str(tmp_path)):
            with NULL_TRACER.span("dispatch"):
                pass
    assert [s[0] for s in _host_spans(tmp_path)] == ["repro.dispatch"]


@pytest.mark.parametrize("loop", ["compiled", "supervised"])
def test_executor_stats_time_every_phase(loop):
    params0, loss_fn, daso_data, _ = make_mlp_problem(jax.random.PRNGKey(1))
    strategy = _strategy(loss_fn)
    ex = MacroCycleExecutor(strategy, tail_fallback=False)
    kw = dict(executor=ex, ckpt_every=B, ckpt_cb=lambda *a: None)
    if loop == "compiled":
        run_compiled_training(strategy, params0, daso_data,
                              constant_lr(0.05), 3 * B, **kw)
    else:
        run_with_faults(strategy, params0, daso_data, constant_lr(0.05),
                        3 * B, FaultPlan(), **kw)
    s = ex.stats
    assert s.cycles == 3
    assert tuple(s.phase_s) == LOOP_PHASES
    assert all(v > 0 for v in s.phase_s.values())
    # the slowest cycle is the first (it compiled), split over the same
    # phases; it is at least the mean cycle and at most all of them
    assert set(s.slowest_cycle_s) == set(LOOP_PHASES)
    slowest = sum(s.slowest_cycle_s.values())
    assert sum(s.phase_s.values()) / 3 <= slowest <= sum(s.phase_s.values())
    assert s.slowest_cycle_s["dispatch"] == max(
        s.slowest_cycle_s.values())


def test_recompile_for_new_placement_counts_as_compile(tmp_path):
    """A cycle whose batches are committed to a device recompiles the
    already-built program (new input shardings). The executor builds no
    program for it, so a build count misses it; the backend-compile count
    does not."""
    params0, loss_fn, daso_data, _ = make_mlp_problem(jax.random.PRNGKey(2))
    strategy = _strategy(loss_fn)
    base = str(tmp_path / "trace.jsonl")
    tr = Tracer(stream_path(base, 0))
    ex = MacroCycleExecutor(strategy, tail_fallback=False, tracer=tr)
    box = {}

    def keep(step, carry, _losses):
        box["carry"] = carry

    def committed(step):
        return {k: jax.device_put(v, jax.devices()[0])
                for k, v in daso_data(step).items()}

    lr = constant_lr(0.05)
    run_compiled_training(strategy, params0, daso_data, lr, 2 * B,
                          executor=ex, ckpt_every=B, ckpt_cb=keep)
    built, seen = ex.stats.compiles, ex.stats.backend_compiles
    assert built >= 1 and seen >= built
    # the same shape again, its batches committed
    run_compiled_training(strategy, None, committed, lr, 3 * B,
                          executor=ex, start_step=2 * B,
                          carry=box["carry"], ckpt_every=B, ckpt_cb=keep)
    tr.close()
    merge_streams(base)
    assert ex.stats.compiles == built          # no program was built
    assert ex.stats.backend_compiles > seen    # but XLA compiled again
    cycles = [ev["args"] for ev in load_events(base)
              if ev["name"] == "cycle"]
    assert [c["start_step"] for c in cycles] == [0, B, 2 * B]
    assert cycles[0]["fresh_compile"] and not cycles[1]["fresh_compile"]
    assert cycles[2]["fresh_compile"] and cycles[2]["compiles"] >= 1


def _lowered_text(step, *args):
    return jax.jit(step).lower(*args).as_text(debug_info=True)


def _daso_args(R=2, d=8):
    params = {"w": jnp.ones((R, d, 1)), "b": jnp.zeros((R, 1))}
    opt = sgd(momentum=0.9)
    opt_state = jax.vmap(opt.init)(params)
    batch = {"x": jnp.ones((R, 4, d)), "y": jnp.zeros((R, 4, 1))}
    return opt, params, opt_state, batch


def _loss(params, batch):
    pred = batch["x"] @ params["w"] + params["b"]
    return jnp.mean((pred - batch["y"]) ** 2), {}


@pytest.mark.parametrize("mode,inner,scope", [
    ("send", (), "repro.exchange.send"),
    ("receive", (), "repro.exchange.receive"),
    ("blocking", (), "repro.exchange.blocking"),
    ("hard_avg", (), "repro.exchange.blocking"),
    ("local", (("host", 2),), "repro.exchange.level"),
])
def test_daso_step_ops_carry_named_scopes(mode, inner, scope):
    opt, params, opt_state, batch = _daso_args()
    cfg = DasoConfig(n_replicas=2, global_world=2)
    step = daso_train_step(_loss, opt, cfg, mode=mode, inner_syncs=inner)
    text = _lowered_text(step, params, opt_state, params, batch,
                         jnp.float32(0.1))
    for name in ("repro.fwd_bwd", "repro.optimizer", scope):
        assert name in text, name
    others = {"repro.exchange.send", "repro.exchange.receive",
              "repro.exchange.blocking", "repro.exchange.level"} - {scope}
    assert not [n for n in others if n in text]


def test_sync_and_local_steps_carry_named_scopes():
    opt, params, opt_state, batch = _daso_args()
    text = _lowered_text(local_step(_loss, opt), params, opt_state, batch,
                         jnp.float32(0.1))
    one = jax.tree.map(lambda x: x[0], (params, opt_state, batch))
    sync = _lowered_text(sync_train_step(_loss, opt), *one,
                         jnp.float32(0.1))
    for t in (text, sync):
        assert "repro.fwd_bwd" in t and "repro.optimizer" in t
        assert "repro.exchange" not in t
