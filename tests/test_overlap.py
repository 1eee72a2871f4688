"""The double-buffered compute/communication overlap tier (ISSUE 6):

  * Eq. (1) with `extra_staleness` — 0 is bit-exact with the pre-overlap
    merge; kernel/ref/per-leaf implementations agree for every extra age
    (property tests, hypothesis or the conftest fallback shim).
  * The overlap controller schedule: ov_start / ov_sync~E tokens, the
    cut-after-ov-step cycle planning, and checkpoint state round-trips
    (including pre-overlap state dicts without `_ov_last`).
  * Executor equivalence: the overlap-dispatched macro path is bit-exact
    with the per-step reference path, and `serial_exchange` (the
    benchmark baseline leg) changes host waiting only, never numerics.
  * Convergence: the one-cycle-stale merge stays within tolerance of the
    blocking schedule on both executors.
  * Checkpointing: mid-run resume of the 4-slot overlap carry is
    bit-exact; carry-layout mismatches are rejected with the fix named.
  * `check_overlap_topology` and the `overlap_step_s` analytic algebra.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from conftest import make_exchange, make_mlp_problem

from repro.core.daso import (DasoConfig, daso_train_step,
                             global_receive, global_receive_per_leaf)
from repro.core.executor import (OVERLAP_COMPUTE_PREFIX, MacroCycleExecutor,
                                 make_strategy, run_compiled_training)
from repro.core.schedule import DasoController, Mode, is_ov_mode, split_ov
from repro.kernels.ref import eq1_merge_ref
from repro.optim.optimizers import sgd
from repro.optim.schedules import constant_lr
from repro.train.loop import TrainLoopConfig, run_training

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- Eq. (1) with extra staleness: properties ---------------------------------

def _old_eq1(local, stale, s, p):
    """The pre-overlap Eq. (1) merge, written out independently."""
    s2 = jnp.float32(2.0 * s)
    pf = jnp.float32(float(p))
    out = (s2 * local.astype(jnp.float32)
           + pf * stale.astype(jnp.float32)) / (s2 + pf)
    return out.astype(local.dtype)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 8), st.integers(2, 64),
       st.sampled_from(["float32", "bfloat16"]))
def test_extra_staleness_zero_is_pre_overlap_merge(staleness, world, dtype):
    """extra_staleness=0 must be BIT-exact with the pre-overlap kernel:
    2.0 * (S + 0) is the same float as 2.0 * S, so the whole multiply-add
    chain is unchanged."""
    k = jax.random.PRNGKey(staleness * 1000 + world)
    local = jax.random.normal(k, (2, 33)).astype(dtype)
    stale = jax.random.normal(jax.random.fold_in(k, 1), (2, 33)).astype(dtype)
    got = eq1_merge_ref(local, stale, staleness=staleness,
                        global_world=world, extra_staleness=0)
    want = _old_eq1(local, stale, staleness, world)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 6), st.integers(0, 5), st.integers(2, 32))
def test_extra_staleness_equals_shifted_staleness(staleness, extra, world):
    """The merge depends only on the EFFECTIVE age S + E: (s, e) and
    (s + e, 0) produce bit-identical outputs."""
    k = jax.random.PRNGKey(7 * staleness + extra)
    local = jax.random.normal(k, (3, 17))
    stale = jax.random.normal(jax.random.fold_in(k, 1), (3, 17))
    a = eq1_merge_ref(local, stale, staleness=staleness,
                      global_world=world, extra_staleness=extra)
    b = eq1_merge_ref(local, stale, staleness=staleness + extra,
                      global_world=world, extra_staleness=0)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 4), st.integers(0, 4),
       st.sampled_from(["float32", "bfloat16"]))
def test_global_receive_impls_agree_with_extra(staleness, extra, dtype):
    """per_leaf / fused-ref / Pallas-kernel merges agree for every extra
    age and dtype (the kernel runs interpret=True on CPU): the oracle
    `global_receive_per_leaf`, then the exchange without and with the
    kernels."""
    k = jax.random.PRNGKey(staleness + 10 * extra)
    tree = {"a": jax.random.normal(k, (2, 5, 3)).astype(dtype),
            "b": jax.random.normal(jax.random.fold_in(k, 1), (2, 7))}
    stale = jax.tree.map(lambda x: x + 0.25, tree)
    kw = dict(staleness=staleness, extra_staleness=extra)
    out = {"per_leaf": global_receive_per_leaf(tree, stale, global_world=8,
                                               **kw)}
    for name, kern in [("ref", False), ("kernel", True)]:
        out[name] = global_receive(
            tree, stale, ex=make_exchange(2, 8, exchange_kernels=kern), **kw)
    for name in ("ref", "kernel"):
        for la, lb in zip(jax.tree.leaves(out["per_leaf"]),
                          jax.tree.leaves(out[name])):
            np.testing.assert_allclose(np.asarray(la, np.float32),
                                       np.asarray(lb, np.float32),
                                       atol=2e-6, err_msg=name)


def test_overlap_flag_does_not_leak_into_blocking_graphs():
    """The off-mode bit-exactness contract at the HLO level: the compiled
    program of every NON-overlap mode is identical whether cfg.overlap is
    "off" or "one_cycle" — the flag changes which programs run, never what
    a given program computes."""
    cfg_off = DasoConfig(n_replicas=2, global_world=4, b_max=4,
                         warmup_steps=2, cooldown_steps=2, total_steps=12)
    cfg_ov = dataclasses.replace(cfg_off, overlap="one_cycle")
    params = {"w": jnp.ones((2, 4, 3))}
    opt = sgd(momentum=0.9)
    opt_state = jax.vmap(opt.init)(params)
    inflight = jax.tree.map(jnp.zeros_like, params)
    batch = {"x": jnp.ones((2, 8, 4)), "y": jnp.ones((2, 8, 3))}

    def loss_fn(p, b):
        return jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2), {}

    for mode in ("local", "blocking", "send", "receive"):
        texts = []
        for cfg in (cfg_off, cfg_ov):
            step = daso_train_step(loss_fn, opt, cfg, mode=mode, staleness=1)
            texts.append(jax.jit(step).lower(
                params, opt_state, inflight, batch, 0.1).as_text())
        assert texts[0] == texts[1], f"mode {mode!r} HLO differs"


# -- controller schedule -------------------------------------------------------

def _cfg(overlap="one_cycle", **kw):
    base = dict(n_replicas=2, global_world=4, b_max=4, warmup_steps=3,
                cooldown_steps=2, total_steps=16, overlap=overlap)
    base.update(kw)
    return DasoConfig(**base)


def test_split_ov_tokens():
    assert split_ov("ov_sync~2") == (Mode.OV_SYNC, 2)
    assert split_ov("ov_sync") == (Mode.OV_SYNC, 0)
    assert split_ov("local") == ("local", 0)
    assert is_ov_mode("ov_sync~1+host")
    assert is_ov_mode("ov_start")
    assert not is_ov_mode("send+host")


def test_overlap_schedule_tokens():
    """Warm-up blocking, then ov_start, B-1 locals, and ov_sync~E where
    E = age - min(W, age); cool-down blocking resets the snapshot."""
    c = DasoController(_cfg(), loss_window=50)
    modes = [c.mode_for_step(s) for s in range(16)]
    assert [m for m, _ in modes[:3]] == [Mode.BLOCKING] * 3
    assert modes[3] == (Mode.OV_START, 1)
    assert [m for m, _ in modes[4:7]] == [Mode.LOCAL] * 3
    # age 4, W = max(1, 4 // 4) = 1 -> S = 1, extra = 3
    assert modes[7] == ("ov_sync~3", 1)
    assert [m for m, _ in modes[8:11]] == [Mode.LOCAL] * 3
    assert modes[11] == ("ov_sync~3", 1)
    assert [m for m, _ in modes[14:]] == [Mode.BLOCKING] * 2
    assert c._ov_last is None  # cooldown superseded the snapshot


def test_overlap_plan_cycle_cuts_after_ov_step():
    c = DasoController(_cfg(), loss_window=50)
    assert [m for m, _ in c.plan_cycle(0)] == [Mode.BLOCKING] * 3
    assert [m for m, _ in c.plan_cycle(3)] == [Mode.OV_START]
    assert [m for m, _ in c.plan_cycle(4)] == [Mode.LOCAL] * 3 + ["ov_sync~3"]
    assert [m for m, _ in c.plan_cycle(8)] == [Mode.LOCAL] * 3 + ["ov_sync~3"]


def test_overlap_controller_state_roundtrip():
    a = DasoController(_cfg(total_steps=40, cooldown_steps=0),
                       loss_window=50)
    for s in range(9):
        a.mode_for_step(s)
    sd = a.state_dict()
    assert sd["_ov_last"] == 7
    b = DasoController(_cfg(total_steps=40, cooldown_steps=0),
                       loss_window=50)
    b.load_state_dict(sd)
    for s in range(9, 20):
        assert a.mode_for_step(s) == b.mode_for_step(s)


def test_pre_overlap_state_dict_loads():
    """A checkpoint written before the overlap tier has no _ov_last key;
    loading it must keep the fresh default (re-snapshot via ov_start)."""
    a = DasoController(_cfg(), loss_window=50)
    for s in range(6):
        a.mode_for_step(s)
    sd = a.state_dict()
    del sd["_ov_last"]
    b = DasoController(_cfg(), loss_window=50)
    b.load_state_dict(sd)
    assert b._ov_last is None
    # next cycling step re-snapshots instead of merging a lost buffer
    assert b.mode_for_step(6) == (Mode.OV_START, 1)


def test_overlap_sync_fraction_counts_ov_sync():
    c = DasoController(_cfg(), loss_window=50)
    for s in range(16):
        c.mode_for_step(s)
    # 3 warmup + 2 ov_sync + 2 cooldown of 16 steps
    assert c.global_sync_fraction() == pytest.approx(7 / 16)
    assert c.level_sync_counts()["_outer"] == 7


# -- executor: overlap cycle recognition and carry layout ---------------------

def _strategy(overlap):
    cfg = _cfg(overlap=overlap)
    _, loss_fn, _, _ = make_mlp_problem(jax.random.PRNGKey(0))
    return make_strategy("daso", loss_fn, sgd(momentum=0.9), cfg,
                         controller=DasoController(cfg, loss_window=50))


def test_overlap_carry_is_four_slot():
    params0, _, _, _ = make_mlp_problem(jax.random.PRNGKey(0))
    assert len(_strategy("one_cycle").init_carry(params0)) == 4
    assert len(_strategy("off").init_carry(params0)) == 3
    assert _strategy("off").overlap_cycle((("local", 1),)) is None


def test_overlap_cycle_recognition():
    s = _strategy("one_cycle")
    ov = s.overlap_cycle((("local", 1), ("local", 1), ("ov_sync~2", 1)))
    assert ov is not None
    assert (ov.staleness, ov.extra_staleness) == (1, 2)
    assert all(m.startswith(OVERLAP_COMPUTE_PREFIX)
               for m, _ in ov.compute_shape)
    # ov_start ends a cycle without an exchange to dispatch
    assert s.overlap_cycle((("ov_start", 1),)) is None
    # a blocking step inside the cycle forbids the async dispatch
    assert s.overlap_cycle((("blocking", 1), ("ov_sync", 1))) is None
    assert s.overlap_cycle(()) is None


# -- executor equivalence and convergence -------------------------------------

def _run(overlap, executor, *, serial_exchange=False, n_steps=24,
         ckpt_every=0, ckpt_dir=None, resume_from=None):
    key = jax.random.PRNGKey(3)
    params0, loss_fn, daso_data, _ = make_mlp_problem(key)
    cfg = TrainLoopConfig(strategy="daso", n_steps=n_steps, n_replicas=2,
                          b_max=4, loss_window=50, executor=executor,
                          overlap=overlap,
                          overlap_serial_exchange=serial_exchange,
                          ckpt_every=ckpt_every, ckpt_dir=ckpt_dir,
                          resume_from=resume_from)
    return run_training(loss_fn, params0, daso_data, cfg,
                        optimizer=sgd(momentum=0.9),
                        lr_fn=constant_lr(0.05), log=None)


def test_overlap_macro_matches_per_step():
    """The overlap-dispatched macro path is bit-exact with the per-step
    reference path — the dispatch structure changes, the math does not."""
    macro = _run("one_cycle", "macro")
    ref = _run("one_cycle", "per_step")
    assert macro.losses == ref.losses
    for a, b in zip(jax.tree.leaves(macro.params),
                    jax.tree.leaves(ref.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert macro.executor_stats.overlap_cycles > 0


@pytest.mark.parametrize("serial", [False, True])
def test_overlap_stats_legs_partition_wall(serial):
    """The overlap timing legs are an EXACT partition of the overlap wall
    time: every leg ends on block_until_ready at a boundary timestamp that
    is also where the next leg starts (core/executor.py::_run_overlap), so
    compute + visible (or blocking) + merge == wall to float addition."""
    r = _run("one_cycle", "macro", serial_exchange=serial)
    st = r.executor_stats
    assert st.overlap_cycles > 0
    legs = (st.overlap_compute_s + st.overlap_exchange_visible_s
            + st.overlap_exchange_blocking_s + st.overlap_merge_s)
    assert st.overlap_wall_s > 0.0
    assert legs == pytest.approx(st.overlap_wall_s, rel=1e-9, abs=1e-9)
    # the mode under test fills its leg, the other stays zero
    if serial:
        assert st.overlap_exchange_blocking_s > 0.0
        assert st.overlap_exchange_visible_s == 0.0
    else:
        assert st.overlap_exchange_visible_s > 0.0
        assert st.overlap_exchange_blocking_s == 0.0


def test_serial_exchange_identical_numerics():
    """serial_exchange (the benchmark's blocking baseline leg) changes
    only WHEN the host waits — losses and params must be bit-identical."""
    a = _run("one_cycle", "macro", serial_exchange=False)
    b = _run("one_cycle", "macro", serial_exchange=True)
    assert a.losses == b.losses
    for la, lb in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    assert b.executor_stats.overlap_exchange_blocking_s >= 0.0
    assert b.executor_stats.overlap_cycles == a.executor_stats.overlap_cycles


@pytest.mark.parametrize("executor", ["macro", "per_step"])
def test_overlap_convergence_close_to_blocking(executor):
    """One-cycle-stale merges may move the loss, but on the tiny 2-level
    problem the gap to the blocking schedule stays small — the paper's
    claim that selective/asynchronous sync does not hurt convergence."""
    ov = _run("one_cycle", executor, n_steps=32)
    off = _run("off", executor, n_steps=32)
    assert ov.losses[-1] < ov.losses[0]  # it actually trains
    assert abs(ov.final_loss - off.final_loss) < 0.25


def test_overlap_off_losses_unchanged_by_serial_flag():
    """overlap=off runs have no overlap cycles for serial_exchange to
    touch; the flag must be inert."""
    a = _run("off", "macro", serial_exchange=True)
    b = _run("off", "macro", serial_exchange=False)
    assert a.losses == b.losses
    assert a.executor_stats.overlap_cycles == 0


# -- checkpointing of the 4-slot overlap carry --------------------------------

def test_overlap_checkpoint_resume_bit_exact(tmp_path):
    """Resume mid-overlap: the pending arena and the controller's
    _ov_last survive the round-trip, so the resumed run is bit-exact."""
    ckpt = str(tmp_path / "ck")
    fresh = _run("one_cycle", "macro", n_steps=24)
    _run("one_cycle", "macro", n_steps=24, ckpt_every=8, ckpt_dir=ckpt)
    dirs = sorted(os.listdir(ckpt))
    assert dirs
    resumed = _run("one_cycle", "macro", n_steps=24,
                   resume_from=os.path.join(ckpt, dirs[0]))
    assert resumed.losses == fresh.losses
    for a, b in zip(jax.tree.leaves(resumed.params),
                    jax.tree.leaves(fresh.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_overlap_layout_mismatch_rejected(tmp_path):
    from repro.checkpoint.io import TrainState, load_train_state, \
        save_train_state
    path = str(tmp_path / "st")
    carry = ({"w": jnp.ones((2, 3))}, {"m": jnp.zeros((2, 3))},
             {"w": jnp.zeros((2, 3))})
    save_train_state(path, TrainState(step=4, carry=carry, overlap="off"))
    with pytest.raises(ValueError, match="--overlap off"):
        load_train_state(path, expect_overlap="one_cycle")
    assert load_train_state(path, expect_overlap="off").overlap == "off"


def test_v1_checkpoint_defaults_to_off(tmp_path):
    """A TrainState written before the overlap tier (v1, no overlap key)
    must load as overlap="off" — and be rejected by an overlap run."""
    from repro.checkpoint.io import TrainState, load_train_state, \
        save_train_state
    path = str(tmp_path / "st")
    save_train_state(path, TrainState(step=2, carry={"w": jnp.ones((2,))}))
    mf = os.path.join(path, "manifest.json")
    with open(mf) as f:
        manifest = json.load(f)
    host = manifest["extra"]["train_state"]
    host["version"] = 1
    del host["overlap"]
    with open(mf, "w") as f:
        json.dump(manifest, f)
    ts = load_train_state(path, expect_overlap="off")
    assert ts.overlap == "off" and ts.version == 1
    with pytest.raises(ValueError, match="TrainState v1"):
        load_train_state(path, expect_overlap="one_cycle")


# -- multi-process guardrails --------------------------------------------------

def test_check_overlap_topology():
    from repro.launch.distributed import check_overlap_topology
    from repro.topo import TopologySpec
    spec = TopologySpec.load("chip:1 x host:2 x pod:2")  # R=4, host groups 2
    check_overlap_topology(spec, 1)   # single process: nothing to race
    check_overlap_topology(spec, 2)   # 2 rows/proc, host group 2: local
    with pytest.raises(ValueError, match="process-local"):
        check_overlap_topology(spec, 4)  # host groups span processes


def test_sync_strategy_rejects_overlap():
    _, loss_fn, _, sync_data = make_mlp_problem(jax.random.PRNGKey(0))
    cfg = TrainLoopConfig(strategy="sync", n_steps=4, overlap="one_cycle")
    with pytest.raises(ValueError, match="sync"):
        run_training(loss_fn, {"w": jnp.ones((8, 1))}, sync_data, cfg,
                     log=None)


# -- analytic model: overlap_step_s algebra -----------------------------------

def _comm():
    import sys
    sys.path.insert(0, REPO)
    from benchmarks import comm_model
    return comm_model


def test_overlap_step_free_exchange_is_pure_compute():
    """Zero-cost DCN: the cycle costs exactly one compute + local
    all-reduce per step — overlap adds nothing."""
    cm = _comm()
    c = cm.ClusterModel(ib_bw=1e30, step_latency_s=0.0)
    t_local = cm.ring_allreduce_s(1e8, c.gpus_per_node, c.nvlink_bw,
                                  latency=3e-6)
    got = cm.overlap_step_s(1e8, 16, c, b=4, blocking_frac=0.0)
    assert got == pytest.approx(c.t_compute_s + t_local, rel=1e-12)


def test_overlap_step_exchange_dominated():
    """No compute, no local members: the step degenerates to the exchange
    amortized over the cycle — t_exchange / B exactly."""
    cm = _comm()
    c = cm.ClusterModel(gpus_per_node=1, t_compute_s=0.0)
    t_ex = cm.degraded_exchange_s(1e9, 16, c)
    got = cm.overlap_step_s(1e9, 16, c, b=4, blocking_frac=0.0)
    assert got == pytest.approx(t_ex / 4, rel=1e-12)


def test_overlap_step_compute_dominated():
    cm = _comm()
    c = cm.ClusterModel(t_compute_s=100.0)
    t_local = cm.ring_allreduce_s(1e8, c.gpus_per_node, c.nvlink_bw,
                                  latency=3e-6)
    got = cm.overlap_step_s(1e8, 4, c, b=4, blocking_frac=0.0)
    assert got == pytest.approx(c.t_compute_s + t_local, rel=1e-12)


def test_overlap_step_blocking_frac_blend():
    """blocking_frac=1 is the fully blocking schedule for both models."""
    cm = _comm()
    c = cm.ClusterModel()
    assert cm.overlap_step_s(1e8, 16, c, blocking_frac=1.0) == \
        pytest.approx(cm.daso_step_s(1e8, 16, c, blocking_frac=1.0,
                                     nonblocking_hidden=0.0), rel=1e-12)


def test_overlap_step_rejects_bad_cycle():
    cm = _comm()
    with pytest.raises(ValueError, match="b must be >= 1"):
        cm.overlap_step_s(1e8, 16, cm.ClusterModel(), b=0)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 16), st.integers(2, 64),
       st.floats(0.0, 1.0))
def test_overlap_never_worse_than_unhidden(b, n_nodes, blocking_frac):
    """The measured-dispatch model never prices a step above the same
    schedule with zero hiding."""
    cm = _comm()
    c = cm.ClusterModel()
    ov = cm.overlap_step_s(1e8, n_nodes, c, b=b,
                           blocking_frac=blocking_frac)
    blk = cm.daso_step_s(1e8, n_nodes, c, b=b, blocking_frac=blocking_frac,
                         nonblocking_hidden=0.0)
    assert ov <= blk + 1e-15
