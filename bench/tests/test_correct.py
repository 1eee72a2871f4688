"""`correct` at a test's size on the CPU: a sound run passes; the timed
path broken underneath fails, once for each fault a training cell can
have; the float8 control and the planted faults in the reference fail
too."""
import jax
import pytest

from bench import calibrate, checks, harness
from bench.tests.helpers import tiny_cell

SEED = 2 ** 33 + 5


def _run(cell):
    res, compared = harness.run_cell(
        cell, SEED, 0.5, False, jax.devices()[:1],
        {"bf16_flops_per_s": 1e12}, 0.0, log=lambda s: None)
    return res, {k: v for k, v, _ in compared}


def _unchanged_state(monkeypatch):
    from repro.core.executor import DasoStrategy
    build = DasoStrategy.build_step

    def broken(self, mode, staleness):
        step = build(self, mode, staleness)

        def same(carry, batch, lr):
            _, m = step(carry, batch, lr)
            return carry, m
        return same
    monkeypatch.setattr(DasoStrategy, "build_step", broken)


def _half_batch(monkeypatch):
    import repro.train.step as step_mod
    make = step_mod.make_lm_loss

    def broken(cfg, **kw):
        loss = make(cfg, **kw)

        def half(params, batch):
            n = batch["tokens"].shape[0] // 2
            return loss(params, {k: v[:n] for k, v in batch.items()})
        return half
    monkeypatch.setattr(step_mod, "make_lm_loss", broken)


def _no_exchange(monkeypatch):
    import repro.core.daso as daso
    monkeypatch.setattr(daso, "global_send",
                        lambda params, **kw: jax.tree.map(lambda x: x + 0,
                                                          params))


CELLS = [("tiny.json", "tiny-limits.json"),
         ("tiny-moe.json", "tiny-moe-limits.json")]


@pytest.mark.parametrize("config,limits", CELLS)
def test_sound_run_is_correct(config, limits):
    res, values = _run(tiny_cell(config, limits))
    assert res["correct"], values
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) >= {"tokens_per_s_per_chip", "setup_s"}


@pytest.mark.parametrize("config,limits", CELLS)
@pytest.mark.parametrize("plant", [_unchanged_state, _half_batch,
                                   _no_exchange])
def test_broken_timed_path_is_not_correct(monkeypatch, plant, config,
                                          limits):
    plant(monkeypatch)
    res, values = _run(tiny_cell(config, limits))
    assert not res["correct"], values


# for the MoE cell the program's float8 path crashes, so the reference
# computed in float8 (`control_ref`) is its control
@pytest.mark.parametrize("config,limits", CELLS)
def test_control_and_planted_faults_fail(config, limits):
    cell = tiny_cell(config, limits)
    rows, _ = calibrate.calibrate(cell, jax.devices()[:1], 3, 3, SEED,
                                  log=lambda s: None)
    for row in rows:
        assert checks.judge(row["program"], cell.limits), row["program"]
        # a control that crashes has failed
        assert ("crashed" in row["control"]
                or not checks.judge(row["control"], cell.limits)), row
        for kind in ("control_ref",) + calibrate.FAULTS:
            assert not checks.judge(row[kind], cell.limits), (kind, row)


def test_a_number_without_a_limit_is_printed_not_compared():
    values = {k: 0.0 for k in checks.CHECKS}
    limits = dict(values, loss1_gap=None)
    values["loss1_gap"] = 1e9
    assert checks.judge(values, limits)
    values["loss_gap"] = 1e-9
    assert not checks.judge(values, limits)
