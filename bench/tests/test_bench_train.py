"""The training cell rehearsed on the CPU through the harness's run, its
control, and the faults a training step can have."""

import jax
import jax.numpy as jnp
import pytest

from bench.tests.cells import no_activation_rules, of_driver, run, small  # noqa: F401

TRAIN = of_driver("train")


@pytest.mark.parametrize("name", TRAIN)
def test_cell_runs_and_is_correct(name):
    out = run(small(name))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("name", TRAIN)
def test_control_is_not_correct(name):
    out = run(small(name), stand_in="control")
    assert not out["correct"], out["checks"]


def _patch_step(monkeypatch, make):
    import repro.launch.train as train_mod

    real = train_mod._build_step

    def build(*a, **kw):
        opt_init, step = real(*a, **kw)
        return opt_init, make(step)

    monkeypatch.setattr(train_mod, "_build_step", build)


def test_fault_state_unchanged(monkeypatch):
    def make(step):
        def unchanged(params, opt_state, batch, i):
            copy = lambda t: jax.tree.map(jnp.copy, t)
            _, _, mets = step(copy(params), copy(opt_state), batch, i)
            return params, opt_state, mets
        return unchanged

    _patch_step(monkeypatch, make)
    out = run(small(TRAIN[0]))
    assert not out["correct"], out["checks"]


def test_fault_half_batch(monkeypatch):
    def make(step):
        def half(params, opt_state, batch, i):
            rows = batch["inputs"].shape[0] // 2
            return step(params, opt_state, jax.tree.map(lambda a: a[:rows], batch), i)
        return half

    _patch_step(monkeypatch, make)
    out = run(small(TRAIN[0]))
    assert not out["correct"], out["checks"]
