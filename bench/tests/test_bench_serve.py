"""The serving cells rehearsed on the CPU through the harness's run, their
control, and a token altered where it is produced."""

import jax
import pytest

from bench.harness import runner
from bench.tests.cells import no_activation_rules, of_driver, run, small  # noqa: F401

SERVE = of_driver("serve")


@pytest.mark.parametrize("name", SERVE)
def test_cell_runs_and_is_correct(name):
    out = run(small(name))
    assert list(out) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in runner.load_cell(name).end_to_end}
    assert out["device"]["platform"] == jax.devices()[0].platform


def test_traced_run_reports_per_layer_metrics():
    out = run(small(SERVE[0]), trace=True)
    assert list(out)[-1] == "checks" and "breakdown" in out
    assert out["device"]["window_s"] > 0
    assert out["metrics"]["decode_step_ms"]["value"] > 0


@pytest.mark.parametrize("name", SERVE)
def test_control_separates_from_the_program(name):
    """Through the harness's own comparison, with the control in the
    program's place. At a test run's size a request has 8 served tokens and
    the vocabulary is short, so the control reads below some cells' limits;
    it still reads above nought and three times the program's reading or
    more. At the cell's size it fails the limit (PERF.md)."""
    program = run(small(name, layers=4))["checks"]["gap_share"]["value"]
    control = run(small(name, layers=4), stand_in="control")["checks"]["gap_share"]["value"]
    assert control > 0 and control >= 3 * program, (program, control)


def test_fault_served_token_altered(monkeypatch):
    import repro.launch.serve as serve_mod

    real = serve_mod.serve_batch

    def altered(cfg, st, prompts=None, **kw):
        out = real(cfg, st, prompts=prompts, **kw)
        out["generated"] = out["generated"].copy()
        out["generated"][0] = (out["generated"][0] + cfg.vocab // 2) % cfg.vocab
        return out

    monkeypatch.setattr(serve_mod, "serve_batch", altered)
    out = run(small(SERVE[0]))
    assert not out["correct"], out["checks"]
