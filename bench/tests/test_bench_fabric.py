"""The fabric cell rehearsed on the CPU through the harness's run, with its
control and an answer altered where it is produced in the program's place."""

import jax.numpy as jnp

from bench.tests.cells import no_activation_rules, run, small  # noqa: F401

FABRIC = "fabric_graph.smollm-135m-fabric"


def test_cell_runs_and_is_correct():
    out = run(small(FABRIC))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


def test_control_is_not_correct():
    out = run(small(FABRIC), stand_in="control")
    assert not out["correct"], out["checks"]


def test_fault_answer_altered(monkeypatch):
    from repro.fabric.graph import GraphProgram

    real = GraphProgram.__call__

    def altered(self, x, weights, *a, **kw):
        y = real(self, x, weights, *a, **kw)
        return y.at[0, 0, :8].add(0.5 * jnp.abs(y).max())

    monkeypatch.setattr(GraphProgram, "__call__", altered)
    out = run(small(FABRIC))
    assert not out["correct"], out["checks"]
