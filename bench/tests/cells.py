"""Cells at a size a CPU test run holds: the published widths, fewer layers,
a shorter vocabulary and small batches, so that the logits spread as the
cell's do and the cell's own limits apply."""

import json
import time

import pytest

from bench.harness import runner

SEED = 2**31 + 101
TRAFFIC = {"serve": dict(batch=4, prompt_len=16, gen_len=8),
           "fabric": dict(batch=1, seq=4),
           "train": dict(batch=2, seq=64)}
CELLS = [w["name"] for w in json.loads((runner.ROOT / "BENCHMARK.json").read_text())["workloads"]]


def small(name: str, layers: int = 2):
    """The cell ``name`` of ``BENCHMARK.json`` at a test run's size."""
    cell = runner.load_cell(name)
    cell.config = dict(cell.config, num_hidden_layers=layers, vocab_size=512)
    cell.traffic = dict(cell.traffic, **TRAFFIC[cell.traffic["driver"]], trace_seconds=0.5)
    return cell


def run(cell, trace: bool = False, stand_in: str | None = None) -> dict:
    """A whole run but for the look for a chip."""
    return runner.run(cell, SEED, 0.5, trace, time.perf_counter(), stand_in=stand_in)


def of_driver(kind: str) -> list:
    return [c for c in CELLS if runner.load_cell(c).traffic["driver"] == kind]


@pytest.fixture(autouse=True)
def no_activation_rules():
    """The program's train-step builder sets module-wide activation sharding
    rules; a benchmark run is one cell per process, a test run is not."""
    from repro.models import layers

    yield
    layers.set_act_rules(None)
