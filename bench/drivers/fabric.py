"""Back-to-back calls of the fabric's scanned, fused graph program.

Each call simulates one forward of ``batch x seq`` token rows through every
node of the graph (bit-plane CiM matmuls through the ADC, attention mixing,
norms, residuals, the unembed) as one ``shard_map`` program. Weights and a
pool of inputs are drawn from the seed by the benchmark and cycled through.

Traffic keys: ``batch``, ``seq``, ``pool`` (inputs drawn), ``check_calls``
(finished calls the check draws from the seed), ``trace_seconds`` and
``limits``. The configuration states float32, which a TPU computes for a
float32 matmul only at JAX's ``highest`` precision: the program and the
reference run under it.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from bench.drivers import common
from bench.reference import fabric_graph


@dataclasses.dataclass
class State:
    cell: object
    seed: int
    program: object
    weights: dict
    inputs: object  # (pool, batch, seq, d) on the device
    calls: list = dataclasses.field(default_factory=list)  # (input index, logits)
    sample: list = dataclasses.field(default_factory=list)


def float32():
    return jax.default_matmul_precision("highest")


def _program(conf: dict, tokens: int):
    from repro.core.cim_linear import CiMConfig
    from repro.fabric import ChipMeshConfig, FabricConfig, compile_graph_forward

    fab = conf["fabric"]
    fb = FabricConfig(mode=fab["mode"], n_arrays=fab["n_arrays"])
    cim = CiMConfig(**conf["cim"], adc_bits=fb.adc_bits, rows=fb.rows, ste=False)
    data, model = fab["mesh"]
    prog = compile_graph_forward(
        common.model_config({**conf, "cim": None}), ChipMeshConfig(data=data, model=model, fabric=fb),
        cim=cim, backend="shard_map", tokens=tokens, scan_layers=True,
    )
    if prog.backend != "shard_map" or prog.problems:
        raise RuntimeError(f"fused graph unavailable: {prog.problems}")
    return prog


def setup(cell, seed: int) -> State:
    tr, conf = cell.traffic, cell.config
    with float32():
        prog = _program(conf, tr["batch"] * tr["seq"])
        weights = fabric_graph.make_weights(conf, seed)
        inputs = fabric_graph.make_inputs(conf, seed, tr["pool"], tr["batch"], tr["seq"])
        state = State(cell, seed, prog, weights, inputs)
        with common.span("warmup"):
            jax.block_until_ready(prog(inputs[0], weights))
    return state


def window(state: State, seconds: float) -> float:
    pool = state.inputs.shape[0]
    t0 = time.perf_counter()
    with float32():
        while True:
            i = len(state.calls) % pool
            with common.span("fabric_call"):
                y = jax.block_until_ready(state.program(state.inputs[i], state.weights))
            state.calls.append((i, y))
            t = time.perf_counter()
            if t - t0 >= seconds:
                return t - t0


def attempted(state: State) -> int:
    return len(state.calls)


def failed(state: State) -> int:
    return sum(not bool(np.isfinite(np.asarray(y)).all()) for _, y in state.calls)


def end_to_end(state: State, window_s: float) -> dict:
    tr = state.cell.traffic
    return {"fabric_tok_s": len(state.calls) * tr["batch"] * tr["seq"] / window_s}


def counters(state: State) -> dict:
    return {"calls": len(state.calls)}


def check_sample(state: State) -> list:
    n = state.cell.traffic["check_calls"]
    pick = common.rng(state.seed, 2).choice(len(state.calls), min(n, len(state.calls)),
                                            replace=False)
    return [(state.calls[i][0], np.asarray(state.calls[i][1])) for i in sorted(pick)]


def readings(conf: dict, weights: dict, inputs, sample: list) -> dict:
    """The numbers compared, over the sampled calls: the largest absolute
    difference of any logit from the reference's, over the largest
    reference logit (``logit_err``)."""
    worst = 0.0
    with float32():
        for i, y in sample:
            want = np.asarray(fabric_graph.forward(conf, weights, inputs[i]))
            worst = max(worst, float(np.abs(y - want).max() / np.abs(want).max()))
    return {"logit_err": worst}


def check(state: State) -> dict:
    state.sample = check_sample(state)
    state.calls, state.program = [], None
    common.release()
    return readings(state.cell.config, state.weights, state.inputs, state.sample)


def control(state: State) -> dict:
    """The reference at the configuration's control precision in the
    program's place, read against the reference (after :func:`check`)."""
    conf = state.cell.config
    with float32():
        ctl = [(i, np.asarray(fabric_graph.forward(conf, state.weights, state.inputs[i],
                                                   conf["control_precision"])))
               for i, _ in state.sample]
    return readings(conf, state.weights, state.inputs, ctl)


def faults(state: State) -> dict:
    """The check's numbers for a fault planted where answers are produced
    (after :func:`check`): one token row of one sampled call altered."""
    (i, y), *rest = state.sample
    y = y.copy()
    y[0, 0] += 0.5 * np.abs(y).max()
    return {"answer_altered": readings(state.cell.config, state.weights, state.inputs,
                                       [(i, y), *rest])}
