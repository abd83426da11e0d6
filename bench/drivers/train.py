"""The program's own jitted train step, driven step after step.

Set-up builds the step and its state once (``launch.train``'s step builder,
parameters from the seed, AdamW state) and runs the first ``ref_steps``
steps through the same call and feed the window uses: one
``TokenPipeline`` batch per step, rows that all differ, and a wait for each
step's loss, as ``launch.train.train`` does. The window goes on from there
with no checkpoint saves.

Traffic keys: ``batch``, ``seq``, ``lr``, ``warmup``, ``steps`` (the length
of the learning-rate schedule), ``optimizer`` (what the program's AdamW is
stated with), ``ref_steps``, ``trace_seconds`` and ``limits``.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.drivers import common
from bench.harness import flops


@dataclasses.dataclass
class State:
    cell: object
    seed: int
    step_fn: object
    params: object
    opt_state: object
    pipe: object
    mesh: object
    step: int = 0
    losses: list = dataclasses.field(default_factory=list)
    window_steps: int = 0
    readings: dict = dataclasses.field(default_factory=dict)


def _run_step(state: State) -> dict:
    batch = jax.tree.map(jnp.asarray, state.pipe.batch(state.step))
    state.params, state.opt_state, mets = state.step_fn(
        state.params, state.opt_state, batch, jnp.asarray(state.step, jnp.int32))
    state.losses.append(float(mets["loss"]))  # waits for the step
    state.step += 1
    return mets


def _leaf_norms(tree) -> dict:
    return {jax.tree_util.keystr(k): float(jnp.linalg.norm(jnp.ravel(v).astype(jnp.float32)))
            for k, v in jax.tree_util.tree_leaves_with_path(tree)}


def setup(cell, seed: int) -> State:
    from repro.data.tokens import TokenPipeline
    from repro.launch.mesh import make_local_mesh
    from repro.launch.train import TrainSettings, _build_step
    from repro.models import build_model

    tr = cell.traffic
    cfg = common.model_config(cell.config, ste=True)
    st = TrainSettings(steps=tr["steps"], batch=tr["batch"], seq=tr["seq"], lr=tr["lr"],
                       warmup=tr["warmup"], seed=seed)
    mesh = make_local_mesh()
    model = build_model(cfg)
    opt_init, step_fn = _build_step(model, cfg, st, mesh)
    with jax.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(seed))
        opt_state = opt_init(params)
        start = jax.tree.map(lambda p: np.asarray(p, np.float32), params)
        pipe = TokenPipeline(vocab=cfg.vocab, seq_len=st.seq, global_batch=st.batch, seed=seed)
        state = State(cell, seed, step_fn, params, opt_state, pipe, mesh)
        opt = tr["optimizer"]
        with common.span("first_steps"):
            for _ in range(tr["ref_steps"]):
                mets = _run_step(state)
                if state.step == 1:
                    # AdamW got the gradient g, clipped it by c to a global
                    # norm and kept m = (1 - b1) c g; the step reports |g|
                    clip = min(1.0, opt["grad_clip"] / max(float(mets["grad_norm"]), 1e-9))
                    first = jax.tree.map(lambda m: m / ((1 - opt["b1"]) * clip),
                                         state.opt_state.m)
                    state.readings["grad"] = _leaf_norms(first)
        change = jax.tree.map(lambda p, p0: np.asarray(p, np.float32) - p0, state.params, start)
        state.readings["change"] = _leaf_norms(change)
    return state


def window(state: State, seconds: float) -> float:
    t0 = time.perf_counter()
    with jax.set_mesh(state.mesh):
        while True:
            with common.span("train_step"):
                _run_step(state)
            state.window_steps += 1
            t = time.perf_counter()
            if t - t0 >= seconds:
                return t - t0


def attempted(state: State) -> int:
    return state.window_steps


def failed(state: State) -> int:
    return sum(not np.isfinite(x) for x in state.losses[-state.window_steps:])


def _tokens(state: State) -> int:
    tr = state.cell.traffic
    return state.window_steps * tr["batch"] * tr["seq"]


def end_to_end(state: State, window_s: float) -> dict:
    return {"train_tok_s": _tokens(state) / window_s}


def counters(state: State) -> dict:
    return {"steps": state.window_steps,
            "model_flops": _tokens(state) * flops.train_flops_per_token(state.cell.config)}


def worst_gap(got: dict, want: dict) -> float:
    """Largest gap between two sets of leaf norms, each over the larger of
    the reference leaf's norm and the median leaf's. Leaves whose reference
    norm is under a thousandth of the median leaf's move by rounding alone
    and are left out. A norm that is not finite on either side reads as
    infinitely far."""
    if not all(np.isfinite(v) for v in (*got.values(), *want.values())):
        return float("inf")
    median = float(np.median(list(want.values())))
    return max(abs(got[k] - w) / max(w, median) for k, w in want.items() if w >= 1e-3 * median)


def reference_readings(conf: dict, traffic: dict, seed: int, batches: list,
                       precision: str = "float32") -> dict:
    from bench.reference import llama

    sched = {k: traffic[k] for k in ("lr", "warmup", "steps", "optimizer")}
    losses, grad, start, end = llama.train_steps(conf, seed, batches, sched, precision)
    return {
        "losses": losses,
        "grad": _leaf_norms(grad),
        "change": _leaf_norms(jax.tree.map(lambda a, b: a - b, end, start)),
    }


def compare(program: dict, ref: dict) -> dict:
    return {
        "loss_gap": max(abs(a - b) / abs(b) for a, b in zip(program["losses"], ref["losses"])),
        "grad_norm_gap": worst_gap(program["grad"], ref["grad"]),
        "change_norm_gap": worst_gap(program["change"], ref["change"]),
    }


def _batches(state: State) -> list:
    n = state.cell.traffic["ref_steps"]
    return [(b["inputs"], b["labels"]) for b in map(state.pipe.batch, range(n))]


def check(state: State) -> dict:
    tr = state.cell.traffic
    program = dict(state.readings, losses=state.losses[:tr["ref_steps"]])
    state.params = state.opt_state = state.step_fn = None
    common.release()
    state.readings["reference"] = reference_readings(state.cell.config, tr, state.seed,
                                                     _batches(state))
    return compare(program, state.readings["reference"])


def control(state: State) -> dict:
    """The reference at the configuration's control precision in the
    program's place, read against the reference (after :func:`check`)."""
    conf = state.cell.config
    ctl = reference_readings(conf, state.cell.traffic, state.seed, _batches(state),
                             conf["control_precision"])
    return compare(ctl, state.readings["reference"])


def faults(state: State) -> dict:
    """The check's numbers for faults planted in the reference put in the
    program's place (after :func:`check`): half of each batch left out, the
    mean taken over the rest (``half_batch``), and the ADC round taken out
    of every CiM linear (``adc_removed``)."""
    conf, tr = state.cell.config, state.cell.traffic
    half = [(x[: len(x) // 2], y[: len(y) // 2]) for x, y in _batches(state)]
    ref = state.readings["reference"]
    no_adc = dict(conf, cim=dict(conf["cim"], adc_bits=None))
    return {
        "half_batch": compare(reference_readings(conf, tr, state.seed, half), ref),
        "adc_removed": compare(reference_readings(no_adc, tr, state.seed, _batches(state)), ref),
    }
