"""Closed loop of static batches through the program's ``serve_batch``.

``serve_batch`` serves one static batch lock-step (one jitted prefill, then a
host loop of jitted decode steps) and admits nothing while it runs, so the
load is a closed loop: the next batch is sent when the last one returns.
Prompts are drawn from the seed, uniform over the vocabulary; every seed gets
the same sizes.

Traffic keys: ``batch``, ``prompt_len``, ``gen_len``, ``check_batches`` (how
many finished batches the check draws from the seed and runs the reference
over), ``check_requests`` (how many of their requests it compares),
``gap_tolerance`` (where the check counts the tokens whose gap exceeds it),
``trace_seconds`` and ``limits``.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench.drivers import common
from bench.harness import flops
from bench.reference import llama


@dataclasses.dataclass
class State:
    cell: object
    seed: int
    cfg: object
    settings: object
    prompts: object  # np.random.Generator of the prompt stream
    batches: list = dataclasses.field(default_factory=list)
    sample: list = dataclasses.field(default_factory=list)  # what the check compares
    detail: dict = dataclasses.field(default_factory=dict)  # summary of the gaps


def _next_prompts(state: State) -> np.ndarray:
    tr = state.cell.traffic
    return state.prompts.integers(
        0, state.cell.config["vocab_size"], (tr["batch"], tr["prompt_len"]), dtype=np.int32
    )


def setup(cell, seed: int) -> State:
    from repro.launch.serve import ServeSettings, serve_batch

    tr = cell.traffic
    st = ServeSettings(batch=tr["batch"], prompt_len=tr["prompt_len"],
                       gen_len=tr["gen_len"], seed=seed)
    state = State(cell, seed, common.model_config(cell.config, ste=False), st,
                  common.rng(seed, 1))
    # weights are made by the program from the seed; one batch compiles
    # prefill and decode at the cell's shapes
    with common.span("warmup"):
        serve_batch(state.cfg, st, prompts=_next_prompts(state))
    return state


def window(state: State, seconds: float) -> float:
    """Serve batches back to back until ``seconds`` have passed; the window
    ends when the batch that crossed the mark returns."""
    from repro.launch.serve import serve_batch

    t0 = time.perf_counter()
    while True:
        prompts = _next_prompts(state)
        with common.span("serve_batch"):
            out = serve_batch(state.cfg, state.settings, prompts=prompts)
        state.batches.append({
            "prompts": prompts,
            "generated": out["generated"],
            "prefill_s": out["prefill_s"],
            "decode_s": out["decode_s"],
            "finite": out["logits_finite"],
        })
        t = time.perf_counter()
        if t - t0 >= seconds:
            return t - t0


def attempted(state: State) -> int:
    return sum(len(b["prompts"]) for b in state.batches)


def failed(state: State) -> int:
    return sum(len(b["prompts"]) for b in state.batches if not b["finite"])


def end_to_end(state: State, window_s: float) -> dict:
    tr = state.cell.traffic
    ttft = np.repeat([b["prefill_s"] for b in state.batches], tr["batch"])
    return {
        "serve_tok_s": attempted(state) * tr["gen_len"] / window_s,
        "ttft_p95_ms": float(np.percentile(ttft, 95)) * 1e3,
    }


def counters(state: State) -> dict:
    tr = state.cell.traffic
    n = len(state.batches)
    return {
        "batches": n,
        "decode_s": sum(b["decode_s"] for b in state.batches),
        "decode_steps": n * (tr["gen_len"] - 1),
        "model_flops": n * flops.serve_batch_flops(
            state.cell.config, tr["batch"], tr["prompt_len"], tr["gen_len"]),
    }


def check_sample(state: State) -> list:
    """The finished batches the check runs the reference over, and in them
    the requests whose served tokens it compares, drawn from the seed:
    ``[(batch, rows)]``."""
    tr = state.cell.traffic
    r = common.rng(state.seed, 2)
    picked = sorted(r.choice(len(state.batches), min(tr["check_batches"], len(state.batches)),
                             replace=False))
    slots = [(i, row) for i in range(len(picked)) for row in range(tr["batch"])]
    chosen = r.choice(len(slots), min(tr["check_requests"], len(slots)), replace=False)
    return [(state.batches[b], sorted(row for i, row in (slots[c] for c in chosen) if i == k))
            for k, b in enumerate(picked)]


def _sequences(batch: dict) -> np.ndarray:
    return np.concatenate([batch["prompts"], batch["generated"][:, :-1]], axis=1)


THRESHOLDS = (0.02, 0.05, 0.1, 0.15, 0.2, 0.3, 0.5, 1.0)


def _token_gaps(conf, seed, sample, targets, precision="float32") -> list:
    """Per compared request, the gap of each of its target tokens below the
    reference's best logit; ``targets(batch, index)`` gives ``(B, G)``."""
    out = []
    for k, (batch, rows) in enumerate(sample):
        gaps, _ = llama.served_gaps(conf, seed, _sequences(batch), batch["prompts"].shape[1],
                                    targets(batch, k)[..., None], precision)
        out += [gaps[row, :, 0] for row in rows]
    return out


def numbers(conf: dict, traffic: dict, gaps: list) -> dict:
    """The numbers a serving check can compare: the widest gap of any
    compared token (``logit_gap``) and, where the traffic names a
    ``gap_tolerance``, the largest share in percent, over the compared
    requests, of one request's served tokens whose gap exceeds it
    (``gap_share``)."""
    out = {"logit_gap": float(max(g.max() for g in gaps))}
    if "gap_tolerance" in traffic:
        out["gap_share"] = max(float(np.mean(g > traffic["gap_tolerance"])) * 100.0
                               for g in gaps)
    return out


def summary(gaps: list) -> dict:
    """What a calibration prints of the per-token gaps."""
    every = np.concatenate(gaps)
    return {"per_request_max": [float(g.max()) for g in gaps],
            "per_request_over_0.1": [int((g > 0.1).sum()) for g in gaps],
            "tokens": int(every.size),
            "over": {str(t): int((every > t).sum()) for t in THRESHOLDS}}


def served_gaps(conf: dict, seed: int, sample: list) -> list:
    """Per compared request, the gap by which each served token's logit lies
    below the reference's best at its position."""
    return _token_gaps(conf, seed, sample, lambda batch, k: batch["generated"])


def first_token_gaps(conf: dict, seed: int, sample: list, other: dict, precision: str) -> list:
    """The same reading for the token that ``other`` (the reference at
    another precision or with another configuration) puts first at each
    position of the same prompts and served tokens."""
    firsts = []
    for batch, _ in sample:
        _, first = llama.served_gaps(other, seed, _sequences(batch), batch["prompts"].shape[1],
                                     batch["generated"][..., None], precision)
        firsts.append(first)
    return _token_gaps(conf, seed, sample, lambda batch, k: firsts[k])


def check(state: State) -> dict:
    """``{name: value}`` of every number compared; the program's state is
    freed first. ``state.detail`` keeps a summary of the per-token gaps."""
    state.sample = check_sample(state)
    state.batches = []
    common.release()
    gaps = served_gaps(state.cell.config, state.seed, state.sample)
    state.detail = summary(gaps)
    return numbers(state.cell.config, state.cell.traffic, gaps)


def control(state: State) -> dict:
    """The check's numbers for the reference at the configuration's control
    precision in the program's place (after :func:`check`)."""
    conf = state.cell.config
    gaps = first_token_gaps(conf, state.seed, state.sample, conf, conf["control_precision"])
    state.detail = summary(gaps)
    return numbers(conf, state.cell.traffic, gaps)


def faults(state: State) -> dict:
    """The check's numbers for faults planted in what is served (after
    :func:`check`): every served token of one request replaced
    (``token_altered``), and the reference with its ADC round taken out in
    the program's place (``adc_removed``)."""
    conf, traffic = state.cell.config, state.cell.traffic
    vocab = conf["vocab_size"]
    sample = [(dict(b, generated=b["generated"].copy()), rows) for b, rows in state.sample]
    batch, rows = next((b, rows) for b, rows in sample if rows)
    batch["generated"][rows[0]] = (batch["generated"][rows[0]] + vocab // 2) % vocab
    no_adc = dict(conf, cim=dict(conf["cim"], adc_bits=None))
    return {
        "token_altered": numbers(conf, traffic, served_gaps(conf, state.seed, sample)),
        "adc_removed": numbers(conf, traffic, first_token_gaps(
            conf, state.seed, state.sample, no_adc, "float32")),
    }
