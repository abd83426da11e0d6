#!/usr/bin/env python3
"""Bring-up check on TPU: serve, train, the CiM fabric and flash attention,
in one process, through the entry points a user calls.

    python chip_smoke.py             # one chip: serve, train, fabric, flash
    python chip_smoke.py --chips 4   # four chips: the sharded fabric only

Serve and train run smollm-135m at its published widths (30 layers, d 576,
vocab 49152) from random weights. Every phase checks its output against the
repo's own reference and prints one JSON line; the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU the script exits non-zero before any phase runs; it never
falls back to the CPU. A failed check raises, so the script exits non-zero
and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import shutil
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import ModelConfig  # noqa: E402
from repro.configs.registry import get_config  # noqa: E402
from repro.core.cim_linear import CiMConfig, quantize_symmetric  # noqa: E402
from repro.fabric import (  # noqa: E402
    ChipMeshConfig,
    FabricConfig,
    compile_graph_forward,
    execute_sharded_matmul,
    map_matmul,
    resolve_backend,
    shard_placement,
)
from repro.kernels import ref  # noqa: E402
from repro.kernels.flash_attention import flash_attention_pallas  # noqa: E402
from repro.kernels.ops import cim_matmul_op  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_chip_mesh  # noqa: E402
from repro.launch.serve import ServeSettings, compiled_model, serve_batch  # noqa: E402
from repro.launch.train import TrainSettings, train  # noqa: E402

ARCH = "smollm-135m"
OUT = REPO / "results" / "chip_smoke"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# the fabric's fused graph unrolls a python loop over 32-column array tiles;
# the 49152-wide unembed alone is 1536 of them, so the graph phases keep the
# block at published widths and cut depth and vocab
GRAPH_CUT = {"n_layers": 2, "vocab": 1024}


class PhaseFailed(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise PhaseFailed(what)


def emit(rec: dict) -> None:
    print(json.dumps(rec, default=float), flush=True)


class CompileLog:
    """The backend compilations JAX reports while the log is open (a load
    from the persistent cache counts too: it still builds an executable)."""

    def __init__(self):
        self.events: list[tuple[str, float]] = []

    def _on(self, event: str, secs: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.events.append((str(kw.get("fun_name")), secs))

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)

    def count(self, fun: str) -> int:
        return sum(name == f"jit({fun})" for name, _ in self.events)

    @property
    def seconds(self) -> float:
        return sum(s for _, s in self.events)


def max_diff(a, b) -> tuple[float, float]:
    """(max |a - b|, max |b|) in float32."""
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.abs(a - b).max()), float(np.abs(b).max())


def timed(fn, *args):
    t = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def phase_serve(name: str, cfg: ModelConfig, st: ServeSettings) -> dict:
    """``serve_batch`` twice on one batch: finite logits, the same tokens,
    and no compilation in the second run."""
    with CompileLog() as first:
        a = serve_batch(cfg, st)
    with CompileLog() as again:
        b = serve_batch(cfg, st)
    check(a["logits_finite"] and b["logits_finite"], f"{name}: non-finite logits")
    check(a["generated"].shape == (st.batch, st.gen_len), f"{name}: token shape")
    check(
        np.array_equal(a["generated"], b["generated"]),
        f"{name}: two runs generated different tokens",
    )
    for fun in ("prefill", "decode_step"):
        check(first.count(fun) == 1, f"{name}: {fun} compiled {first.count(fun)}x")
    check(
        not again.events,
        f"{name}: the second run compiled {[n for n, _ in again.events]}",
    )
    compiled_model.cache_clear()  # drop this config's weights from the chip
    return {
        "phase": name,
        "compile_s": first.seconds,
        "run_s": b["prefill_s"] + b["decode_s"],
        "prefill_s": b["prefill_s"],
        "decode_tok_s": b["decode_tok_s"],
        "tokens": b["generated"][0, :8].tolist(),
    }


def phase_train(cfg: ModelConfig, st: TrainSettings) -> dict:
    """A few ``train`` steps from scratch: finite losses, one compile of the
    step."""
    shutil.rmtree(st.ckpt_dir, ignore_errors=True)  # else train() resumes
    with CompileLog() as log:
        out = train(cfg, st)
    shutil.rmtree(st.ckpt_dir, ignore_errors=True)
    losses = out["losses"]
    check(len(losses) == st.steps, f"train: {len(losses)} of {st.steps} steps ran")
    check(all(math.isfinite(x) for x in losses), f"train: losses {losses}")
    check(log.count("train_step") == 1, f"train: step compiled {log.count('train_step')}x")
    run_s = sum(out["step_s"][1:])
    return {
        "phase": "train",
        "compile_s": log.seconds,
        "first_step_s": out["step_s"][0],
        "run_s": run_s,
        "tokens_s": st.batch * st.seq * (st.steps - 1) / run_s,
        "losses": losses,
    }


def cim_linear_shapes(cfg: ModelConfig) -> dict:
    """``(K, N)`` of the model's block linears, one entry per shape."""
    d, q, kv, ff = cfg.d_model, cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim, cfg.d_ff
    shapes: dict = {}
    for name, kn in (("wq", (d, q)), ("wk", (d, kv)), ("wv", (d, kv)), ("wo", (q, d)),
                     ("gate", (d, ff)), ("up", (d, ff)), ("down", (ff, d))):
        shapes.setdefault(kn, []).append(name)
    return {"+".join(names): kn for kn, names in shapes.items()}


def phase_cim_kernel(cfg: ModelConfig, m: int) -> dict:
    """``kernels.ops.cim_matmul_op`` at the model's linear shapes, both modes,
    against the ``kernels.ref`` oracle on the same quantized operands."""
    rows, adc_bits, bits = 128, 8, 8
    key = jax.random.PRNGKey(0)
    rec = {"phase": "fabric_cim_kernel", "m": m, "compile_s": 0.0, "run_s": 0.0, "diffs": {}}
    for mode in ("fake_quant", "bitplane"):
        for i, (name, (k, n)) in enumerate(cim_linear_shapes(cfg).items()):
            kx, kw = jax.random.split(jax.random.fold_in(key, i))
            x = jax.random.normal(kx, (m, k), jnp.float32)
            w = jax.random.normal(kw, (k, n), jnp.float32) / math.sqrt(k)
            t = time.perf_counter()
            compiled = cim_matmul_op.lower(
                x, w, rows=rows, adc_bits=adc_bits, mode=mode, interpret=False
            ).compile()
            rec["compile_s"] += time.perf_counter() - t
            check("tpu_custom_call" in compiled.as_text(), f"{mode} {name}: no Mosaic kernel")
            compiled(x, w)
            y, run_s = timed(compiled, x, w)
            rec["run_s"] += run_s

            x_int, sx = quantize_symmetric(x, bits, True)
            w_int, sw = quantize_symmetric(w, bits, True, per_axis=-1)
            pad = (-k) % rows
            want = ref.cim_matmul_ref(
                jnp.pad(x_int, ((0, 0), (0, pad))), jnp.pad(w_int, ((0, pad), (0, 0))),
                rows=rows, adc_bits=adc_bits, mode=mode, a_bits=bits, w_bits=bits,
            ) * sx * sw
            diff, scale = max_diff(y, want)
            rec["diffs"][f"{mode}:{name}"] = diff
            # the operands are integers below 2^8, exact at any matmul
            # precision; only the order of the float sums may differ
            check(diff <= 1e-5 * scale, f"{mode} {name}: max diff {diff} of {scale}")
    return rec


def graph_config(cfg: ModelConfig) -> ModelConfig:
    return dataclasses.replace(cfg, **GRAPH_CUT)


def fabric_setup():
    """The fabric and CiM config of ``serve --fabric hybrid --fabric-program``."""
    fb = FabricConfig(mode="hybrid", n_arrays=256)
    cim = CiMConfig(
        mode="bitplane", a_bits=4, w_bits=4, adc_bits=fb.adc_bits, rows=fb.rows, ste=False
    )
    return fb, cim


def phase_graph(cfg: ModelConfig, data: int, tokens: int) -> dict:
    """The scanned fused graph program on a real ``data x 1`` mesh against
    the per-node reference loop on one chip (splitting rows over chips
    changes no arithmetic, and one chip's loop costs a quarter of four
    chips' on the host). ``backend="shard_map"`` makes any fallback to the
    reference loop an error."""
    fb, cim = fabric_setup()
    gcfg = graph_config(cfg)
    compile_on = lambda cm, backend: compile_graph_forward(
        gcfg, cm, cim=cim, backend=backend, tokens=tokens, scan_layers=True
    )
    prog = compile_on(ChipMeshConfig(data=data, model=1, fabric=fb), "shard_map")
    check(prog.backend == "shard_map" and not prog.problems, f"graph: {prog.problems}")
    one_chip = prog if data == 1 else compile_on(ChipMeshConfig(fabric=fb), "sequential")
    x = jax.random.normal(jax.random.PRNGKey(2), (tokens, 1, prog.d_in))
    w = prog.random_weights(jax.random.PRNGKey(3))
    with CompileLog() as log:
        y, first_s = timed(prog, x, w)
    y, run_s = timed(prog, x, w)
    y_ref, ref_s = timed(one_chip.reference_forward, x, w)
    check(bool(np.isfinite(np.asarray(y)).all()), "graph: non-finite logits")
    diff, scale = max_diff(y, y_ref)
    # bit-exact on CPU; TPU matmul precision may flip an ADC code at a bin edge
    check(diff <= 1e-2 * scale, f"graph: max diff {diff} of {scale}")
    return {
        "phase": f"fabric_graph_{data}x1",
        "reduced": GRAPH_CUT,
        "compile_s": log.seconds,
        "first_call_s": first_s,
        "run_s": run_s,
        "reference_s": ref_s,
        "max_abs_diff": diff,
        "max_abs_ref": scale,
        "bit_exact": diff == 0.0,
    }


def phase_flash(cfg: ModelConfig, seq: int) -> dict:
    """The flash kernel once at the model's head layout against the plain
    softmax oracle."""
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    kq, kk, kvk = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(kq, (1, h, seq, hd), jnp.bfloat16)
    k = jax.random.normal(kk, (1, kv, seq, hd), jnp.bfloat16)
    v = jax.random.normal(kvk, (1, kv, seq, hd), jnp.bfloat16)
    t = time.perf_counter()
    compiled = flash_attention_pallas.lower(q, k, v, causal=True, interpret=False).compile()
    compile_s = time.perf_counter() - t
    check("tpu_custom_call" in compiled.as_text(), "flash: no Mosaic kernel")
    compiled(q, k, v)
    o, run_s = timed(compiled, q, k, v)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    diff, _ = max_diff(o, want)
    err = np.abs(np.asarray(o, np.float32) - np.asarray(want, np.float32))
    # the bf16 tolerance of tests/test_flash_attention.py
    check(bool((err <= 2e-2 + 2e-2 * np.abs(np.asarray(want, np.float32))).all()),
          f"flash: max diff {diff}")
    return {
        "phase": "flash",
        "shape": [1, h, kv, seq, hd],
        "compile_s": compile_s,
        "run_s": run_s,
        "max_abs_diff": diff,
    }


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def phase_sharded_matmul(cfg: ModelConfig, data: int, model: int, m: int) -> dict:
    """``execute_sharded_matmul`` on the ``shard_map`` backend for the
    model's linears, against the sequential backend on the same mesh."""
    fb, cim = fabric_setup()
    make_chip_mesh(data, model, require_concrete=True)
    cm = ChipMeshConfig(data=data, model=model, fabric=fb)
    key = jax.random.PRNGKey(5)
    rec = {"phase": f"fabric_shard_{data}x{model}", "m": m, "run_s": 0.0,
           "reference_s": 0.0, "diffs": {}}
    for i, (name, (k, n)) in enumerate(cim_linear_shapes(cfg).items()):
        sp = shard_placement(map_matmul(name, m, k, n, fb), cm)
        resolved = resolve_backend(sp, "auto")
        check(resolved == "shard_map", f"{name} on {data}x{model}: auto resolved {resolved}")
        kx, kw = jax.random.split(jax.random.fold_in(key, i))
        x = jax.random.normal(kx, (m, k))
        w = jax.random.normal(kw, (k, n)) / math.sqrt(k)
        run = lambda backend: execute_sharded_matmul(x, w, cm, cim, sharded=sp, backend=backend)
        timed(run, "shard_map")
        y, run_s = timed(run, "shard_map")
        y_ref, ref_s = timed(run, "sequential")
        rec["run_s"] += run_s
        rec["reference_s"] += ref_s
        diff, scale = max_diff(y, y_ref)
        rec["diffs"][name] = diff
        # integer partial sums: the reduce-scatter sum is exact
        check(diff <= 1e-5 * scale, f"{name} on {data}x{model}: max diff {diff} of {scale}")
    return rec


# ---------------------------------------------------------------------------


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4 runs only the sharded fabric (1x4 and 2x2 matmuls, 4x1 graph)",
    )
    args = ap.parse_args()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform is {devices[0].platform!r})")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} TPUs, found {len(devices)}")
    use_compile_cache()
    cfg = get_config(ARCH)

    if args.chips == 4:
        emit(phase_sharded_matmul(cfg, 1, 4, m=8))
        emit(phase_sharded_matmul(cfg, 2, 2, m=8))
        emit(phase_graph(cfg, data=4, tokens=4))
    else:
        st = ServeSettings(batch=4, prompt_len=128, gen_len=32)
        emit(phase_serve("serve_exact", cfg, st))
        fq = dataclasses.replace(cfg, cim=CiMConfig(mode="fake_quant", ste=False))
        emit(phase_serve("serve_cim_fake_quant", fq, st))
        emit(phase_train(cfg, TrainSettings(
            steps=5, batch=8, seq=512, warmup=1, ckpt_dir=str(OUT / "ckpt"),
            ckpt_every=5, log_every=1,
        )))
        emit(phase_cim_kernel(cfg, m=st.batch * st.prompt_len))
        emit(phase_graph(cfg, data=1, tokens=st.batch))
        emit(phase_flash(cfg, seq=2048))

    d = jax.devices()[0]
    emit({"ok": True, "device": {"platform": d.platform, "kind": d.device_kind,
                                 "count": len(jax.devices())}})


if __name__ == "__main__":
    main()
