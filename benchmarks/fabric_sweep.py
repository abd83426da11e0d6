"""Fabric design-space sweep: networking mode x precision x chip budget.

Emits one JSON record per design point — chip area, digitization area,
conversions/cycle, throughput/mm^2, energy/conversion, and the iso-area
ratios against the conventional-ADC baseline — so successive PRs can track
the chip-level trajectory. ``shard_sweep_points`` extends the sweep across
1- / 4- / 16-chip meshes (``repro.fabric.shard``), reporting per-layer
on-chip EMA vs cross-chip reduce-scatter traffic; ``shard_backend_smoke``
executes the sharded matmul numerically through both chip backends
(sequential host loop vs real multi-device ``shard_map``) and compares;
``program_smoke`` runs the whole-model fused forward
(``repro.fabric.program``) against the per-layer loop and records the
measured-vs-modeled link-latency ratio; ``graph_smoke`` runs the
full-transformer-block fused GRAPH forward (``repro.fabric.graph``) with
real ``init_transformer`` weights against the per-node reference and checks
the collective census against the documented budget; ``scan_smoke``
compiles the SAME graph unrolled and scanned (``scan_layers=True``) at
``n_layers=8`` and records the compile-time speedup plus scanned-vs-unrolled
bit-exactness; ``autotune_smoke`` serves a mixed-length ragged request
trace through the bucketed fused-program cache (``repro.fabric.autotune``)
— bit-exact after pad-slicing, measured speedup vs the per-node loop, and
the autotuner's plan cost vs the default mesh; ``obs_smoke`` runs the
fused chain under an active ``repro.obs`` registry + JSONL tracer and
reports the canonical metric names, fallback-counter semantics, and
obs-on/off bit-identity the CI observability gate checks. Doubles as the
``fabric`` / ``fabric-autotune`` / ``fabric-smokes`` entries of
``benchmarks/run.py`` (``fabric_bench`` / ``autotune_bench`` /
``smoke_bench``, the latter two at 1x1 so they run without forced
devices) and the <30 s smoke benchmark of ``tools/ci_check.py``.

  PYTHONPATH=src python -m benchmarks.fabric_sweep [--out BENCH_fabric.json]
  PYTHONPATH=src:. XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python -m benchmarks.fabric_sweep --backend-smoke
  PYTHONPATH=src:. XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python -m benchmarks.fabric_sweep --program-smoke
  PYTHONPATH=src:. XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python -m benchmarks.fabric_sweep --graph-smoke
  PYTHONPATH=src:. XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python -m benchmarks.fabric_sweep --scan-smoke
  PYTHONPATH=src:. XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python -m benchmarks.fabric_sweep --autotune-smoke
  PYTHONPATH=src:. XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python -m benchmarks.fabric_sweep --obs-smoke
"""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path


def sweep_points(
    modes=("pair_sar", "hybrid", "flash"),
    bit_range=(4, 5, 6),
    array_budgets=(128, 256),  # >= one flash group even at 6 bits (3+63)
) -> list[dict]:
    from repro.core.energy_area import energy_pj
    from repro.fabric.pipeline import fabric_throughput, iso_area_comparison
    from repro.fabric.topology import FabricConfig

    points = []
    for mode in modes:
        for bits in bit_range:
            flash_bits = min(2, bits - 1)
            for n_arrays in array_budgets:
                fb = FabricConfig(
                    mode=mode, adc_bits=bits, flash_bits=flash_bits, n_arrays=n_arrays
                )
                tp = fabric_throughput(fb)
                iso = iso_area_comparison(fb)
                points.append(
                    {
                        "mode": mode,
                        "adc_bits": bits,
                        "n_arrays": fb.resolved_n_arrays(),
                        "chip_area_mm2": fb.chip_area_um2() / 1e6,
                        "chip_adc_area_mm2": fb.chip_adc_area_um2() / 1e6,
                        "conversions_per_cycle": tp["chip_conversions_per_cycle"],
                        "throughput_per_mm2": tp["throughput_per_mm2"],
                        "energy_pj_per_conversion": energy_pj(
                            fb.adc_style,
                            bits,
                            flash_bits=flash_bits,
                            flash_share=fb.n_cim_per_group,
                        ),
                        "adc_area_ratio": iso["adc_area_ratio"],
                        "iso_area_throughput_ratio": iso["throughput_ratio"],
                    }
                )
    return points


def shard_sweep_points(
    meshes=((1, 1), (2, 2), (4, 4)),  # 1-, 4-, 16-chip meshes (data x model)
    mode="hybrid",
    n_arrays=252,
    tokens=4,
) -> list[dict]:
    """Shard a smollm block across chip meshes; per-layer on-chip EMA vs
    cross-chip reduce-scatter traffic, per ``repro.fabric.shard``."""
    from repro.configs.registry import get_config
    from repro.fabric.report import sharded_fabric_report
    from repro.fabric.shard import shard_model
    from repro.fabric.topology import ChipMeshConfig, FabricConfig

    cfg = get_config("smollm-135m")
    points = []
    for data, model in meshes:
        cm = ChipMeshConfig(
            data=data, model=model, fabric=FabricConfig(mode=mode, n_arrays=n_arrays)
        )
        t0 = time.perf_counter()
        sps = shard_model(cfg, cm, tokens=tokens, block_only=True)
        rep = sharded_fabric_report(sps, cm)
        wall = time.perf_counter() - t0
        t = rep["totals"]
        points.append(
            {
                "mesh": f"{data}x{model}",
                "n_chips": cm.n_chips,
                "map_report_s": wall,
                "tiles_per_chip": t["tiles_per_chip"],
                "model_resident": t["model_resident"],
                "latency_s": t["latency_s"],
                "latency_s_overlapped": t["latency_s_overlapped"],
                "onchip_ema_bits_per_pass": t["ema_bits_per_pass"],
                "crosschip_bits_per_pass": t["crosschip_bits_per_pass"],
                "crosschip_energy_pj": t["crosschip_energy_pj"],
                "fallbacks": len(rep["mesh"]["fallbacks"]),
                "layers": [
                    {
                        "layer": r["layer"],
                        "k_splits": r["k_splits"],
                        "d_splits": r["d_splits"],
                        "onchip_ema_bits": r["ema_bits_per_pass"],
                        "crosschip_bits": r["crosschip_bits_per_pass"],
                    }
                    for r in rep["layers"]
                ],
            }
        )
    return points


def shard_backend_smoke(meshes=((1, 1), (2, 2))) -> dict:
    """Numeric backend smoke: execute the same sharded matmul through the
    sequential and shard_map backends and compare.

    Meant to run with forced host devices (``tools/ci_check.py`` launches it
    in a subprocess with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
    via ``python -m benchmarks.fabric_sweep --backend-smoke``); on a
    single-device host the shard_map points simply resolve to sequential and
    are reported as such.
    """
    import jax
    import numpy as np

    from repro.core.cim_linear import CiMConfig
    from repro.fabric import (
        ChipMeshConfig,
        FabricConfig,
        execute_matmul,
        execute_sharded_matmul,
        map_matmul,
        resolve_backend,
        shard_placement,
    )

    fb = FabricConfig(mode="pair_sar", rows=16, cols=32, n_arrays=8)
    noisy = CiMConfig(
        mode="bitplane", a_bits=4, w_bits=4, adc_bits=5, rows=16, ste=False,
        comparator_sigma=0.05,
    )
    key = jax.random.PRNGKey(0)
    nk = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (4, 64))
    w = jax.random.normal(jax.random.fold_in(key, 1), (64, 48))

    out = {"devices": len(jax.devices()), "points": []}
    for data, model in meshes:
        cm = ChipMeshConfig(data=data, model=model, fabric=fb)
        sp = shard_placement(map_matmul("matmul", 4, 64, 48, fb), cm)
        try:  # auto keeps 1x1 sequential; probe explicit shard_map eligibility
            resolve_backend(sp, "shard_map")
            shard_map_available = True
        except ValueError:
            shard_map_available = False
        t0 = time.perf_counter()
        y_seq = np.asarray(
            execute_sharded_matmul(x, w, cm, noisy, sharded=sp, key=nk,
                                   backend="sequential")
        )
        t_seq = time.perf_counter() - t0
        rec = {
            "mesh": f"{data}x{model}",
            "backend_auto": resolve_backend(sp, "auto"),
            "shard_map_available": shard_map_available,
            "sequential_s": t_seq,
            "crosschip_bits_per_pass": sp.crosschip_bits_per_pass,
        }
        if shard_map_available:
            t0 = time.perf_counter()
            y_sm = np.asarray(
                execute_sharded_matmul(x, w, cm, noisy, sharded=sp, key=nk,
                                       backend="shard_map")
            )
            rec["shard_map_s"] = time.perf_counter() - t0
            rec["max_abs_diff_vs_sequential"] = float(np.abs(y_sm - y_seq).max())
            if (data, model) == (1, 1):
                y_ref = np.asarray(execute_matmul(x, w, fb, noisy, key=nk))
                rec["bit_exact_1x1_vs_execute"] = bool((y_sm == y_ref).all())
        out["points"].append(rec)
    return out


def program_smoke(mesh=(2, 2)) -> dict:
    """Fused whole-model forward smoke (``repro.fabric.program``): compile a
    small 3-layer chain, check 1x1 bit-exactness (noisy ADC included) and
    multi-chip agreement vs the per-layer ``execute_sharded_matmul`` loop,
    count the fused program's collectives, and record the measured-vs-modeled
    link-latency ratio. Meant for forced host devices
    (``python -m benchmarks.fabric_sweep --program-smoke`` inside
    ``tools/ci_check.py``'s 8-device subprocess -> ``BENCH_fabric_program.json``).
    """
    import jax
    import numpy as np

    from repro.core.cim_linear import CiMConfig
    from repro.fabric import (
        ChipMeshConfig,
        FabricConfig,
        compile_forward,
        map_matmul,
        measure_forward,
        per_layer_forward,
        shard_placement,
    )

    fb = FabricConfig(mode="pair_sar", rows=16, cols=32, n_arrays=8)
    noisy = CiMConfig(
        mode="bitplane", a_bits=4, w_bits=4, adc_bits=5, rows=16, ste=False,
        comparator_sigma=0.05,
    )
    shapes = [("l0", 4, 64, 64), ("l1", 4, 64, 96), ("l2", 4, 96, 32)]

    def chain(cm):
        return [
            shard_placement(map_matmul(n, m, k, nn, fb, cim=noisy), cm)
            for n, m, k, nn in shapes
        ]

    nk = jax.random.PRNGKey(7)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
    out = {"devices": len(jax.devices()), "mesh": f"{mesh[0]}x{mesh[1]}"}

    # 1x1: the fused program must be bit-for-bit the per-layer loop
    cm1 = ChipMeshConfig(fabric=fb)
    prog1 = compile_forward(chain(cm1), cm1, noisy)
    ws = prog1.random_weights(jax.random.PRNGKey(1))
    y1 = np.asarray(prog1(x, ws, key=nk))
    y1_ref = np.asarray(
        per_layer_forward(x, ws, prog1.placements, cm1, noisy, key=nk,
                          backend="sequential")
    )
    out["backend_1x1"] = prog1.backend
    out["bit_exact_1x1"] = bool((y1 == y1_ref).all())

    # multi-chip: float agreement + collective census + measured timings
    cmn = ChipMeshConfig(data=mesh[0], model=mesh[1], fabric=fb)
    prog = compile_forward(chain(cmn), cmn, noisy)
    out["backend"] = prog.backend
    out["problems"] = prog.problems
    y = np.asarray(prog(x, ws, key=nk))
    y_ref = np.asarray(
        per_layer_forward(x, ws, prog.placements, cmn, noisy, key=nk,
                          backend="sequential")
    )
    out["max_abs_diff_vs_per_layer"] = float(np.abs(y - y_ref).max())
    if prog.backend == "shard_map":
        out["collectives"] = prog.collective_counts(x, ws, key=nk)
    out["measure"] = measure_forward(
        prog, x=x, weights=ws, key=nk, iters=2,
        per_layer_backend="sequential", per_layer_iters=1,
    )
    out["measured_over_modeled"] = out["measure"]["measured_over_modeled"]
    out["link_clock_calibration"] = out["measure"]["link_clock_calibration"]
    # a second measure on warm jit caches: tools/ci_check.py gates that the
    # calibration constant is stable across runs, never its magnitude
    # (per_layer=False — the stability run only needs the fused twins)
    m2 = measure_forward(prog, x=x, weights=ws, key=nk, iters=2, per_layer=False)
    out["link_clock_calibration_runs"] = [
        out["measure"]["link_clock_calibration"],
        m2["link_clock_calibration"],
    ]
    return out


def graph_smoke(mesh=(2, 2)) -> dict:
    """Full-transformer-block fused GRAPH smoke (``repro.fabric.graph``):
    run REAL ``init_transformer`` weights through the fused graph forward —
    siblings, attention mixing, norms, residuals — checking 1x1
    bit-exactness vs the per-node reference (noisy ADC included),
    multi-chip agreement, and the collective census against the documented
    budget (per-sibling scatters enumerated, ONE trailing all-gather).
    Meant for forced host devices
    (``python -m benchmarks.fabric_sweep --graph-smoke`` inside
    ``tools/ci_check.py``'s 8-device subprocess -> ``BENCH_fabric_graph.json``).
    """
    import jax
    import numpy as np

    from repro.configs.base import ModelConfig
    from repro.core.cim_linear import CiMConfig
    from repro.fabric import (
        ChipMeshConfig,
        FabricConfig,
        compile_graph_forward,
        measure_forward,
        transformer_graph_weights,
    )
    from repro.models.transformer import init_transformer

    # graph-eligible on a 2x2 mesh: every K tile-aligns (64/128 % 32 == 0)
    # and q/kv heads (4/2) divide the model axis. ONE block keeps the smoke
    # inside the CI budget; the >=2-block acceptance lives in tier-1
    # (tests/test_fabric_graph.py)
    cfg = ModelConfig(
        name="graph-smoke", family="dense", n_layers=1, d_model=64, vocab=64,
        n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, pad_vocab_multiple=16,
        param_dtype="float32", compute_dtype="float32",
    )
    fb = FabricConfig(mode="pair_sar", rows=16, cols=32, n_arrays=8)
    noisy = CiMConfig(
        mode="bitplane", a_bits=4, w_bits=4, adc_bits=5, rows=16, ste=False,
        comparator_sigma=0.05,
    )
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    ws = transformer_graph_weights(params, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, cfg.d_model))
    nk = jax.random.PRNGKey(7)
    out = {"devices": len(jax.devices()), "mesh": f"{mesh[0]}x{mesh[1]}"}

    # 1x1: the fused graph must be bit-for-bit the per-node reference
    cm1 = ChipMeshConfig(fabric=fb)
    prog1 = compile_graph_forward(cfg, cm1, noisy, tokens=8)
    out["n_nodes"] = len(prog1.graph.nodes)
    out["n_matmuls"] = len(prog1.placements)
    out["backend_1x1"] = prog1.backend
    y1 = np.asarray(prog1(x, ws, key=nk))
    y1_ref = np.asarray(prog1.reference_forward(x, ws, key=nk))
    out["bit_exact_1x1"] = bool((y1 == y1_ref).all())

    # multi-chip: float agreement + census-vs-budget + measured timings
    cmn = ChipMeshConfig(data=mesh[0], model=mesh[1], fabric=fb)
    prog = compile_graph_forward(cfg, cmn, noisy, tokens=8)
    out["backend"] = prog.backend
    out["problems"] = prog.problems
    y = np.asarray(prog(x, ws, key=nk))
    y_ref = np.asarray(prog.reference_forward(x, ws, key=nk))
    out["max_abs_diff_vs_reference"] = float(np.abs(y - y_ref).max())
    if prog.backend == "shard_map":
        out["collectives"] = prog.collective_counts(key=nk)
        out["collective_budget"] = prog.collective_budget()
        out["budget_match"] = out["collectives"] == out["collective_budget"]
    out["measure"] = measure_forward(
        prog, x=x, weights=ws, key=nk, iters=1,
        per_layer_backend="sequential", per_layer_iters=1,
    )
    out["measured_over_modeled"] = out["measure"]["measured_over_modeled"]
    out["link_clock_calibration"] = out["measure"]["link_clock_calibration"]
    # second warm measure for the CI stability-across-runs gate (fused
    # twins only — the per-node reference is the expensive part)
    m2 = measure_forward(prog, x=x, weights=ws, key=nk, iters=1, per_layer=False)
    out["link_clock_calibration_runs"] = [
        out["measure"]["link_clock_calibration"],
        m2["link_clock_calibration"],
    ]
    return out


def scan_smoke(depth: int = 8, mesh=(2, 2)) -> dict:
    """Scan-over-layers smoke (``compile_graph_forward(scan_layers=True)``):
    at ``depth`` transformer blocks, AOT trace+compile the unrolled and the
    scanned 1x1 programs (``fn.lower(...).compile()`` isolates exactly the
    cost the scan collapses), run BOTH compiled executables on the same
    noisy-ADC inputs and check bit-exactness, then check the scanned
    program's collective census on the forced mesh against the documented
    budget AND the per-block census × ``n_blocks`` + tail decomposition.
    Meant for forced host devices
    (``python -m benchmarks.fabric_sweep --scan-smoke`` inside
    ``tools/ci_check.py``'s 8-device subprocess -> ``BENCH_fabric_scan.json``).
    """
    import jax
    import numpy as np

    from repro.configs.base import ModelConfig
    from repro.core.cim_linear import CiMConfig
    from repro.fabric import ChipMeshConfig, FabricConfig, compile_graph_forward

    cfg = ModelConfig(
        name="scan-smoke", family="dense", n_layers=depth, d_model=64, vocab=64,
        n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, pad_vocab_multiple=16,
        param_dtype="float32", compute_dtype="float32",
    )
    fb = FabricConfig(mode="pair_sar", rows=16, cols=32, n_arrays=8)
    noisy = CiMConfig(
        mode="bitplane", a_bits=4, w_bits=4, adc_bits=5, rows=16, ste=False,
        comparator_sigma=0.05,
    )
    out = {
        "devices": len(jax.devices()), "n_layers": depth,
        "mesh": f"{mesh[0]}x{mesh[1]}",
    }
    cm1 = ChipMeshConfig(fabric=fb)
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 4, cfg.d_model))
    compiled = {}
    for tag, scan in (("unrolled", False), ("scanned", True)):
        prog = compile_graph_forward(cfg, cm1, noisy, tokens=4, scan_layers=scan)
        # random_weights stacks the SAME per-layer draws for the scanned
        # form, so one key yields corresponding weights in both programs
        args = prog._fused_args(x, prog.random_weights(jax.random.PRNGKey(3)), key)
        t0 = time.perf_counter()
        exe = prog._fused(True).lower(*args).compile()
        out[f"{tag}_compile_s"] = time.perf_counter() - t0
        compiled[tag] = (exe, args)
    out["compile_speedup"] = out["unrolled_compile_s"] / out["scanned_compile_s"]
    y_un = np.asarray(compiled["unrolled"][0](*compiled["unrolled"][1])[0])
    y_sc = np.asarray(compiled["scanned"][0](*compiled["scanned"][1])[0])
    out["bit_exact_1x1"] = bool((y_un == y_sc).all())
    out["max_abs_diff_1x1"] = float(np.abs(y_un - y_sc).max())

    # census on the forced mesh is trace-only (jax.make_jaxpr, no XLA
    # compile) — cheap at any depth, which is itself part of the point
    cmn = ChipMeshConfig(data=mesh[0], model=mesh[1], fabric=fb)
    sc = compile_graph_forward(cfg, cmn, noisy, tokens=8, scan_layers=True)
    out["backend"] = sc.backend
    out["problems"] = sc.problems
    if sc.backend == "shard_map":
        counts = sc.collective_counts(key=key)
        budget = sc.collective_budget()
        blk = sc.block_graph.block_census(cmn.model)
        tail = sc.tail_graph.collective_budget(cmn.model)
        out["collectives"] = counts
        out["collective_budget"] = budget
        out["block_census_x_layers"] = {
            k: blk[k] * sc.n_blocks + tail[k] for k in blk
        }
        out["budget_match"] = (
            counts == budget == out["block_census_x_layers"]
        )
    return out


def obs_smoke(mesh=(2, 2)) -> dict:
    """Observability smoke (``repro.obs``): run the fused 3-layer chain under
    an active metrics registry + JSONL tracer and report everything the CI
    gate needs — the required metric names, the fallback counter staying 0 on
    an aligned batch and reaching exactly 1 (reason ``ragged_batch``) on a
    ragged batch, a parse-clean JSONL trace log, and bit-identical fused
    outputs with observability on vs off. Meant for forced host devices
    (``python -m benchmarks.fabric_sweep --obs-smoke`` inside
    ``tools/ci_check.py``'s 8-device subprocess -> ``BENCH_obs.json``).
    """
    import os
    import tempfile

    import jax
    import numpy as np

    from repro import obs
    from repro.core.cim_linear import CiMConfig
    from repro.fabric import (
        ChipMeshConfig,
        FabricConfig,
        compile_forward,
        map_matmul,
        shard_placement,
    )

    fb = FabricConfig(mode="pair_sar", rows=16, cols=32, n_arrays=8)
    noisy = CiMConfig(
        mode="bitplane", a_bits=4, w_bits=4, adc_bits=5, rows=16, ste=False,
        comparator_sigma=0.05,
    )
    shapes = [("l0", 4, 64, 64), ("l1", 4, 64, 96), ("l2", 4, 96, 32)]
    cmn = ChipMeshConfig(data=mesh[0], model=mesh[1], fabric=fb)
    chain = [
        shard_placement(map_matmul(n, m, k, nn, fb, cim=noisy), cmn)
        for n, m, k, nn in shapes
    ]
    prog = compile_forward(chain, cmn, noisy)
    ws = prog.random_weights(jax.random.PRNGKey(1))
    nk = jax.random.PRNGKey(7)
    x_aligned = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
    x_ragged = x_aligned[:3]  # 3 rows % data axis 2 != 0 -> documented fallback

    out = {
        "devices": len(jax.devices()),
        "mesh": f"{mesh[0]}x{mesh[1]}",
        "backend": prog.backend,
    }

    # baseline with observability OFF — the neutrality reference
    y_off = np.asarray(prog(x_aligned, ws, key=nk))

    fd, jsonl_path = tempfile.mkstemp(suffix=".jsonl")
    os.close(fd)
    os.unlink(jsonl_path)  # JsonlSink lazily (re)creates it
    try:
        with obs.tracing(jsonl=jsonl_path), obs.collecting() as reg:
            y_on = np.asarray(prog(x_aligned, ws, key=nk))
            out["fallbacks_aligned"] = obs.get_value(
                "fabric_fallback_total", reason=obs.REASON_RAGGED_BATCH
            )
            _ = np.asarray(prog(x_ragged, ws, key=nk))
            out["fallbacks_ragged"] = obs.get_value(
                "fabric_fallback_total", reason=obs.REASON_RAGGED_BATCH
            )
            out["fused_requests"] = obs.get_value(
                "fabric_requests_total", path="fused"
            )
            out["fallback_requests"] = obs.get_value(
                "fabric_requests_total", path="fallback"
            )
            out["conversions_total"] = obs.get_value("fabric_conversions_total")
            out["link_bits_total"] = obs.get_value("fabric_link_bits_total")
            out["metric_names"] = reg.names()
            out["prometheus_lines"] = len(reg.prometheus_text().splitlines())
        out["bit_identical_with_obs"] = bool((y_on == y_off).all())
        records = obs.read_jsonl(jsonl_path)  # raises on any unparseable line
        out["jsonl_records"] = len(records)
        out["jsonl_names"] = sorted({r["name"] for r in records})
    finally:
        if os.path.exists(jsonl_path):
            os.unlink(jsonl_path)
    return out


def autotune_smoke(mesh=(2, 2)) -> dict:
    """Continuous-batching smoke (``repro.fabric.autotune``): serve a
    mixed-length ragged request trace through the bucketed fused-program
    cache and check (a) the padded fused result is bit-exact to the
    unpadded per-node reference after slicing (noiseless AND noisy ADC —
    per-row noise keys make pad rows draw-invisible), (b) the measured
    trace wall-clock beats the per-node fallback loop, (c) the autotuner's
    cost-model plan is never costlier than the default mesh with one
    max-batch bucket. Meant for forced host devices
    (``python -m benchmarks.fabric_sweep --autotune-smoke`` inside
    ``tools/ci_check.py``'s 8-device subprocess ->
    ``BENCH_fabric_autotune.json``).
    """
    import dataclasses

    import jax
    import numpy as np

    from repro.configs.base import ModelConfig
    from repro.core.cim_linear import CiMConfig
    from repro.fabric import (
        BucketedGraphCache,
        ChipMeshConfig,
        FabricConfig,
        autotune_plan,
        autotune_section,
        request_histogram,
        transformer_graph_weights,
    )
    from repro.models.transformer import init_transformer

    # the graph-smoke config: 2x2-eligible (K tile-aligned, GQA heads 4/2)
    cfg = ModelConfig(
        name="autotune-smoke", family="dense", n_layers=1, d_model=64,
        vocab=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
        pad_vocab_multiple=16, param_dtype="float32", compute_dtype="float32",
    )
    fb = FabricConfig(mode="pair_sar", rows=16, cols=32, n_arrays=8)
    cim = CiMConfig(
        mode="bitplane", a_bits=4, w_bits=4, adc_bits=5, rows=16, ste=False
    )
    noisy = dataclasses.replace(cim, comparator_sigma=0.05)
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    ws = transformer_graph_weights(params, cfg)
    cm = ChipMeshConfig(data=mesh[0], model=mesh[1], fabric=fb)
    seq = 4
    out = {"devices": len(jax.devices()), "mesh": f"{mesh[0]}x{mesh[1]}"}

    # ragged batch on the bucketed fused path: B=3 pads to the 4-bucket
    cache = BucketedGraphCache(cfg, cm, cim, buckets=(4,), seq=seq)
    xs = {
        b: jax.random.normal(jax.random.PRNGKey(b), (b, seq, cfg.d_model))
        for b in (1, 2, 3)
    }
    prog = cache.program_for(4)
    out["backend"] = prog.backend
    y = np.asarray(cache(xs[3], ws))
    y_ref = np.asarray(prog.reference_forward(xs[3], ws))
    out["bit_exact_ragged"] = bool((y == y_ref).all())

    # noisy ADC: pad rows must not consume noise-key draws
    nk = jax.random.PRNGKey(7)
    cache_n = BucketedGraphCache(cfg, cm, noisy, buckets=(4,), seq=seq)
    yn = np.asarray(cache_n(xs[3], ws, key=nk))
    yn_ref = np.asarray(
        cache_n.program_for(4, noisy=True).reference_forward(xs[3], ws, key=nk)
    )
    out["bit_exact_ragged_noisy"] = bool((yn == yn_ref).all())

    # mixed-length trace: bucketed fused serving vs the per-node fallback
    # loop every ragged batch used to take (warm both paths first)
    trace = [3, 1, 2, 3]
    for b in set(trace):
        jax.block_until_ready(cache(xs[b], ws))
        jax.block_until_ready(prog.reference_forward(xs[b], ws))
    t0 = time.perf_counter()
    for b in trace:
        jax.block_until_ready(cache(xs[b], ws))
    out["fused_trace_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    for b in trace:
        jax.block_until_ready(prog.reference_forward(xs[b], ws))
    out["pernode_trace_s"] = time.perf_counter() - t0
    out["ragged_mix_speedup"] = out["pernode_trace_s"] / max(
        out["fused_trace_s"], 1e-9
    )
    out["cache"] = cache.stats()

    # the autotuner's plan must never cost more than the default mesh with
    # a single max-batch bucket (the baseline is in its search space)
    plan = autotune_plan(
        cfg, request_histogram(trace), cm.n_chips, fb, seq=seq, cim=cim,
        default_mesh=mesh,
    )
    out["plan"] = autotune_section(plan)
    out["plan_cost_le_default"] = (
        plan.expected_latency_s <= plan.baseline_latency_s
    )
    return out


def fabric_mapping_smoke() -> dict:
    """Map a smollm block on a hybrid fabric — the perf-trajectory anchor."""
    from repro.configs.registry import get_config
    from repro.fabric.mapper import map_model
    from repro.fabric.report import fabric_report
    from repro.fabric.topology import FabricConfig

    fb = FabricConfig(mode="hybrid", n_arrays=252)
    t0 = time.perf_counter()
    placements = map_model(get_config("smollm-135m"), fb, tokens=4, block_only=True)
    report = fabric_report(placements, fb)
    wall = time.perf_counter() - t0
    return {
        "map_report_s": wall,
        "tiles": report["totals"]["tiles"],
        "conversions": report["totals"]["conversions"],
        "latency_s": report["totals"]["latency_s"],
        "adc_area_ratio_vs_sar": report["paper_ratios"]["adc_area_ratio_vs_sar"],
        "adc_area_ratio_vs_flash": report["paper_ratios"]["adc_area_ratio_vs_flash"],
        "iso_area_throughput_ratio": report["iso_area"]["throughput_ratio"],
    }


def fabric_bench() -> list[tuple]:
    """benchmarks/run.py rows: name, us_per_call, derived."""
    rows = []
    t0 = time.perf_counter()
    points = sweep_points()
    us = (time.perf_counter() - t0) / max(len(points), 1) * 1e6
    for p in points:
        rows.append(
            (
                f"fabric/{p['mode']}_b{p['adc_bits']}_a{p['n_arrays']}",
                us,
                f"conv_per_cyc={p['conversions_per_cycle']:.2f};"
                f"per_mm2={p['throughput_per_mm2']:.1f};"
                f"iso_ratio={p['iso_area_throughput_ratio']:.2f}",
            )
        )
    smoke = fabric_mapping_smoke()
    rows.append(
        (
            "fabric/map_smollm_block_hybrid252",
            smoke["map_report_s"] * 1e6,
            f"tiles={smoke['tiles']};iso_ratio={smoke['iso_area_throughput_ratio']:.2f}",
        )
    )
    for p in shard_sweep_points():
        rows.append(
            (
                f"fabric/shard_smollm_block_{p['mesh']}",
                p["map_report_s"] * 1e6,
                f"chips={p['n_chips']};onchip_ema={p['onchip_ema_bits_per_pass']:.3g};"
                f"xchip={p['crosschip_bits_per_pass']:.3g};"
                f"resident={int(p['model_resident'])}",
            )
        )
    return rows


def autotune_bench() -> list[tuple]:
    """benchmarks/run.py rows for the continuous-batching autotune smoke.

    Runs at 1x1 so it works without forced host devices; the 8-device
    gated version lives in ``tools/ci_check.py`` (``run_autotune_smoke``
    -> ``BENCH_fabric_autotune.json``).
    """
    s = autotune_smoke(mesh=(1, 1))
    return [
        (
            "fabric-autotune/ragged_trace_1x1",
            s["fused_trace_s"] * 1e6,
            f"speedup={s['ragged_mix_speedup']:.1f};"
            f"bit_exact={int(s['bit_exact_ragged'] and s['bit_exact_ragged_noisy'])};"
            f"plan={s['plan']['mesh']}/{'-'.join(map(str, s['plan']['buckets']))};"
            f"hits={s['cache']['hits']}",
        )
    ]


def _smoke_row(name: str, out: dict, wall_s: float) -> tuple:
    """Summarise a smoke dict as a CSV row: first few scalar metrics."""
    keys = [
        k for k, v in out.items()
        if isinstance(v, (int, float)) and not isinstance(v, bool)
    ][:3]
    derived = ";".join(f"{k}={out[k]:.4g}" for k in keys) or "ok"
    return (f"fabric-smokes/{name}", wall_s * 1e6, derived)


def smoke_bench() -> list[tuple]:
    """benchmarks/run.py rows mirroring every other ``BENCH_*.json`` device
    smoke of ``tools/ci_check.py``, run at 1x1 so they work without forced
    host devices. Keeps each CI trajectory file discoverable from the bench
    harness (``benchmarks/run.py`` asserts the mapping is total)."""
    rows = []
    for name, thunk in (
        ("shard", lambda: shard_backend_smoke(meshes=((1, 1),))),
        ("program", lambda: program_smoke(mesh=(1, 1))),
        ("graph", lambda: graph_smoke(mesh=(1, 1))),
        ("scan", lambda: scan_smoke(mesh=(1, 1))),
        ("obs", lambda: obs_smoke(mesh=(1, 1))),
    ):
        t0 = time.perf_counter()
        out = thunk()
        rows.append(_smoke_row(name, out, time.perf_counter() - t0))
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_fabric.json")
    ap.add_argument(
        "--backend-smoke",
        action="store_true",
        help="print the shard_backend_smoke() JSON to stdout and exit "
        "(tools/ci_check.py runs this in a forced-8-device subprocess)",
    )
    ap.add_argument(
        "--program-smoke",
        action="store_true",
        help="print the program_smoke() JSON (fused whole-model forward vs "
        "per-layer loop + measured/modeled link latency) to stdout and exit "
        "(tools/ci_check.py runs this in a forced-8-device subprocess)",
    )
    ap.add_argument(
        "--graph-smoke",
        action="store_true",
        help="print the graph_smoke() JSON (full-transformer-block fused "
        "graph with real init_transformer weights vs the per-node reference "
        "+ collective census vs budget) to stdout and exit "
        "(tools/ci_check.py runs this in a forced-8-device subprocess)",
    )
    ap.add_argument(
        "--scan-smoke",
        action="store_true",
        help="print the scan_smoke() JSON (scan-over-layers vs unrolled "
        "graph compile wall-clock at n_layers=8, bit-exact noisy forward, "
        "census == per-block x n_layers + tail) to stdout and exit "
        "(tools/ci_check.py runs this in a forced-8-device subprocess)",
    )
    ap.add_argument(
        "--autotune-smoke",
        action="store_true",
        help="print the autotune_smoke() JSON (ragged mixed-length trace "
        "through the bucketed fused-program cache: bit-exact after "
        "pad-slicing, measured speedup vs the per-node loop, autotuner "
        "plan cost vs the default mesh) to stdout and exit "
        "(tools/ci_check.py runs this in a forced-8-device subprocess)",
    )
    ap.add_argument(
        "--obs-smoke",
        action="store_true",
        help="print the obs_smoke() JSON (repro.obs metric names, fallback "
        "counter semantics, JSONL parse check, obs-on/off bit-identity) to "
        "stdout and exit "
        "(tools/ci_check.py runs this in a forced-8-device subprocess)",
    )
    args = ap.parse_args()
    if args.backend_smoke:
        print(json.dumps(shard_backend_smoke(), indent=2, default=float))
        return
    if args.program_smoke:
        print(json.dumps(program_smoke(), indent=2, default=float))
        return
    if args.graph_smoke:
        print(json.dumps(graph_smoke(), indent=2, default=float))
        return
    if args.scan_smoke:
        print(json.dumps(scan_smoke(), indent=2, default=float))
        return
    if args.autotune_smoke:
        print(json.dumps(autotune_smoke(), indent=2, default=float))
        return
    if args.obs_smoke:
        print(json.dumps(obs_smoke(), indent=2, default=float))
        return
    t0 = time.perf_counter()
    # shard-sweep data is written by tools/ci_check.py to BENCH_fabric_shard.json
    # (single source of truth); here it only feeds the run.py bench rows
    payload = {"sweep": sweep_points(), "smoke": fabric_mapping_smoke()}
    payload["wall_s"] = time.perf_counter() - t0
    Path(args.out).write_text(json.dumps(payload, indent=2, default=float))
    print(f"[fabric_sweep] {len(payload['sweep'])} design points -> {args.out} "
          f"({payload['wall_s']:.1f}s)")


if __name__ == "__main__":
    main()
