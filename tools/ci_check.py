"""CI gate: tier-1 tests + <30 s fabric smoke benchmarks + docs checks.

Runs the repo's tier-1 suite (ROADMAP.md), the fabric design-space sweep
(``BENCH_fabric.json``), the multi-chip shard smoke — a local 1x1-mesh
bit-exactness check, the 1/4/16-chip mesh sweep, and the shard_map
execution backend run under forced 8 host devices (subprocess; separate
``shard_map_smoke`` key), written to ``BENCH_fabric_shard.json`` — the
fused whole-model forward smoke (``repro.fabric.program`` under forced 8
host devices: bit-exact vs the per-layer loop, at most one all-gather,
measured/modeled link-latency ratio -> ``BENCH_fabric_program.json``) — the
full-transformer-block fused GRAPH smoke (``repro.fabric.graph`` under
forced 8 host devices: real ``init_transformer`` weights bit-exact vs the
per-node reference on 1x1, collective census == documented budget ->
``BENCH_fabric_graph.json``) — the scan-over-layers gate
(``compile_graph_forward(scan_layers=True)`` at n_layers=8: scanned
trace+compile strictly below unrolled, bit-exact noisy logits, census ==
per-block × n_layers + tail -> ``BENCH_fabric_scan.json``) — the
observability smoke (``repro.obs``
under forced 8 host devices: required metric names present, the fallback
counter 0 on an aligned fused batch and exactly 1 ``ragged_batch`` on a
ragged one, the JSONL trace log parse-clean, fused outputs bit-identical
with observability on vs off -> ``BENCH_obs.json``) — the continuous-
batching smoke (``repro.fabric.autotune`` under forced 8 host devices:
ragged batches served via the bucketed fused-program cache bit-exact after
pad-slicing, noisy ADC included, measured ragged-mix speedup > 5x over the
per-node loop, autotuner plan cost <= the default mesh's ->
``BENCH_fabric_autotune.json``) — the calibration
stability gate (``link_clock_calibration`` agrees across back-to-back runs
in the program/graph smokes; its magnitude is host-dependent and never
gated) — the public-api gate (every submodule ``__all__`` symbol
re-exported from ``repro.fabric.__all__`` / ``repro.obs.__all__``) — and
the docs gate: ``README.md``,
``docs/fabric.md``, and ``docs/observability.md`` must exist, every dotted
``repro.*`` reference in them must import, every ``repro.fabric`` public
symbol must be documented in ``docs/fabric.md``, and every ``repro.obs``
public symbol in ``docs/observability.md``. Exits non-zero if any stage
fails or a smoke benchmark blows its time budget.

Tier-1 additionally enforces a passed-test-count floor
(``TIER1_MIN_PASSED``) so suites cannot silently shrink.

  python tools/ci_check.py [--skip-tests] [--out BENCH_fabric.json]
                           [--shard-out BENCH_fabric_shard.json]
                           [--program-out BENCH_fabric_program.json]
                           [--graph-out BENCH_fabric_graph.json]
                           [--scan-out BENCH_fabric_scan.json]
                           [--obs-out BENCH_obs.json]
                           [--autotune-out BENCH_fabric_autotune.json]
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import re
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
SMOKE_BUDGET_S = 30.0
# tier-1 test-count floor: suites can grow but cannot silently shrink (a
# collection error or an importorskip'd-away file drops dozens at once)
TIER1_MIN_PASSED = 315


def _child_env(*paths: Path) -> dict:
    """Environment of a child process: ``paths`` prepended to PYTHONPATH and
    JAX pinned to the CPU. This parent imports JAX itself; on a TPU host it
    holds the chip, and a child that reached for it would fail or hang."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(p) for p in paths] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    env["JAX_PLATFORMS"] = "cpu"
    return env


def run_tier1() -> bool:
    env = _child_env(REPO / "src")
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q"], cwd=REPO, env=env,
        capture_output=True, text=True,
    )
    tail = proc.stdout.strip().splitlines()
    if tail:
        print(tail[-1])
    if proc.returncode != 0:
        print(proc.stdout[-4000:])
        print(proc.stderr[-2000:])
        return False
    m = re.search(r"(\d+) passed", proc.stdout)
    passed = int(m.group(1)) if m else 0
    if passed < TIER1_MIN_PASSED:
        print(f"[ci_check] FAIL: tier-1 passed only {passed} tests "
              f"< the {TIER1_MIN_PASSED} floor — did a suite stop collecting?")
        return False
    return True


def run_fabric_smoke(out: Path) -> bool:
    sys.path.insert(0, str(REPO / "src"))
    sys.path.insert(0, str(REPO))
    from benchmarks.fabric_sweep import fabric_mapping_smoke, sweep_points

    t0 = time.perf_counter()
    # same payload schema as `python -m benchmarks.fabric_sweep` (both write
    # this tracked file); shard data lives ONLY in BENCH_fabric_shard.json
    payload = {"sweep": sweep_points(), "smoke": fabric_mapping_smoke()}
    wall = time.perf_counter() - t0
    payload["wall_s"] = wall
    out.write_text(json.dumps(payload, indent=2, default=float))
    print(f"[ci_check] fabric smoke: {len(payload['sweep'])} points in "
          f"{wall:.1f}s -> {out}")
    if wall > SMOKE_BUDGET_S:
        print(f"[ci_check] FAIL: smoke took {wall:.1f}s > {SMOKE_BUDGET_S}s budget")
        return False
    ratios = [p["iso_area_throughput_ratio"] for p in payload["sweep"]
              if p["mode"] in ("pair_sar", "hybrid")]
    if not all(r >= 1.0 for r in ratios):
        print(f"[ci_check] FAIL: iso-area throughput regression: {ratios}")
        return False
    return True


def _run_forced_device_smoke(flag: str) -> dict:
    """Run a benchmarks.fabric_sweep smoke under forced 8 host devices
    (subprocess: jax pins the device count at first init, so the in-process
    smokes above cannot change it)."""
    env = _child_env(REPO / "src", REPO)
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.fabric_sweep", flag],
        cwd=REPO, env=env, capture_output=True, text=True,
    )
    if proc.returncode != 0:
        return {"error": f"rc={proc.returncode}: {proc.stderr[-2000:]}"}
    try:
        return json.loads(proc.stdout)
    except json.JSONDecodeError:
        return {"error": f"unparseable output: {proc.stdout[-2000:]}"}


def run_backend_smoke() -> dict:
    return _run_forced_device_smoke("--backend-smoke")


def run_shard_smoke(out: Path) -> bool:
    """Multi-chip smoke: 1x1-mesh bit-exactness, the 1/4/16-chip sweep, and
    the shard_map execution backend under forced 8 host devices (recorded
    under its own ``shard_map_smoke`` key so the sequential trajectory in
    ``shard_sweep`` stays comparable across PRs)."""
    sys.path.insert(0, str(REPO / "src"))
    sys.path.insert(0, str(REPO))
    import jax
    import numpy as np

    from benchmarks.fabric_sweep import shard_sweep_points
    from repro.core.cim_linear import CiMConfig
    from repro.fabric import (
        ChipMeshConfig,
        FabricConfig,
        execute_matmul,
        execute_sharded_matmul,
    )

    t0 = time.perf_counter()
    fb = FabricConfig(mode="hybrid", rows=16, cols=32, n_arrays=12)
    cim = CiMConfig(mode="bitplane", a_bits=4, w_bits=4, adc_bits=5, rows=16, ste=False)
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (4, 64))
    w = jax.random.normal(jax.random.fold_in(key, 1), (64, 48))
    y_shard = execute_sharded_matmul(x, w, ChipMeshConfig(fabric=fb), cim)
    y_ref = execute_matmul(x, w, fb, cim)
    bit_exact = bool((np.asarray(y_shard) == np.asarray(y_ref)).all())

    payload = {"bit_exact_1x1": bit_exact, "shard_sweep": shard_sweep_points()}
    wall = time.perf_counter() - t0
    # the backend smoke is a fresh-jax-init subprocess: budgeted separately
    # so the in-process smoke budget keeps meaning across PRs
    t0_b = time.perf_counter()
    payload["shard_map_smoke"] = run_backend_smoke()
    backend_wall = time.perf_counter() - t0_b
    payload["shard_map_smoke"]["wall_s"] = backend_wall
    payload["wall_s"] = wall
    out.write_text(json.dumps(payload, indent=2, default=float))
    print(f"[ci_check] shard smoke: {len(payload['shard_sweep'])} mesh points in "
          f"{wall:.1f}s (+{backend_wall:.1f}s backend subprocess) -> {out}")
    if not bit_exact:
        print("[ci_check] FAIL: 1x1-mesh sharded execution is not bit-exact")
        return False
    if wall > SMOKE_BUDGET_S:
        print(f"[ci_check] FAIL: shard smoke took {wall:.1f}s > {SMOKE_BUDGET_S}s budget")
        return False
    if backend_wall > 2 * SMOKE_BUDGET_S:
        print(f"[ci_check] FAIL: backend smoke took {backend_wall:.1f}s > "
              f"{2 * SMOKE_BUDGET_S}s budget")
        return False
    xchip = {p["n_chips"]: p["crosschip_bits_per_pass"] for p in payload["shard_sweep"]}
    if xchip.get(1, 1) != 0:
        print(f"[ci_check] FAIL: single-chip mesh reports cross-chip traffic: {xchip}")
        return False
    if not all(bits > 0 for chips, bits in xchip.items() if chips > 1):
        print(f"[ci_check] FAIL: multi-chip mesh reports no reduce-scatter traffic: {xchip}")
        return False
    sm = payload["shard_map_smoke"]
    if "error" in sm:
        print(f"[ci_check] FAIL: shard_map backend smoke failed: {sm['error']}")
        return False
    by_mesh = {p["mesh"]: p for p in sm.get("points", [])}
    p11, p22 = by_mesh.get("1x1"), by_mesh.get("2x2")
    if not (p11 and p22):
        print(f"[ci_check] FAIL: shard_map smoke missing mesh points: {sorted(by_mesh)}")
        return False
    if not p11.get("shard_map_available") or not p11.get("bit_exact_1x1_vs_execute"):
        print(f"[ci_check] FAIL: 1x1 shard_map not bit-exact vs execute_matmul: {p11}")
        return False
    if p22.get("backend_auto") != "shard_map" or p22.get("max_abs_diff_vs_sequential", 1.0) > 1e-4:
        print(f"[ci_check] FAIL: 2x2 shard_map diverges from sequential: {p22}")
        return False
    print(
        f"[ci_check] shard_map backend smoke: {sm['devices']} devices, 1x1 bit-exact, "
        f"2x2 maxdiff {p22['max_abs_diff_vs_sequential']:.2e}"
    )
    return True


def run_program_smoke(out: Path) -> bool:
    """Whole-model fused-forward smoke (``repro.fabric.program``) under
    forced 8 host devices: the fused shard_map program must be bit-exact vs
    the per-layer loop on a 1x1 mesh (noisy ADC included), agree to float
    tolerance on the multi-chip mesh with at most ONE all-gather in the whole
    forward, and the measured/modeled link-latency ratio is recorded to
    ``BENCH_fabric_program.json`` for cross-PR tracking."""
    t0 = time.perf_counter()
    payload = _run_forced_device_smoke("--program-smoke")
    wall = time.perf_counter() - t0
    payload["wall_s"] = wall
    out.write_text(json.dumps(payload, indent=2, default=float))
    if "error" in payload:
        print(f"[ci_check] FAIL: fused program smoke failed: {payload['error']}")
        return False
    ratio = payload.get("measured_over_modeled")
    print(
        f"[ci_check] fused program smoke: {payload['devices']} devices, "
        f"mesh {payload['mesh']}, measured/modeled link ratio "
        f"{'n/a' if ratio is None else f'{ratio:.3g}'} in {wall:.1f}s -> {out}"
    )
    if wall > 2 * SMOKE_BUDGET_S:
        print(f"[ci_check] FAIL: program smoke took {wall:.1f}s > "
              f"{2 * SMOKE_BUDGET_S}s budget")
        return False
    if not payload.get("bit_exact_1x1"):
        print("[ci_check] FAIL: fused forward is not bit-exact vs the "
              f"per-layer loop on a 1x1 mesh: {payload}")
        return False
    if payload.get("max_abs_diff_vs_per_layer", 1.0) > 1e-4:
        print("[ci_check] FAIL: fused forward diverges from the per-layer "
              f"loop: maxdiff {payload['max_abs_diff_vs_per_layer']}")
        return False
    if payload.get("backend") != "shard_map":
        print(f"[ci_check] FAIL: fused program did not resolve to shard_map "
              f"under forced devices: {payload.get('backend')} "
              f"({payload.get('problems')})")
        return False
    gathers = payload.get("collectives", {}).get("all_gather")
    if gathers is None or gathers > 1:
        print(f"[ci_check] FAIL: fused forward should contain at most one "
              f"all-gather, found {gathers}")
        return False
    return _check_calibration_stability("program", payload)


def run_graph_smoke(out: Path) -> bool:
    """Full-transformer-block fused GRAPH smoke (``repro.fabric.graph``)
    under forced 8 host devices: real ``init_transformer`` weights through
    the fused graph must be bit-exact vs the per-node reference on a 1x1
    mesh (noisy ADC included), agree to float tolerance on the multi-chip
    mesh, and the collective census must EQUAL the documented budget —
    per-sibling scatters enumerated, one trailing all-gather. Recorded to
    ``BENCH_fabric_graph.json`` for cross-PR tracking."""
    t0 = time.perf_counter()
    payload = _run_forced_device_smoke("--graph-smoke")
    wall = time.perf_counter() - t0
    payload["wall_s"] = wall
    out.write_text(json.dumps(payload, indent=2, default=float))
    if "error" in payload:
        print(f"[ci_check] FAIL: fused graph smoke failed: {payload['error']}")
        return False
    print(
        f"[ci_check] fused graph smoke: {payload['devices']} devices, mesh "
        f"{payload['mesh']}, {payload.get('n_nodes')} nodes "
        f"({payload.get('n_matmuls')} matmuls) in {wall:.1f}s -> {out}"
    )
    # 3x rather than 2x: per-row comparator noise keys (the continuous-
    # batching bit-exactness contract, repro.fabric.autotune) vmap the ADC
    # convert over batch rows, which grows the noisy trace+compile of this
    # smoke by ~30% (52s -> 69s on the 1-core CI host)
    if wall > 3 * SMOKE_BUDGET_S:
        print(f"[ci_check] FAIL: graph smoke took {wall:.1f}s > "
              f"{3 * SMOKE_BUDGET_S}s budget")
        return False
    if not payload.get("bit_exact_1x1"):
        print("[ci_check] FAIL: fused graph forward is not bit-exact vs the "
              f"per-node reference on a 1x1 mesh: {payload}")
        return False
    if payload.get("max_abs_diff_vs_reference", 1.0) > 1e-4:
        print("[ci_check] FAIL: fused graph forward diverges from the "
              f"per-node reference: maxdiff {payload['max_abs_diff_vs_reference']}")
        return False
    if payload.get("backend") != "shard_map":
        print(f"[ci_check] FAIL: fused graph did not resolve to shard_map "
              f"under forced devices: {payload.get('backend')} "
              f"({payload.get('problems')})")
        return False
    if not payload.get("budget_match"):
        print(f"[ci_check] FAIL: graph collective census != documented budget: "
              f"{payload.get('collectives')} vs {payload.get('collective_budget')}")
        return False
    gathers = payload.get("collectives", {}).get("all_gather")
    if gathers is None or gathers > 1:
        print(f"[ci_check] FAIL: fused graph should contain at most one "
              f"all-gather, found {gathers}")
        return False
    return _check_calibration_stability("graph", payload)


def run_scan_smoke(out: Path) -> bool:
    """Scan-over-layers gate (``compile_graph_forward(scan_layers=True)``)
    under forced 8 host devices: at the smoke depth (n_layers=8) the
    scanned program's trace+compile wall-clock must be STRICTLY below the
    unrolled program's, the two compiled executables must produce
    bit-identical noisy-ADC logits on a 1x1 mesh, and the scanned
    collective census must equal both the documented budget and the
    per-block census × n_layers + tail decomposition. Recorded to
    ``BENCH_fabric_scan.json`` (including ``compile_speedup``) for
    cross-PR tracking.

    Budgeted at 6x the smoke budget rather than 2x: the unrolled depth-8
    compile IS the cost this PR eliminates, and the smoke pays it once on
    purpose to document the ratio."""
    t0 = time.perf_counter()
    payload = _run_forced_device_smoke("--scan-smoke")
    wall = time.perf_counter() - t0
    payload["wall_s"] = wall
    out.write_text(json.dumps(payload, indent=2, default=float))
    if "error" in payload:
        print(f"[ci_check] FAIL: scan smoke failed: {payload['error']}")
        return False
    un = payload.get("unrolled_compile_s")
    sc = payload.get("scanned_compile_s")
    print(
        f"[ci_check] scan smoke: n_layers={payload.get('n_layers')}, "
        f"compile unrolled {un:.1f}s vs scanned {sc:.1f}s "
        f"({payload.get('compile_speedup', 0.0):.1f}x) in {wall:.1f}s -> {out}"
    )
    if wall > 6 * SMOKE_BUDGET_S:
        print(f"[ci_check] FAIL: scan smoke took {wall:.1f}s > "
              f"{6 * SMOKE_BUDGET_S}s budget")
        return False
    if not payload.get("bit_exact_1x1"):
        print("[ci_check] FAIL: scanned graph forward is not bit-exact vs "
              f"the unrolled program on a 1x1 mesh: "
              f"maxdiff {payload.get('max_abs_diff_1x1')}")
        return False
    if payload.get("backend") != "shard_map":
        print(f"[ci_check] FAIL: scanned graph did not resolve to shard_map "
              f"under forced devices: {payload.get('backend')} "
              f"({payload.get('problems')})")
        return False
    if not payload.get("budget_match"):
        print(f"[ci_check] FAIL: scanned collective census != documented "
              f"budget / per-block x n_layers: {payload.get('collectives')} "
              f"vs {payload.get('collective_budget')} vs "
              f"{payload.get('block_census_x_layers')}")
        return False
    if not (un and sc and sc < un):
        print(f"[ci_check] FAIL: scanned trace+compile ({sc}s) is not below "
              f"unrolled ({un}s) — the scan stopped paying for itself")
        return False
    return True


def _check_calibration_stability(which: str, payload: dict) -> bool:
    """Gate the named ``link_clock_calibration`` constant on *stability across
    runs*, never magnitude: the ratio of measured host-simulation seconds to
    modeled fabric-link seconds depends on the host, but back-to-back warm
    runs of the same smoke must land within a generous factor of each other
    (host-timer jitter, not a regression in the link model)."""
    runs = [r for r in payload.get("link_clock_calibration_runs", []) if r]
    if not runs:
        print(f"[ci_check] FAIL: {which} smoke reported no "
              f"link_clock_calibration runs: "
              f"{payload.get('link_clock_calibration_runs')}")
        return False
    spread = max(runs) / min(runs)
    print(f"[ci_check] {which} link_clock_calibration: "
          f"{', '.join(f'{r:.3g}' for r in runs)} (spread {spread:.2f}x)")
    if spread > 100.0:
        print(f"[ci_check] FAIL: {which} link_clock_calibration unstable "
              f"across runs: {runs} ({spread:.1f}x spread)")
        return False
    return True


# metric names the fabric/serve layers must emit under an active registry;
# the canonical table lives in docs/observability.md
REQUIRED_OBS_METRICS = (
    "fabric_conversions_total",
    "fabric_fallback_total",
    "fabric_link_bits_total",
    "fabric_matmuls_total",
    "fabric_requests_total",
)


def run_obs_smoke(out: Path) -> bool:
    """Observability smoke (``repro.obs``) under forced 8 host devices: the
    fused chain must emit every required metric name, keep the
    ``ragged_batch`` fallback counter at 0 on the aligned batch and exactly 1
    on a ragged one, write a parse-clean JSONL trace log, and produce
    bit-identical fused outputs with observability on vs off. Recorded to
    ``BENCH_obs.json`` with its own budget."""
    t0 = time.perf_counter()
    payload = _run_forced_device_smoke("--obs-smoke")
    wall = time.perf_counter() - t0
    payload["wall_s"] = wall
    out.write_text(json.dumps(payload, indent=2, default=float))
    if "error" in payload:
        print(f"[ci_check] FAIL: obs smoke failed: {payload['error']}")
        return False
    print(
        f"[ci_check] obs smoke: {payload['devices']} devices, mesh "
        f"{payload['mesh']}, {len(payload.get('metric_names', []))} metrics, "
        f"{payload.get('jsonl_records')} trace records in {wall:.1f}s -> {out}"
    )
    if wall > 2 * SMOKE_BUDGET_S:
        print(f"[ci_check] FAIL: obs smoke took {wall:.1f}s > "
              f"{2 * SMOKE_BUDGET_S}s budget")
        return False
    if payload.get("backend") != "shard_map":
        print(f"[ci_check] FAIL: obs smoke chain did not resolve to shard_map "
              f"under forced devices: {payload.get('backend')}")
        return False
    missing = [m for m in REQUIRED_OBS_METRICS
               if m not in payload.get("metric_names", [])]
    if missing:
        print(f"[ci_check] FAIL: obs smoke missing required metrics: {missing}")
        return False
    if payload.get("fallbacks_aligned") != 0:
        print(f"[ci_check] FAIL: aligned fused batch recorded fallbacks: "
              f"{payload.get('fallbacks_aligned')}")
        return False
    if payload.get("fallbacks_ragged") != 1:
        print(f"[ci_check] FAIL: ragged batch should record exactly one "
              f"ragged_batch fallback, got {payload.get('fallbacks_ragged')}")
        return False
    if not payload.get("bit_identical_with_obs"):
        print("[ci_check] FAIL: fused outputs differ with observability on "
              "vs off — instrumentation is perturbing the compiled program")
        return False
    # obs_smoke re-reads the log through read_jsonl, which raises on any
    # unparseable line — reaching a positive count IS the parse-clean gate
    if not payload.get("jsonl_records", 0) > 0:
        print(f"[ci_check] FAIL: obs smoke JSONL log is empty or unparsed: "
              f"{payload.get('jsonl_records')}")
        return False
    return True


def run_autotune_smoke(out: Path) -> bool:
    """Continuous-batching gate (``repro.fabric.autotune``) under forced 8
    host devices: a ragged batch (B=3 on the 2x2 mesh) served through the
    bucketed fused-program cache must be bit-exact to the unpadded per-node
    reference after pad-slicing — noiseless AND noisy ADC (per-row noise
    keys: pad rows must not consume draws) — the measured mixed-length
    ragged trace must beat the per-node fallback loop by > 5x, and the
    autotuner's cost-model plan must not cost more than the default mesh
    with a single max-batch bucket. Recorded to
    ``BENCH_fabric_autotune.json`` for cross-PR tracking."""
    t0 = time.perf_counter()
    payload = _run_forced_device_smoke("--autotune-smoke")
    wall = time.perf_counter() - t0
    payload["wall_s"] = wall
    out.write_text(json.dumps(payload, indent=2, default=float))
    if "error" in payload:
        print(f"[ci_check] FAIL: autotune smoke failed: {payload['error']}")
        return False
    print(
        f"[ci_check] autotune smoke: {payload['devices']} devices, mesh "
        f"{payload['mesh']}, ragged-mix speedup "
        f"{payload.get('ragged_mix_speedup', 0):.1f}x, plan "
        f"{payload.get('plan', {}).get('mesh')} buckets "
        f"{payload.get('plan', {}).get('buckets')} in {wall:.1f}s -> {out}"
    )
    # 4x rather than 2x: this smoke compiles TWO fused bucketed programs
    # (noiseless + noisy ADC) and must also warm the ~115x-slower per-node
    # fallback loop it measures the ragged-mix speedup against — that
    # baseline compile IS part of the demonstrated cost (~82s on the
    # 1-core CI host), same reasoning as the scan smoke's 6x
    if wall > 4 * SMOKE_BUDGET_S:
        print(f"[ci_check] FAIL: autotune smoke took {wall:.1f}s > "
              f"{4 * SMOKE_BUDGET_S}s budget")
        return False
    if payload.get("backend") != "shard_map":
        print(f"[ci_check] FAIL: bucketed program did not resolve to "
              f"shard_map under forced devices: {payload.get('backend')}")
        return False
    if not payload.get("bit_exact_ragged"):
        print("[ci_check] FAIL: ragged batch through the bucketed fused path "
              "is not bit-exact vs the per-node reference after pad-slicing")
        return False
    if not payload.get("bit_exact_ragged_noisy"):
        print("[ci_check] FAIL: NOISY ragged batch through the bucketed "
              "fused path is not bit-exact — pad rows are consuming "
              "noise-key draws or perturbing quantization scales")
        return False
    if payload.get("ragged_mix_speedup", 0.0) <= 5.0:
        print(f"[ci_check] FAIL: bucketed fused serving of the ragged mix "
              f"must beat the per-node loop by > 5x, got "
              f"{payload.get('ragged_mix_speedup')}")
        return False
    if payload.get("cache", {}).get("misses", 1) != 0:
        print(f"[ci_check] FAIL: every trace batch fits the bucket, yet the "
              f"cache recorded misses: {payload.get('cache')}")
        return False
    if not payload.get("plan_cost_le_default"):
        print(f"[ci_check] FAIL: autotuner plan costs more than the default "
              f"mesh: {payload.get('plan')}")
        return False
    return True


def check_public_api() -> bool:
    """Every symbol a ``repro.fabric`` / ``repro.obs`` submodule exports via
    ``__all__`` must be re-exported from the package ``__all__`` — a new
    public symbol that misses the package surface fails CI."""
    sys.path.insert(0, str(REPO / "src"))
    import repro.fabric as fabric
    import repro.obs as obs

    packages = (
        (fabric, "repro.fabric", (
            "autotune", "execute", "graph", "mapper", "pipeline", "program",
            "report", "shard", "tiles", "topology",
        )),
        (obs, "repro.obs", ("fallback", "metrics", "sinks", "trace")),
    )
    ok = True
    for pkg, pkg_name, submodules in packages:
        missing = []
        for name in submodules:
            mod = importlib.import_module(f"{pkg_name}.{name}")
            for sym in getattr(mod, "__all__", ()):
                if sym not in pkg.__all__:
                    missing.append(f"{name}.{sym}")
        if missing:
            print(f"[ci_check] FAIL: {pkg_name}.__all__ misses public "
                  "symbols: " + ", ".join(missing))
            ok = False
        else:
            print(f"[ci_check] public api: {pkg_name}.__all__ covers all "
                  f"{len(pkg.__all__)} submodule exports")
    return ok


def _resolve_dotted(ref: str) -> bool:
    """Import ``repro.a.b.C`` — module prefix via importlib, rest via getattr."""
    parts = ref.split(".")
    for split in range(len(parts), 0, -1):
        try:
            obj = importlib.import_module(".".join(parts[:split]))
        except ImportError:
            continue
        try:
            for attr in parts[split:]:
                obj = getattr(obj, attr)
        except AttributeError:
            return False
        return True
    return False


def check_docs() -> bool:
    """README.md / docs/fabric.md / docs/observability.md exist and
    reference only live symbols."""
    sys.path.insert(0, str(REPO / "src"))
    import repro.fabric as fabric
    import repro.obs as obs

    ok = True
    docs = {
        "README.md": REPO / "README.md",
        "docs/fabric.md": REPO / "docs" / "fabric.md",
        "docs/observability.md": REPO / "docs" / "observability.md",
    }
    for name, path in docs.items():
        if not path.is_file():
            print(f"[ci_check] FAIL: {name} is missing")
            ok = False
    if not ok:
        return False
    for name, path in docs.items():
        text = path.read_text()
        for ref in sorted(set(re.findall(r"\brepro(?:\.\w+)+", text))):
            if not _resolve_dotted(ref):
                print(f"[ci_check] FAIL: {name} references {ref}, which does not import")
                ok = False
    fabric_doc = docs["docs/fabric.md"].read_text()
    for sym in fabric.__all__:
        if sym not in fabric_doc:
            print(f"[ci_check] FAIL: docs/fabric.md does not document repro.fabric.{sym}")
            ok = False
    obs_doc = docs["docs/observability.md"].read_text()
    for sym in obs.__all__:
        if sym not in obs_doc:
            print(f"[ci_check] FAIL: docs/observability.md does not document "
                  f"repro.obs.{sym}")
            ok = False
    if ok:
        print("[ci_check] docs: README.md + docs/fabric.md + "
              "docs/observability.md present, all references live")
    return ok


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-tests", action="store_true")
    ap.add_argument("--out", default=str(REPO / "BENCH_fabric.json"))
    ap.add_argument("--shard-out", default=str(REPO / "BENCH_fabric_shard.json"))
    ap.add_argument("--program-out", default=str(REPO / "BENCH_fabric_program.json"))
    ap.add_argument("--graph-out", default=str(REPO / "BENCH_fabric_graph.json"))
    ap.add_argument("--scan-out", default=str(REPO / "BENCH_fabric_scan.json"))
    ap.add_argument("--obs-out", default=str(REPO / "BENCH_obs.json"))
    ap.add_argument(
        "--autotune-out", default=str(REPO / "BENCH_fabric_autotune.json")
    )
    args = ap.parse_args()

    ok = True
    if not args.skip_tests:
        print("[ci_check] running tier-1 tests ...")
        ok = run_tier1()
        print(f"[ci_check] tier-1: {'PASS' if ok else 'FAIL'}")
    if ok:
        ok = run_fabric_smoke(Path(args.out))
    if ok:
        ok = run_shard_smoke(Path(args.shard_out))
    if ok:
        ok = run_program_smoke(Path(args.program_out))
    if ok:
        ok = run_graph_smoke(Path(args.graph_out))
    if ok:
        ok = run_scan_smoke(Path(args.scan_out))
    if ok:
        ok = run_obs_smoke(Path(args.obs_out))
    if ok:
        ok = run_autotune_smoke(Path(args.autotune_out))
    if ok:
        ok = check_public_api()
    if ok:
        ok = check_docs()
    raise SystemExit(0 if ok else 1)


if __name__ == "__main__":
    main()
