"""One run of one cell: set-up, a measured window, the check against the
plain reference, and the result line.

Everything is found by name: the cell in ``BENCHMARK.json``, its
configuration file, its traffic file ``bench/traffic/<traffic>.json`` (which
names the driver ``bench/drivers/<driver>.py``), and one reader
``bench/metrics/<metric>.py`` per per-layer metric.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


@dataclasses.dataclass
class Cell:
    root: Path  # checkout root; files are found under root / "bench"
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list  # metric entries of BENCHMARK.json this cell reports
    per_layer: list


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf_entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    return Cell(
        root=root,
        name=name,
        chips=w["chips"],
        config=json.loads((root / conf_entry["file"]).read_text()),
        traffic=json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text()),
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, name)],
    )


def driver(cell: Cell):
    return importlib.import_module(f"bench.drivers.{cell.traffic['driver']}")


def reader(metric: str, root: Path = ROOT):
    """The ``read(reading)`` function of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Reading:
    """What a per-layer reader may read."""

    cell: Cell
    window_s: float
    counters: dict
    trace: object  # harness.trace.TraceSummary, or None
    peaks: object  # harness.peaks.ChipPeaks


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def memory_peak(n_chips: int) -> int:
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()[:n_chips]]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def require_chips(cell: Cell) -> None:
    """Exit without a result where JAX finds no TPU or too few of them."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"bench: no accelerator: {e}")
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU found (JAX platform is {devs[0].platform!r})")
    if len(devs) < cell.chips:
        raise SystemExit(f"bench: {cell.name} needs {cell.chips} TPUs, found {len(devs)}")


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        trace_dir: str | None = None, stand_in: str | None = None) -> dict:
    """One run; returns the result object (``checks`` last).

    ``stand_in`` puts something else in the program's place for the check,
    after the program's own check has run: ``"control"`` (the reference at
    the configuration's control precision) or the name of one of the
    driver's planted faults. The benchmark's own runs never set it."""
    import jax

    from bench.harness import trace as trace_mod
    from bench.harness.compile_log import CompileLog
    from bench.harness.peaks import peaks

    drv = driver(cell)
    info = device_info()
    chip = peaks(info["kind"]) if info["platform"] == "tpu" else None
    state = drv.setup(cell, seed)
    setup_s = time.perf_counter() - t_start

    summary = None
    log_dir = Path(trace_dir or ROOT / ".bench_trace" / cell.name)
    with CompileLog() as in_window:
        if trace:
            shutil.rmtree(log_dir, ignore_errors=True)
            seconds = min(seconds, cell.traffic["trace_seconds"])
            # no Python tracer: it slows the host loop whose time the
            # per-layer metrics read; the harness's own spans label the gaps
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            with jax.profiler.trace(str(log_dir), profiler_options=opts):
                with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
                    window_s = drv.window(state, seconds)
        else:
            window_s = drv.window(state, seconds)
    if trace:
        summary = trace_mod.reduce(trace_mod.load_profile(str(log_dir)))
        if info["platform"] != "cpu" and not summary.n_chips:
            raise RuntimeError(f"no device ops in the trace under {log_dir}")
        if trace_dir is None:
            shutil.rmtree(log_dir, ignore_errors=True)
    info["memory_peak_bytes"] = memory_peak(cell.chips)

    n_attempted, n_failed = drv.attempted(state), drv.failed(state)
    e2e = drv.end_to_end(state, window_s)
    e2e["setup_s"] = setup_s
    counters = drv.counters(state)
    checks = drv.check(state)
    if stand_in == "control":
        checks = drv.control(state)
    elif stand_in is not None:
        checks = drv.faults(state)[stand_in]
    checks = dict(checks, compiles_in_window=len(in_window.events))
    for name, _ in in_window.events:
        print(f"compiled inside the window: {name}", file=sys.stderr)
    limits = dict(cell.traffic["limits"], compiles_in_window=0)

    if trace:
        reading = Reading(cell, summary.window_s, counters, summary, chip)
        metrics = {}
        for m in cell.per_layer:
            value = reader(m["name"], cell.root)(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        info["busy_s"] = summary.busy_s
        info["window_s"] = summary.window_s
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    correct = all(checks[k] <= limits[k] for k in limits)
    out = {"correct": correct, "attempted": n_attempted, "failed": n_failed,
           "metrics": metrics, "device": info}
    if summary is not None:
        out["breakdown"] = {"device_ops": [list(x) for x in summary.device_ops],
                            "idle_gaps": [list(x) for x in summary.idle_gaps]}
    out["checks"] = {k: {"value": checks[k], "limit": limits[k]} for k in limits}
    return out


def main(argv=None, t_start: float | None = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profile here instead of deleting it")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    require_chips(cell)
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    out = run(cell, args.seed, args.seconds, bool(args.trace), t_start, args.trace_dir)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
