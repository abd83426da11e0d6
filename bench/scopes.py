#!/usr/bin/env python3
"""A traced run of one cell, read by the program's named scopes and spans.

    python3 bench/scopes.py --workload <cell> --seed <n> [--seconds <s>] \\
        [--trace-dir <dir>]

Runs the cell as ``bench/run.py --trace 1`` does, with ``repro.obs``
tracing on around it, so that the program's spans reach the profile beside
the harness's. Then it reads the profile (``bench/harness/scopes.py``) and
prints one JSON line: the harness's own result line (``result``), device
self seconds per scope (``scopes``, ``other`` included, and its share),
device idle seconds inside each program span (``span_idle``), the longest
idle gaps labelled by the innermost span of either kind, the largest ops
with no scope, and the per-layer numbers of ``scopes.METRICS`` per served
batch, training step or fabric call. The profile is kept under
``--trace-dir`` when given. The benchmark's own runs never run this.

It compiles without JAX's persistent compilation cache: the cache's key
leaves out op_name metadata, so an executable cached from a tree with other
scopes, or none, would put that tree's names on the trace.
"""

import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

T_START = time.perf_counter()
sys.path[0] = str(Path(__file__).resolve().parents[1])

from bench.harness import runner, scopes  # noqa: E402


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0,
                    help="capped, as in a traced run, at the traffic's trace_seconds")
    ap.add_argument("--trace-dir", default=None)
    args = ap.parse_args()
    cell = runner.load_cell(args.workload)
    sys.path.insert(0, str(runner.ROOT / "src"))
    runner.require_chips(cell)
    import jax

    from repro import obs

    jax.config.update("jax_enable_compilation_cache", False)
    keep = args.trace_dir is not None
    (runner.ROOT / ".bench_trace").mkdir(exist_ok=True)
    log_dir = args.trace_dir or tempfile.mkdtemp(dir=runner.ROOT / ".bench_trace")
    try:
        with obs.tracing() as tracer:
            result = runner.run(cell, args.seed, args.seconds, True, T_START, trace_dir=log_dir)
        summary = scopes.reduce(scopes.load(log_dir))
    finally:
        if not keep:
            shutil.rmtree(log_dir, ignore_errors=True)
    out = {
        "workload": cell.name,
        "seed": args.seed,
        "result": result,
        "program_spans": len(tracer.spans),
        "units": summary.units,
        "busy_s": summary.busy_s,
        "window_s": summary.window_s,
        "other_share": summary.other_share,
        "scopes": summary.scopes,
        "span_idle": summary.span_idle,
        "idle_gaps": summary.idle_gaps,
        "other_ops": summary.other_ops,
        "metrics": {name: scopes.metric(summary, name) for name in scopes.METRICS},
    }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
