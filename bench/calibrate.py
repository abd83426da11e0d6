#!/usr/bin/env python3
"""Readings the limits of a cell's check are set from: the program's on many
seeds, and the control's (the reference at the configuration's control
precision, in the program's place) and the planted faults' on some of them,
in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --first-seed <n> \\
        --seconds <s> --controls 3 [--config '{"cim": {...}}']
    python3 bench/calibrate.py --workload <cell> --seeds 1 --first-seed <n> \\
        --seconds <s> --stand-in control

Each seed runs the cell's set-up, a window of ``--seconds``, and the check;
the first ``--controls`` seeds also read the control and every fault the
driver plants. One JSON line per reading. With ``--stand-in`` each seed is
instead a whole run of the harness with the control or the named fault in
the program's place for its check, and prints the run's result line. The
benchmark's own runs never run this.
"""

import json
import sys
import time
from pathlib import Path

sys.path[0] = str(Path(__file__).resolve().parents[1])

from bench.harness import runner  # noqa: E402


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--config", default="{}",
                    help="JSON object merged into the configuration for every reading")
    ap.add_argument("--stand-in", default=None,
                    help="'control' or a fault's name: whole harness runs with it in the "
                         "program's place")
    args = ap.parse_args()
    cell = runner.load_cell(args.workload)
    sys.path.insert(0, str(runner.ROOT / "src"))
    runner.require_chips(cell)
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    drv = runner.driver(cell)
    cell.config = {**cell.config, **json.loads(args.config)}
    for i in range(args.seeds):
        seed = args.first_seed + i
        t = time.perf_counter()
        if args.stand_in:
            out = runner.run(cell, seed, args.seconds, False, t, stand_in=args.stand_in)
            print(json.dumps({"seed": seed, "stand_in": args.stand_in, "result": out}),
                  flush=True)
            continue
        state = drv.setup(cell, seed)
        drv.window(state, args.seconds)
        calls = drv.attempted(state)
        rec = {"seed": seed, "program": drv.check(state), "calls": calls,
               "seconds": time.perf_counter() - t, "detail": getattr(state, "detail", None)}
        print(json.dumps(rec), flush=True)
        if i < args.controls:
            t = time.perf_counter()
            print(json.dumps({"seed": seed, "control": drv.control(state),
                              "seconds": time.perf_counter() - t,
                              "detail": getattr(state, "detail", None)}), flush=True)
            if hasattr(drv, "faults"):
                print(json.dumps({"seed": seed, "faults": drv.faults(state)}), flush=True)
        del state
    return 0


if __name__ == "__main__":
    sys.exit(main())
