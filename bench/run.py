#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: every number compared with its limit.
Without a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result.
"""

import sys
import time

T_START = time.perf_counter()

from pathlib import Path  # noqa: E402

# the checkout root, in place of this script's directory
sys.path[0] = str(Path(__file__).resolve().parents[1])

from bench.harness.runner import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
