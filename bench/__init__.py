"""On-chip benchmark of this repository: one run of one cell per process.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
from the root of a checkout. Cells, configurations and metrics are declared in
``BENCHMARK.json``; everything that belongs to one configuration, one traffic
mix or one per-layer metric sits in a file of its own under this directory and
is found by its name.
"""
