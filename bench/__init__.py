"""Chip benchmark of the streaming PLA system: one cell per run.

Run a cell with ``python3 bench/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout; the cells,
metrics and bounds are in ``BENCHMARK.json``.
"""
