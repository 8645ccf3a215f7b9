"""On-chip benchmark of the square-path serving engine.

One cell runs per process:

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` at the root of the checkout names the cells.  Each cell's
configuration, traffic mix, correctness limits and metrics live in files of
their own under this directory, found by name (see ``bench/harness.py``).
"""
