"""Run one benchmark cell on the chip this process finds.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the cell's result as one JSON object.
Set-up time is counted from the start of this process.
"""
import time

_T0 = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_process=_T0))
