"""Readings that a cell's correctness limits are set from (not part of a
benchmark run).

    python bench/calibrate.py --served DIR [--dump DIR2]

reads the files that runs of the cell wrote with
``bench/run.py ... --served-out DIR/<name>.json``: the requests each run
served, at the cell's own size and load, and the per-token gaps its
comparison read (the program's readings, which set a limit's lower end).
For each it computes the reference in fp8 in the program's place at the
same prompts and served tokens, judges it by the cell's limits as a run
is judged, and prints one JSON line (the control's readings set the
limit's upper end).  ``--dump`` writes each seed's per-token gaps,
program and control, to ``DIR2/gaps_<seed>.json``.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness  # noqa: E402


def control(served: dict, cfg: dict, limits: dict):
    """(control's per-token gaps, its numbers, judged correct) for one
    run's served requests, under the cell's configuration and limits."""
    import numpy as np
    from bench import checks, reference, weights
    from bench.drivers import common
    seqs = [(np.asarray(p, np.int32), np.asarray(o, np.int32))
            for p, o in served["served"]]
    gaps = checks.served_gaps(weights.model(cfg),
                              weights.base_key(served["seed"]), seqs,
                              control=reference.FP8)
    numbers = checks.served_numbers(gaps)
    return gaps, numbers, common.judge(limits, numbers)[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--served", required=True)
    ap.add_argument("--dump")
    args = ap.parse_args(argv)
    import numpy as np
    from bench import checks
    for path in sorted(glob.glob(os.path.join(args.served, "*.json"))):
        with open(path) as f:
            served = json.load(f)
        t0 = time.perf_counter()
        _, cfg, _, limits = harness.load_cell(served["workload"])
        gaps, numbers, ok = control(served, cfg, limits)
        line = {"seed": served["seed"], "file": os.path.basename(path),
                "program": checks.served_numbers(np.asarray(served["gaps"])),
                "control": numbers, "control_correct": ok,
                "tokens": len(gaps), "control_s": time.perf_counter() - t0}
        if args.dump:
            os.makedirs(args.dump, exist_ok=True)
            with open(os.path.join(args.dump, f"gaps_{served['seed']}.json"),
                      "w") as f:
                json.dump({"program": served["gaps"],
                           "control": [float(g) for g in gaps]}, f)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
