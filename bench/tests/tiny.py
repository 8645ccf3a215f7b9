"""The benchmark's cells cut to a size the CPU runs in seconds: the same
traffic kinds, drivers, checks and limits, with a small model of the same
architecture, its module's ``TINY`` (float32, so the program and the
reference agree to rounding)."""
from bench import arch, harness

SERVE = "danube3-4b-sq.reason"


def serve_cell():
    entry, cfg, mix, limits = harness.load_cell(SERVE)
    mix = dict(mix, clients=2, rounds=4,
               prompt=dict(mix["prompt"], median=12, lo=4, hi=24),
               output=dict(mix["output"], median=8, lo=4, hi=24),
               engine=dict(max_slots=2, block_size=8, prefill_chunk=8,
                           blocks_per_seq=8, num_blocks=17,
                           max_new_tokens=24))
    return entry, dict(cfg, **arch.of(cfg).TINY), mix, limits


def run(workload, cell, hooks=None, seed=2 ** 33 + 5, seconds=0.5):
    return harness.run_cell(workload, seed, seconds, False,
                            require_chip=False, hooks=hooks, cell=cell)
