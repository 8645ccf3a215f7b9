"""The low-precision control at test size: the reference computed in fp8
in the program's place, read at the prompts and tokens a run served,
reads far worse than the program, and the harness's own comparison
judges it not correct.  (On the chip, at the cell's own size, the same
readings set the limit's ends; see PERF.md.)"""
import json

import numpy as np

from bench import calibrate, checks
from bench.drivers import common
from bench.tests import tiny


def test_serving_control_is_not_correct():
    cell = tiny.serve_cell()
    res, rec = tiny.run(tiny.SERVE, cell)
    assert res["correct"], res["checks"]
    served = json.loads(json.dumps(rec.served_json()))
    assert np.allclose(served["gaps"], rec.gaps)
    program = checks.served_numbers(np.asarray(served["gaps"]))
    assert program == {k: v for k, (v, _) in rec.checks.items()}
    _, cfg, _, _ = cell
    gaps, numbers, _ = calibrate.control(served, cfg, {"limits": {}})
    assert len(gaps) == len(served["gaps"])
    assert numbers["logit_gap_mean"] > max(3 * program["logit_gap_mean"],
                                           1e-4)
    # a limit between the two readings passes the program and fails the
    # control, through the same judgement a run makes
    limit = {"limits": {"logit_gap_mean": (program["logit_gap_mean"]
                                           * numbers["logit_gap_mean"]) ** .5}}
    assert common.judge(limit, program)[1]
    assert not calibrate.control(served, cfg, limit)[2]
