"""Pallas TPU emulation kernels for the paper's square-based datapaths.

Layout:
- ``sq_matmul`` / ``cpm3_matmul`` / ``cpm4_matmul`` / ``sq_conv`` /
  ``sq_conv2d``: raw kernels (VMEM scratch accumulators; ``sq_matmul``
  walks K one row at a time, the others reduce chunked PM blocks -- see
  ``pm_blocks``; ``sq_conv2d`` streams 2D windows without im2col);
- ``ops``: jit'd public wrappers (widening, padding, corrections, planner);
- ``tuning``: each kernel's PM schedule and the cost-model
  (bm, bn, bk, kc) / (bh, bw, bk, kc, bf) tile planners;
- ``ref``: pure-jnp oracles for the test sweeps.
"""
from repro.kernels.ops import (sq_matmul, cpm3_matmul, cpm4_matmul, sq_conv,
                               sq_conv2d, sq_conv2d_im2col, default_interpret)
from repro.kernels.tuning import (TilePlan, Conv2DPlan, plan_matmul,
                                  plan_conv, plan_conv2d)

__all__ = ["sq_matmul", "cpm3_matmul", "cpm4_matmul", "sq_conv", "sq_conv2d",
           "sq_conv2d_im2col", "default_interpret", "TilePlan", "Conv2DPlan",
           "plan_matmul", "plan_conv", "plan_conv2d"]
