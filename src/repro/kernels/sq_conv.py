"""Pallas TPU kernel: square-based 1D correlation (paper §5, Fig.8).

The paper's Fig.8 engine broadcasts each incoming sample to all N taps,
forms ``(w_i + x)``, squares, and accumulates into per-output registers; the
shared ``x^2`` is computed once and subtracted at every tap.

TPU adaptation: outputs are tiled over a 1D grid (``bo`` outputs per step,
``dimension_semantics=("parallel",)`` -- output tiles are independent).

The tap walk is **block-vectorized**: instead of one dynamic-slice load and
one rank-1 PM update per tap, the kernel processes ``tb`` taps per chunk.
One chunk loads a single ``bo + tb - 1``-sample window, forms the ``tb``
shifted views with static slices (a register-level rotation on silicon --
no extra VMEM traffic), and accumulates the whole (tb, bo) PM block

    pm[t, j] = (x[j + t] + w[t])^2 - x[j + t]^2

in one rank-2 pass.  ``tb`` is chosen by kernels.tuning.plan_conv; the
wrapper zero-pads the taps to a multiple of ``tb`` (zero taps contribute
``(0 + x)^2 - x^2 = 0`` -- exact).  The data-side correction (the sliding
sum of squares, shared-x^2 term) and the kernel-side ``Sw`` are accumulated
in the same pass, so the kernel is self-contained.

The input block uses an ELEMENT-indexed BlockSpec trick: we pass a padded
input whose block size equals ``bo`` but read across the boundary via
a ``pl.ds`` window of an un-blocked (whole-array) ref -- on real TPU silicon this
block would be double-buffered by the pipeline; sizes here are
filter-engine scale (n_taps <= a few hundred), so a whole-stream VMEM
residency is realistic for DSP workloads the paper targets.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["sq_conv_kernel", "sq_conv_pallas"]


# Tap counts up to this bound unroll the tap walk statically (one window
# load, static shifted views, no loop bookkeeping).  Beyond it the kernel
# falls back to the fori_loop tap-block walk -- filter-engine tap counts
# (the paper's Fig.8 workloads) sit far below the bound.
UNROLL_TAPS_MAX = 128


def sq_conv_kernel(x_ref, w_ref, out_ref, *, n_taps: int, bo: int, tb: int):
    i = pl.program_id(0)
    start = i * bo
    w = w_ref[...]                                   # (n_taps,)
    sw = -jnp.sum(w * w)                             # Sw (paper eq 11)
    nt = n_taps // tb

    if tb == 1 and n_taps <= UNROLL_TAPS_MAX:
        # STATIC rank-1 walk: one window load covers every tap's shifted
        # view; each tap is a static slice + operand add + square.  The
        # tap-block form below pays a (tb, bo) stack materialization and a
        # fori_loop round-trip per chunk, which at tb=1 is pure
        # bookkeeping -- it cost more than the arithmetic under interpret
        # execution (the PR 1 sq_conv regression: 84.9us seed -> 118.9us;
        # this path measures ~24us at the tracked L=2048/16-tap shape).
        xwin = x_ref[pl.ds(start, bo + n_taps - 1)]
        acc = jnp.full((bo,), sw, dtype=out_ref.dtype)
        for t in range(n_taps):
            xs = jax.lax.slice_in_dim(xwin, t, t + bo)
            s = xs + w[t]
            acc = acc + (s * s - xs * xs)            # shared x^2 subtracted
        out_ref[...] = acc * 0.5                     # the final right shift
        return

    def tap_block(c, acc):
        t0 = c * tb
        # One window load covers all tb shifted views of this chunk.
        xwin = x_ref[pl.ds(start + t0, bo + tb - 1)]
        wblk = jax.lax.dynamic_slice_in_dim(w, t0, tb)          # (tb,)
        xs = jnp.stack([jax.lax.slice_in_dim(xwin, t, t + bo)
                        for t in range(tb)])                    # (tb, bo)
        pm = (xs + wblk[:, None]) * (xs + wblk[:, None])        # add + square
        return acc + jnp.sum(pm - xs * xs, axis=0)   # shared x^2 subtracted

    acc = jnp.full((bo,), sw, dtype=out_ref.dtype)   # init with correction
    if nt == 1:
        acc = tap_block(0, acc)
    else:
        acc = jax.lax.fori_loop(0, nt, tap_block, acc)
    out_ref[...] = acc * 0.5                         # the final right shift


def sq_conv_pallas(x, w, *, bo: int = 256, tb: int = 8,
                   interpret: bool = False):
    """Valid square-based correlation ``y_k = sum_i w_i x_{i+k}``.

    x: (L,) pre-widened samples; w: (n,) taps, n a multiple of ``tb``
    (the ops wrapper zero-pads taps).  Output length L - n + 1, padded by
    the ops wrapper to a multiple of ``bo``.
    """
    L = x.shape[0]
    n = w.shape[0]
    k_out = L - n + 1
    assert k_out % bo == 0, (k_out, bo)
    assert n % tb == 0, (n, tb)
    kernel = functools.partial(sq_conv_kernel, n_taps=n, bo=bo, tb=tb)
    return pl.pallas_call(
        kernel,
        grid=(k_out // bo,),
        in_specs=[
            pl.BlockSpec(x.shape, lambda i: (0,)),    # stream-resident input
            pl.BlockSpec(w.shape, lambda i: (0,)),    # taps stationary
        ],
        out_specs=pl.BlockSpec((bo,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((k_out,), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, w)
