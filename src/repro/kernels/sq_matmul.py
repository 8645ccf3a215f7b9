"""Pallas TPU kernel: square-based matmul (paper §3.2 systolic array, adapted).

TPU adaptation of the paper's weight-stationary square-based systolic array
(Fig.2/3).  The hardware streams staggered operands through PEs holding
``REGA``; on TPU the same dataflow is a K-blocked accumulation over a
(M/bm, N/bn, K/bk) grid with the output tile resident in VMEM across the
K axis (grid minor dimension), exactly like a weight-stationary pass:

- a dedicated VMEM **scratch accumulator** (``scratch_shapes``) holds the
  (bm, bn) tile for the whole K walk -- ``out_ref`` is written exactly once,
  at the final K step, instead of being read-modify-written every grid step;
- the accumulator is initialized with the corrections ``Sa_i + Sb_j`` at the
  first K step -- the paper's "initialise the register with Sa_i + Sb_j"
  (Fig.1b / Fig.5b);
- every K step accumulates PM terms ``(a_ik + b_kj)^2`` (the PE array);
- the final K step applies the paper's "simple right shift" (x0.5 / >>1).

Dataflow (block-level PM accumulation)
--------------------------------------
The contraction is **chunked, not rank-1**: each (bm, bk) x (bk, bn) grid
step processes its K slab in ``kc``-wide chunks of rank-2 broadcast
squaring.  One chunk forms the rank-3 PM block

    s[i, c, j] = a[i, c] + b[c, j]          # (bm, kc, bn) operand adders
    acc[i, j] += sum_c s[i, c, j]^2         # squarers + block reduction

so a (256, 256, 128) tile is a handful of block-wide VPU passes rather
than 128 serialized rank-1 sweeps.  ``kc`` (which must divide ``bk``) is
the knob trading the live intermediate's footprint (bm * kc * bn
accumulator-dtype words) against loop-issue overhead; a ``kc == bk`` plan
degenerates to a single unrolled chunk with no inner loop at all.

Two PM-block layouts are compiled, selected by the static ``pm_layout``:

``"mkn"``
    The block is (bm, kc, bn), reduced over the middle axis.  ``bn`` stays
    on the 128-lane minor axis, so Mosaic keeps native vreg layouts -- the
    TPU-native schedule.
``"mnk"``
    Each ``b`` chunk is transposed and the block is (bm, bn, kc), reduced
    over the *minor* axis.  Minor-axis reduction fuses into a
    dot-product-shaped loop nest, which is what CPU interpret mode (and
    the XLA CPU backend generally) executes fastest -- ~6x over the seed
    rank-1 kernel at 128^3 f32.

Both are the same arithmetic (one operand add + one square per PM term);
the planner in :mod:`repro.kernels.tuning` picks ``(bm, bn, bk, kc)`` and
the layout per call site (cost-model ranked, optionally autotuned).

One kernel serves the plain, batched and batch-folded forms: its refs
carry ``fb`` batch elements on a leading axis (``fb == 1`` for a plain or
one-element-per-step batched call), and the PM machinery broadcasts over
it.  The grid is marked ``dimension_semantics=("parallel", "parallel",
"parallel", "arbitrary")``: batch/M/N tiles carry no cross-step state (the scratch
accumulator is only live along K), so Mosaic may pipeline and reorder
them freely; only the K axis is sequential.

The squares execute on the VPU; on the paper's silicon they are the
half-area squarer circuits.  This kernel is the bit-faithful *emulation*
used for verification (float and int8 paths); the production MXU-routed
path is ``core.matmul`` mode ``square_virtual``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pm_blocks import PM_LAYOUTS, pm_chunked_reduce

__all__ = ["sq_matmul_kernel", "sq_matmul_pallas", "sq_matmul_batched_pallas",
           "pm_block_accum", "PM_LAYOUTS"]


def pm_block_accum(acc, a_ref, b_ref, *, kc: int, pm_layout: str):
    """Chunked block PM accumulation: ``acc + sum_k (a[i,k] + b[k,j])^2``.

    a_ref: (..., bm, bk) and b_ref: (..., bk, bn) VMEM refs holding values
    pre-widened to the accumulator dtype; acc: the carried (..., bm, bn)
    accumulator.  The K slab is processed in ``kc``-wide chunks via the
    shared machinery in kernels.pm_blocks.
    """
    def body(rs, cs, axis, acc):
        s = rs[0] + cs[0]                    # PE operand adders
        return acc + jnp.sum(s * s, axis)    # squarers + block reduction

    return pm_chunked_reduce(acc, (a_ref,), (b_ref,), kc=kc,
                             pm_layout=pm_layout, body=body)


def sq_matmul_kernel(a_ref, b_ref, sa_ref, sb_ref, out_ref, acc_ref, *,
                     nk: int, kc: int, pm_layout: str, is_int: bool):
    """One (batch-block, i, j, k) grid step of the chunked square matmul.

    a_ref: (fb, bm, bk); b_ref: (fb, bk, bn); sa_ref: (fb, bm, 1);
    sb_ref: (fb, 1, bn); out_ref and the scratch acc_ref: (fb, bm, bn).
    ``fb > 1`` folds a block of batch elements into one grid step --
    ``fb * bm`` rows' worth of PM work amortizes one step's issue overhead
    (the small-(M, N), large-B regime of kernels.routing).
    """
    k_step = pl.program_id(3)

    @pl.when(k_step == 0)
    def _init():
        # Accumulator init = Sa_i + Sb_j (paper Fig.1b: "initialise its
        # register first with Sa_i + Sb_j").
        acc_ref[...] = sa_ref[...] + sb_ref[...]

    acc_ref[...] = pm_block_accum(acc_ref[...], a_ref, b_ref, kc=kc,
                                  pm_layout=pm_layout)

    @pl.when(k_step == nk - 1)
    def _finalize():
        # The paper's final right shift: 2*c_ij -> c_ij.
        acc = acc_ref[...]
        if is_int:
            out_ref[...] = jax.lax.shift_right_arithmetic(
                acc, jnp.ones_like(acc))
        else:
            out_ref[...] = acc * 0.5


def sq_matmul_batched_pallas(a, b, sa, sb, *, bm: int = 256, bn: int = 256,
                             bk: int = 128, kc: int | None = None,
                             fb: int = 1, pm_layout: str = "mkn",
                             interpret: bool = False):
    """Batched pallas_call wrapper: a (B, m, k), b (B, k, n), corrections
    sa (B, m, 1) / sb (B, 1, n).  ``fb`` batch elements per grid step on
    the outermost batch grid axis; B must be an fb multiple (the ops
    wrapper zero-pads, and zero batch elements are exact no-ops).
    Operands must be pre-widened to the accumulator dtype and pre-padded
    to tile multiples (see kernels.ops).  ``kc`` must divide ``bk``
    (defaults to ``bk``: one chunk)."""
    nb, m, k = a.shape
    nb2, k2, n = b.shape
    assert nb == nb2 and k == k2
    assert sa.shape == (nb, m, 1) and sb.shape == (nb, 1, n)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    assert nb % fb == 0, (nb, fb)
    kc = bk if kc is None else kc
    assert bk % kc == 0, (bk, kc)
    nk = k // bk
    is_int = jnp.issubdtype(a.dtype, jnp.integer)

    kernel = functools.partial(sq_matmul_kernel, nk=nk, kc=kc,
                               pm_layout=pm_layout, is_int=is_int)
    return pl.pallas_call(
        kernel,
        grid=(nb // fb, m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((fb, bm, bk), lambda bb, i, j, kk: (bb, i, kk)),
            pl.BlockSpec((fb, bk, bn), lambda bb, i, j, kk: (bb, kk, j)),
            pl.BlockSpec((fb, bm, 1), lambda bb, i, j, kk: (bb, i, 0)),
            pl.BlockSpec((fb, 1, bn), lambda bb, i, j, kk: (bb, 0, j)),
        ],
        out_specs=pl.BlockSpec((fb, bm, bn), lambda bb, i, j, kk: (bb, i, j)),
        out_shape=jax.ShapeDtypeStruct((nb, m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((fb, bm, bn), a.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(a, b, sa, sb)


def sq_matmul_pallas(a, b, sa, sb, *, bm: int = 256, bn: int = 256,
                     bk: int = 128, kc: int | None = None,
                     pm_layout: str = "mkn", interpret: bool = False):
    """Plain (m, k) x (k, n) wrapper: the batched kernel at one element,
    with sa (m, 1) / sb (1, n).  Same operand contract as
    :func:`sq_matmul_batched_pallas`."""
    return sq_matmul_batched_pallas(
        a[None], b[None], sa[None], sb[None], bm=bm, bn=bn, bk=bk, kc=kc,
        pm_layout=pm_layout, interpret=interpret)[0]
