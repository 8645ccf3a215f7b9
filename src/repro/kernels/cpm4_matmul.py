"""Pallas TPU kernel: complex matmul with 4 squares per multiply (paper §6).

The CPM block of Fig.9a as a K-blocked Pallas grid: four real operand planes
stream through; real/imag accumulators live in dedicated VMEM scratch
buffers across the K walk (out refs written once, at the final K step) and
are initialized with the shared corrections ``Sx_h + Sy_k`` (eq 18) -- note
CPM4's real and imaginary parts share ONE correction pair, unlike CPM3's
four distinct terms.  Grid semantics and the chunked PM blocks (``kc``)
are exactly as in kernels.cpm3_matmul.

Per (h, i, k):
    re += (a + c)^2 + (b - s)^2        (eq 21)
    im += (b + c)^2 + (a + s)^2        (eq 22)

Unlike CPM3 there is NO square shared between the planes to hoist: each
of the four squares pairs one row plane directly with one column plane,
already one broadcast add per PM term.  The only hoistable subexpression
is the negated column plane ``-s`` (formed at rank 2 once per chunk so
the (b - s) term is a uniform broadcast *add* like the other three); the
remaining ~2x-vs-3x interpret gap against ``sq_matmul`` is intrinsic --
CPM4 does 4 squares + 4 rank-3 adds per complex multiply where the real
kernel does 1 + 1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pm_blocks import pm_chunked_reduce

__all__ = ["cpm4_matmul_kernel", "cpm4_matmul_pallas"]


def _cpm4_body(rs, cs, carry):
    """One chunk's four squares (paper eqs 21/22) on pre-broadcast slabs.

    Column slabs are (c, s, -s) with the negation hoisted to rank 2 (see
    module docstring); every square is one broadcast add."""
    re, im = carry
    a_s, b_s = rs
    c_s, s_s, ns_s = cs
    t1 = a_s + c_s
    t2 = b_s + ns_s                     # (b - s) via the hoisted -s plane
    t3 = b_s + c_s
    t4 = a_s + s_s
    re = re + jnp.sum(t1 * t1 + t2 * t2, -2)
    im = im + jnp.sum(t3 * t3 + t4 * t4, -2)
    return re, im


def _cpm4_cols(c, s):
    return c, s, -s


def cpm4_matmul_kernel(a_ref, b_ref, c_ref, s_ref, sx_ref, re_ref, im_ref,
                       re_acc, im_acc, *, nk: int, kc: int):
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        # both planes start from the row correction Sx_h (col term added
        # by the wrapper, mirroring Fig.2's staggered Sb_j injection)
        re_acc[...] = jnp.broadcast_to(sx_ref[...], re_acc.shape)
        im_acc[...] = jnp.broadcast_to(sx_ref[...], im_acc.shape)

    re, im = pm_chunked_reduce(
        (re_acc[...], im_acc[...]), (a_ref, b_ref), (c_ref, s_ref),
        kc=kc, body=_cpm4_body, cols=_cpm4_cols)
    re_acc[...] = re
    im_acc[...] = im

    @pl.when(k_step == nk - 1)
    def _finalize():
        re_ref[...] = re_acc[...] * 0.5
        im_ref[...] = im_acc[...] * 0.5


def cpm4_matmul_pallas(a, b, c, s, sx, sy, *, bm: int = 256, bn: int = 256,
                       bk: int = 128, kc: int | None = None,
                       interpret: bool = False):
    """sx: (m, 1) row corrections; sy: (1, n) column corrections (eq 18),
    added post-kernel (linearity; see cpm3_matmul.py for the Fig.2 note)."""
    m, k = a.shape
    _, n = c.shape
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    kc = bk if kc is None else kc
    assert bk % kc == 0, (bk, kc)
    nk = k // bk
    kernel = functools.partial(cpm4_matmul_kernel, nk=nk, kc=kc)
    re, im = pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), a.dtype),
            jax.ShapeDtypeStruct((m, n), a.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, bn), a.dtype),
            pltpu.VMEM((bm, bn), a.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b, c, s, sx)
    return re + 0.5 * sy, im + 0.5 * sy
