"""Pallas TPU kernel: complex matmul with 3 squares per multiply (paper §9).

Implements the CPM3 accumulator array (paper Fig.12b) as a K-blocked Pallas
grid.  Four real input planes (a, b = Re/Im of X; c, s = Re/Im of Y) stream
through; two output planes (re, im) accumulate in dedicated VMEM scratch
buffers for the whole K walk (out refs are written once, at the final K
step).  The grid is ``dimension_semantics=("parallel", "parallel",
"arbitrary")`` -- only K is sequential.

Per (h, i, k) the three squares are:
    shared = (c + a + b)^2            -- computed ONCE, used by both planes
    re    += shared - (b + c + s)^2   (paper eq 32)
    im    += shared + (a + s - c)^2   (paper eq 34)

Each grid step processes its K slab in ``kc``-wide rank-2 broadcast
chunks, PM blocks of shape (bm, kc, bn) reduced over the chunk axis
(:func:`repro.kernels.pm_blocks.pm_chunked_reduce`).

The shared subexpressions of the three squares are HOISTED out of the
PM terms: the combined planes ``a+b`` (rows), ``c+s`` and ``s-c``
(columns) are formed once per chunk on the rank-2 slabs, so each PM term
is exactly ONE broadcast add + one square --
    shared = ((a+b) + c)^2    u = (b + (c+s))^2    v = (a + (s-c))^2
-- the same adds/square ratio as the real kernel, instead of the naive
two broadcast adds per term (6 rank-3 adds per chunk down to 3).

Accumulators are initialized with the row corrections (paper §9.1):
    re0 = Sab_h       im0 = Sba_h
and the final K step halves both planes (the x2 output scale); column
corrections (Scs_k / Ssc_k) are added by the wrapper after the kernel
(algebraically identical -- Fig.2's staggered Sb_j injection).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pm_blocks import pm_chunked_reduce

__all__ = ["cpm3_matmul_kernel", "cpm3_matmul_pallas"]


def _cpm3_body(rs, cs, carry):
    """One chunk's three squares (paper eqs 32/34) on pre-broadcast slabs.

    Row slabs are (a+b, b, a); column slabs (c, c+s, s-c) -- the pairwise
    sums hoisted to rank 2 (:func:`_cpm3_rows`/:func:`_cpm3_cols`), so
    every square costs one broadcast add here (see module docstring)."""
    re, im = carry
    ab_s, b_s, a_s = rs
    c_s, cs_s, sc_s = cs
    t = ab_s + c_s                      # (c + a + b)
    shared = t * t                      # the square shared by Re and Im
    u = b_s + cs_s                      # (b + c + s)
    v = a_s + sc_s                      # (a + s - c)
    re = re + jnp.sum(shared - u * u, -2)
    im = im + jnp.sum(shared + v * v, -2)
    return re, im


def _cpm3_rows(a, b):
    return a + b, b, a


def _cpm3_cols(c, s):
    return c, c + s, s - c


def cpm3_matmul_kernel(a_ref, b_ref, c_ref, s_ref, sre_ref, sim_ref,
                       re_ref, im_ref, re_acc, im_acc, *, nk: int, kc: int):
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        re_acc[...] = jnp.broadcast_to(sre_ref[...], re_acc.shape)
        im_acc[...] = jnp.broadcast_to(sim_ref[...], im_acc.shape)

    re, im = pm_chunked_reduce(
        (re_acc[...], im_acc[...]), (a_ref, b_ref), (c_ref, s_ref),
        kc=kc, body=_cpm3_body, rows=_cpm3_rows,
        cols=_cpm3_cols)
    re_acc[...] = re
    im_acc[...] = im

    @pl.when(k_step == nk - 1)
    def _finalize():
        re_ref[...] = re_acc[...] * 0.5
        im_ref[...] = im_acc[...] * 0.5


def cpm3_matmul_pallas(a, b, c, s, sre, sim, scs, ssc, *, bm: int = 256,
                       bn: int = 256, bk: int = 128, kc: int | None = None,
                       interpret: bool = False):
    """Raw pallas_call wrapper.

    sre: (m, 1) row corrections Sab_h; sim: (m, 1) Sba_h;
    scs: (1, n) Scs_k; ssc: (1, n) Ssc_k.  Row terms are injected at
    accumulator init (the paper's Fig.1b register preload); the (1, n)
    column terms are added after the pallas_call, halved to match the
    already-halved planes (linearity -- the systolic array of Fig.2 does
    the same: "as soon as the first result starts to emerge ... we start
    to shift in Sb_j which are added and finalise the results").
    """
    m, k = a.shape
    _, n = c.shape
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    kc = bk if kc is None else kc
    assert bk % kc == 0, (bk, kc)
    nk = k // bk

    kernel = functools.partial(cpm3_matmul_kernel, nk=nk, kc=kc)
    re, im = pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
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
    )(a, b, c, s, sre, sim)
    # Column corrections, halved to match the already-halved planes.
    return re + 0.5 * scs, im + 0.5 * ssc
