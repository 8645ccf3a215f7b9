"""Shared PM machinery for the Pallas square kernels.

Every square kernel walks a K slab and accumulates ``(a + b)^2`` terms;
they differ only in the squares computed per step (one PM term for the
real kernel and for attention, three/four for CPM3/CPM4).  This module
owns the part they share -- slicing, broadcast shaping, the layout
dispatch, and the homogeneous ``fori_loop`` -- so the layout logic exists
exactly once.

Slabs are sliced from the VMEM *refs* (``ref[..., pl.ds(c * kc, kc)]``),
never by dynamically slicing loaded values: Mosaic has no lowering for
``dynamic_slice`` on values.  A dynamic start on the minor (lane) axis
must be provably a multiple of 128 lanes, so on the TPU a multi-chunk
walk needs a lane-aligned ``kc``; a single chunk (``kc == bk``) has a
static start and any width.  The planner (:mod:`repro.kernels.tuning`)
only proposes such plans.  The walk is a ``fori_loop`` and not a Python
loop: Mosaic gives every statically unrolled chunk its own VMEM stack
allocation, so an unrolled walk runs out of VMEM.

Three PM layouts (see kernels.sq_matmul for the performance story).  The
two *chunked* layouts (:data:`CHUNKED_LAYOUTS`, :func:`pm_chunked_reduce`)
form a rank-3 PM block per ``kc``-wide chunk:

``"mkn"``
    Slabs broadcast to (bm, kc, 1) x (1, kc, bn); ``body`` reduces the
    second-minor axis.  bn stays on the 128-lane minor axis.  The layout
    of every TPU kernel but ``sq_matmul``: the paged-attention and
    grouped-expert kernels keep it, since their tiles were tuned and
    timed on it and the row walk below never was.
``"mnk"``
    Column chunks are transposed; slabs broadcast to (bm, 1, kc) x
    (1, bn, kc); ``body`` reduces the minor axis, which fuses into a
    dot-product-shaped loop nest -- the CPU/interpret schedule.

A chunk's block holds ``bm * kc * bn`` words, far more than the 64-vreg
(256 KiB) register file at any useful ``kc``, so on the TPU Mosaic spills
each block-wide value -- the lane-broadcast row operand, the sums, the
squares -- to VMEM and reduces across sublanes.

``"kmn"`` (:func:`pm_row_walk`, ``sq_matmul`` only)
    No block at all: the (bm, bn) partial sum lives in vregs while K is
    walked one index at a time, ``part += (a[:, k] + b[k, :])^2`` with
    ``a``'s column broadcast over lanes and ``b``'s row over sublanes.
    The reduction is over K itself, a major axis, so it is plain vreg
    adds.  ``kc`` is the lane-aligned group one ``fori_loop`` iteration
    unrolls; each group's terms sum into a fresh partial that is then
    added to the carried accumulator, so the f32 rounding is blocked the
    way the chunked walk blocks it.

Refs may carry leading batch dims (the batch-folded matmul): they ride
through every slice and broadcast unchanged.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["PM_LAYOUTS", "CHUNKED_LAYOUTS", "KMN_GROUP",
           "pm_chunked_reduce", "pm_row_walk"]

CHUNKED_LAYOUTS = ("mkn", "mnk")
PM_LAYOUTS = CHUNKED_LAYOUTS + ("kmn",)
# K indices one "kmn" loop iteration unrolls: one lane group of ``a``.
KMN_GROUP = 128


def pm_chunked_reduce(carry, row_refs, col_refs, *, kc: int, pm_layout: str,
                      body, rows=None, cols=None):
    """Run ``body`` over every kc-wide chunk of the K slab.

    row_refs: tuple of (..., bm, bk) refs; col_refs: tuple of (..., bk, bn)
    refs, holding values already widened to the accumulator dtype.
    ``rows``/``cols`` optionally map a chunk's loaded (..., bm, kc) /
    (..., kc, bn) slabs to the tuple ``body`` squares (pairwise sums
    formed once per chunk at rank 2, not once per PM term).
    ``body(row_slabs, col_slabs, axis, carry) -> carry`` receives the
    chunk's slabs pre-broadcast (layouts above) and the reduction axis;
    it computes the squares and accumulates.
    """
    if pm_layout not in CHUNKED_LAYOUTS:
        raise ValueError(f"unknown chunked pm_layout {pm_layout!r}; "
                         f"expected one of {CHUNKED_LAYOUTS}")
    nc = row_refs[0].shape[-1] // kc

    def chunk(c, carry):
        if nc == 1:                    # whole refs: no slice to align
            rs = tuple(r[...] for r in row_refs)
            cs = tuple(co[...] for co in col_refs)
        else:
            start = pl.multiple_of(c * kc, kc)
            rs = tuple(r[..., pl.ds(start, kc)] for r in row_refs)
            cs = tuple(co[..., pl.ds(start, kc), :] for co in col_refs)
        rs = rows(*rs) if rows is not None else rs
        cs = cols(*cs) if cols is not None else cs
        if pm_layout == "mkn":
            rs = tuple(r[..., :, :, None] for r in rs)       # (bm, kc, 1)
            cs = tuple(co[..., None, :, :] for co in cs)     # (1, kc, bn)
            return body(rs, cs, -2, carry)
        rs = tuple(r[..., :, None, :] for r in rs)           # (bm, 1, kc)
        cs = tuple(jnp.swapaxes(co, -1, -2)[..., None, :, :]
                   for co in cs)                             # (1, bn, kc)
        return body(rs, cs, -1, carry)

    if nc == 1:
        return chunk(0, carry)
    return jax.lax.fori_loop(0, nc, chunk, carry)


def pm_row_walk(acc, a_ref, b_ref, *, kc: int):
    """The "kmn" walk: ``acc + sum_k (a[..., i, k] + b[..., k, j])^2``.

    a_ref: (..., bm, bk) and b_ref: (..., bk, bn) VMEM refs holding values
    widened to the accumulator dtype; acc: the carried (..., bm, bn)
    value.  ``kc`` (dividing ``bk``) is the group one loop iteration
    unrolls: its (bm, kc) row slab is loaded once, ``b`` is loaded one
    8-row sublane tile at a time, and each of the ``kc`` indices adds one
    lane-broadcast column plus sublane-broadcast row, squared, to the
    group's partial.
    """
    ng = a_ref.shape[-1] // kc

    def group(g, acc):
        start = 0 if ng == 1 else pl.multiple_of(g * kc, kc)
        a = a_ref[..., pl.ds(start, kc)]                 # (..., bm, kc)
        part = None
        for t in range(0, kc, 8):
            rows = min(8, kc - t)
            st = start + t
            if ng > 1 and kc % 8 == 0:
                st = pl.multiple_of(st, 8)
            b8 = b_ref[..., pl.ds(st, rows), :]          # (..., rows, bn)
            for r in range(rows):
                s = a[..., :, t + r:t + r + 1] + b8[..., r:r + 1, :]
                part = s * s if part is None else part + s * s
        return acc + part

    if ng == 1:
        return group(0, acc)
    return jax.lax.fori_loop(0, ng, group, acc)
