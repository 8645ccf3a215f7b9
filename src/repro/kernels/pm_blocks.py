"""Shared PM machinery for the Pallas square kernels.

Every square kernel walks a K slab and accumulates ``(a + b)^2`` terms;
they differ only in the squares computed per step (one PM term for the
real kernel and for attention, three/four for CPM3/CPM4).  This module
owns the part they share -- slicing, broadcast shaping and the
homogeneous ``fori_loop`` of both PM schedules -- so each exists exactly
once.

Slabs are sliced from the VMEM *refs* (``ref[..., pl.ds(c * kc, kc)]``),
never by dynamically slicing loaded values: Mosaic has no lowering for
``dynamic_slice`` on values.  A dynamic start on the minor (lane) axis
must be provably a multiple of 128 lanes, so on the TPU a multi-chunk
walk needs a lane-aligned ``kc``; a single chunk (``kc == bk``) has a
static start and any width.  The planner (:mod:`repro.kernels.tuning`)
only proposes such plans.  The walk is a ``fori_loop`` and not a Python
loop: Mosaic gives every statically unrolled chunk its own VMEM stack
allocation, so an unrolled walk runs out of VMEM.

Two PM schedules, one per kernel; :mod:`repro.kernels.tuning` names
which kernel runs which (:func:`repro.kernels.tuning.pm_schedule`), and
plans its tiles for it.

Chunked PM blocks (:func:`pm_chunked_reduce`, :func:`pm_block_accum`)
    Every kernel but ``sq_matmul``: CPM3/CPM4, the fused conv2d, the
    grouped-expert and the paged-attention kernels.  One ``kc``-wide
    chunk broadcasts its slabs to (bm, kc, 1) x (1, kc, bn), forms the
    rank-3 PM block and reduces its second-minor axis; bn stays on the
    128-lane minor axis.  A block holds ``bm * kc * bn`` words, far
    more than the 64-vreg (256 KiB) register file at any useful ``kc``,
    so on the TPU Mosaic spills each block-wide value -- the
    lane-broadcast row operand, the sums, the squares -- to VMEM and
    reduces across sublanes.

The row walk (:func:`pm_row_walk`, ``sq_matmul`` only)
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

__all__ = ["KMN_GROUP", "pm_chunked_reduce", "pm_block_accum",
           "pm_row_walk"]

# K indices one row-walk loop iteration unrolls: one lane group of ``a``.
KMN_GROUP = 128


def pm_chunked_reduce(carry, row_refs, col_refs, *, kc: int, body,
                      rows=None, cols=None):
    """Run ``body`` over every kc-wide chunk of the K slab.

    row_refs: tuple of (..., bm, bk) refs; col_refs: tuple of (..., bk, bn)
    refs, holding values already widened to the accumulator dtype.
    ``rows``/``cols`` optionally map a chunk's loaded (..., bm, kc) /
    (..., kc, bn) slabs to the tuple ``body`` squares (pairwise sums
    formed once per chunk at rank 2, not once per PM term).
    ``body(row_slabs, col_slabs, carry) -> carry`` receives the chunk's
    slabs broadcast to (..., bm, kc, 1) and (..., 1, kc, bn); it
    computes the squares and accumulates them reduced over axis -2.
    """
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
        rs = tuple(r[..., :, :, None] for r in rs)           # (bm, kc, 1)
        cs = tuple(co[..., None, :, :] for co in cs)         # (1, kc, bn)
        return body(rs, cs, carry)

    if nc == 1:
        return chunk(0, carry)
    return jax.lax.fori_loop(0, nc, chunk, carry)


def pm_block_accum(acc, a_ref, b_ref, *, kc: int):
    """PM accumulation of one K slab in chunked PM blocks:
    ``acc + sum_k (a[..., i, k] + b[..., k, j])^2``.

    a_ref: (..., bm, bk) and b_ref: (..., bk, bn) VMEM refs holding values
    widened to the accumulator dtype; acc: the carried (..., bm, bn)
    value.  The PM step of the conv2d, grouped-expert and
    paged-attention kernels."""
    def body(rs, cs, acc):
        s = rs[0] + cs[0]                    # PE operand adders
        return acc + jnp.sum(s * s, -2)      # squarers + block reduction

    return pm_chunked_reduce(acc, (a_ref,), (b_ref,), kc=kc, body=body)


def pm_row_walk(acc, a_ref, b_ref, *, kc: int):
    """The row walk: ``acc + sum_k (a[..., i, k] + b[..., k, j])^2``.

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
