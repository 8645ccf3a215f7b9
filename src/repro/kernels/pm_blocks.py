"""Shared chunked block-PM machinery for the Pallas square kernels.

Every square kernel walks a K slab in ``kc``-wide chunks of rank-2
broadcast squaring; they differ only in the squares computed per chunk
(one PM term for the real kernel and for attention, three/four for
CPM3/CPM4).  This module owns the part they share -- chunk slicing,
broadcast shaping, the layout dispatch, and the homogeneous ``fori_loop``
-- so the layout logic exists exactly once.

Chunks are sliced from the VMEM *refs* (``ref[..., pl.ds(c * kc, kc)]``),
never by slicing loaded values: Mosaic has no lowering for
``dynamic_slice`` on values.  A dynamic start on the minor (lane) axis
must be provably a multiple of 128 lanes, so on the TPU a multi-chunk
walk needs a lane-aligned ``kc``; a single chunk (``kc == bk``) has a
static start and any width.  The planner (:mod:`repro.kernels.tuning`)
only proposes such ``"mkn"`` plans.  The chunk walk is a ``fori_loop``
and not a Python loop: Mosaic gives every statically unrolled chunk its
own VMEM stack allocation, so an unrolled walk runs out of VMEM.

Two PM-block layouts (see kernels.sq_matmul for the performance story):

``"mkn"``
    Slabs broadcast to (bm, kc, 1) x (1, kc, bn); ``body`` reduces the
    second-minor axis.  bn stays on the 128-lane minor axis -- the
    TPU-native schedule.
``"mnk"``
    Column chunks are transposed; slabs broadcast to (bm, 1, kc) x
    (1, bn, kc); ``body`` reduces the minor axis, which fuses into a
    dot-product-shaped loop nest -- the CPU/interpret schedule.

Refs may carry leading batch dims (the batch-folded matmul): they ride
through every slice and broadcast unchanged.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["PM_LAYOUTS", "pm_chunked_reduce"]

PM_LAYOUTS = ("mkn", "mnk")


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
    if pm_layout not in PM_LAYOUTS:
        raise ValueError(f"unknown pm_layout {pm_layout!r}; "
                         f"expected one of {PM_LAYOUTS}")
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
