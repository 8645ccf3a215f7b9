"""Square-based real matrix multiplication (paper §3).

    c_ij = 1/2 ( Sab_ij + Sa_i + Sb_j )
    Sab_ij = sum_k (a_ik + b_kj)^2
    Sa_i   = -sum_k a_ik^2          Sb_j = -sum_k b_kj^2

Execution modes
---------------
``standard``
    Plain MXU matmul (the multiplier baseline the paper compares against).
``square_virtual``
    *Beyond-paper production mode.*  Produces the square-form result (the
    x2-scaled accumulator, corrections applied, final halving) by routing the
    bulk contraction through the MXU using the identity
    ``Sab = -Sa - Sb + 2 A@B``.  Numerically identical to ``standard`` up to
    reassociation, with O(MN + M + N) extra elementwise work - asymptotically
    free.  This is the mode the distributed framework runs at scale: the
    square-form *contract* (scale, correction injection points) is preserved
    so that models validated here drop onto squarer-based ASICs unchanged.
``square_exact``
    Faithful datapath emulation: every (i,k,j) square is executed, exactly as
    the PE array of paper Fig.2 computes it.  O(M*K*N) memory when vectorized
    -- small operands only (tests / verification).
``square_scan``
    Same arithmetic as ``square_exact`` but streamed over K blocks with
    ``lax.scan`` (O(M*N) live memory) -- mirrors how operands stream through
    the systolic array cycle by cycle.
``square_pallas``
    The Pallas TPU kernel emulation (kernels/sq_matmul.py), explicit
    HBM->VMEM tiling.  Validated in interpret mode on CPU.

All square modes share correction/halving code so the algebra is written once.

This module is the rank-2 contraction engine (``a[..., K] @ b[K, N]``).
Model code does NOT call it directly: every model contraction -- dense
layers, attention scores, batched MoE expert GEMMs, recurrent state mixes,
the vocab GEMM -- goes through the einsum-shaped dispatcher
:func:`repro.core.einsum.fs_einsum`, which canonicalizes arbitrary
two-operand specs to (batch, M, K, N) form, generalizes the correction
algebra here to batched contractions, and falls back to these kernels for
the unbatched case.  ``matmul_mode`` (or a per-site
``ContractionPolicy``) therefore switches the *whole model*, not just the
dense layers.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import squares as sq
from repro.core.prepared import PreparedOperand

__all__ = ["matmul", "pm_matmul_exact", "pm_matmul_scan", "pm_matmul_virtual",
           "MODES", "set_default_mode", "get_default_mode"]

MODES = ("standard", "square_virtual", "square_exact", "square_scan",
         "square_pallas")

_DEFAULT_MODE = "standard"


def set_default_mode(mode: str) -> None:
    global _DEFAULT_MODE
    if mode not in MODES:
        raise ValueError(f"unknown matmul mode {mode!r}; expected one of {MODES}")
    _DEFAULT_MODE = mode


def get_default_mode() -> str:
    return _DEFAULT_MODE


def _standard(a, b, preferred):
    return jnp.matmul(a, b, preferred_element_type=preferred)


def pm_matmul_virtual(a, b, preferred=None):
    """Square-form result through the MXU (see module docstring).

    Computes the x2-scaled square-form accumulator ``Sab + Sa + Sb`` using
    ``Sab = -Sa - Sb + 2 A@B`` -- the corrections cancel algebraically, so we
    keep only the scale carry: ``acc2 = 2 * (A @ B)`` then halve.  The x2
    carry and final halving are retained (not symbolically folded by us) so
    the numeric contract matches the paper's architectures bit-for-bit in
    integer arithmetic.
    """
    preferred = preferred or sq.accum_dtype(a.dtype)
    acc2 = _standard(a, b, preferred)
    acc2 = acc2 + acc2  # the paper's architectures accumulate 2*c_ij
    return sq.halve(acc2)


def pm_matmul_exact(a, b):
    """Vectorized faithful emulation: materializes the (..., M, K, N) PM cube."""
    acc_dt = sq.accum_dtype(a.dtype)
    aw = a.astype(acc_dt)
    bw = b.astype(acc_dt)
    sab = jnp.sum(sq.square(aw[..., :, None] + bw[None, :, :]), axis=-2)
    sa = sq.row_correction(aw, axis=-1)          # (..., M)
    sb = sq.col_correction(bw, axis=0)           # (N,)
    acc2 = sab + sa[..., None] + sb
    return sq.halve(acc2)


def pm_matmul_scan(a, b, block: int = 16):
    """Streamed faithful emulation: scan over K blocks (systolic streaming).

    The accumulator is *initialized with the corrections* ``Sa_i + Sb_j``,
    exactly like the paper's Fig.1b / Fig.5b PEs, then rank-2 PM blocks
    stream in: each scan step contracts a ``block``-wide K slab in ONE
    broadcast squaring pass over the (..., M, N, block) cube, reduced on
    the *minor* axis (``b`` transposed once, outside the scan) -- the
    dot-product-shaped loop nest XLA CPU vectorizes best.  ``block`` trades the live
    cube's footprint against scan-step count; ~16 keeps it inside the
    cache working set at model-sized (256^3) shapes (measured ~19x over
    the old full-K-slab (M, 128, N) layout: 41 ms -> 2.2 ms).
    """
    acc_dt = sq.accum_dtype(a.dtype)
    aw = a.astype(acc_dt)
    bw = b.astype(acc_dt)
    k = aw.shape[-1]
    block = max(1, min(block, k))
    pad = (-k) % block
    if pad:
        # zero padding adds (0+0)^2 terms and zero corrections: exact.
        aw = jnp.pad(aw, [(0, 0)] * (aw.ndim - 1) + [(0, pad)])
        bw = jnp.pad(bw, [(0, pad), (0, 0)])
    nblk = aw.shape[-1] // block
    n = bw.shape[1]
    sa = sq.row_correction(aw, axis=-1)
    sb = sq.col_correction(bw, axis=0)
    init = sa[..., None] + sb                    # accumulator init = Sa_i + Sb_j
    init = jnp.broadcast_to(init, (*aw.shape[:-1], n)).astype(acc_dt)

    a_blocks = jnp.moveaxis(aw.reshape(*aw.shape[:-1], nblk, block), -2, 0)
    bt = bw.T                                    # (N, K), transposed once
    b_blocks = jnp.moveaxis(bt.reshape(n, nblk, block), -2, 0)

    def step(acc, ab):
        ablk, bblk = ab                          # (..., M, block), (N, block)
        s = ablk[..., :, None, :] + bblk[None, :, :]   # (..., M, N, block)
        return acc + jnp.sum(s * s, axis=-1), None

    acc2, _ = jax.lax.scan(step, init, (a_blocks, b_blocks))
    return sq.halve(acc2)


def pm_matmul_approx(a, b, *, drop_bits: int = 4, block: int = 128):
    """Square-based matmul with APPROXIMATE squarers (paper conclusion).

    Same streaming structure as :func:`pm_matmul_scan` but every squaring --
    PM terms and corrections alike -- runs through
    :func:`squares.square_approx`, modelling a datapath built from truncated
    squarer circuits.  Error characterized in benchmarks/approx.py.
    """
    acc_dt = sq.accum_dtype(a.dtype)
    aw = a.astype(acc_dt)
    bw = b.astype(acc_dt)
    k = aw.shape[-1]
    pad = (-k) % block
    if pad:
        aw = jnp.pad(aw, [(0, 0)] * (aw.ndim - 1) + [(0, pad)])
        bw = jnp.pad(bw, [(0, pad), (0, 0)])
    nblk = aw.shape[-1] // block
    sqx = lambda t: sq.square_approx(t, drop_bits=drop_bits)
    sa = -jnp.sum(sqx(aw), axis=-1)
    sb = -jnp.sum(sqx(bw), axis=0)
    init = jnp.broadcast_to(sa[..., None] + sb,
                            (*aw.shape[:-1], bw.shape[1])).astype(acc_dt)
    a_blocks = jnp.moveaxis(aw.reshape(*aw.shape[:-1], nblk, block), -2, 0)
    b_blocks = bw.reshape(nblk, block, bw.shape[1])

    def step(acc, ab):
        ablk, bblk = ab
        term = jnp.sum(sqx(ablk[..., :, None] + bblk[None, :, :]), axis=-2)
        return acc + term.astype(acc.dtype), None

    acc2, _ = jax.lax.scan(step, init, (a_blocks, b_blocks))
    return sq.halve(acc2)


def matmul(a, b, *, mode: Optional[str] = None, preferred=None):
    """Dense contraction ``a[..., K] @ b[K, N]`` under a fair-square mode.

    ``b`` may be a matmul :class:`repro.core.prepared.PreparedOperand`
    (weight-stationary amortization, see :mod:`repro.core.prepared`): the
    multiplier/virtual/exact/scan modes use its raw source (bit-identical
    to raw dispatch), ``square_pallas`` reuses the prepared column slab.
    The ``square_pallas`` route itself (kernel vs the MXU-form virtual
    fallback below the kernel-overhead floor) is resolved by
    :func:`repro.kernels.routing.select_matmul_route`.
    """
    prep = b if isinstance(b, PreparedOperand) else None
    if prep is not None:
        b_shape = ((prep.shape[-1], prep.shape[-2]) if prep.transposed
                   else prep.shape)
        # materialized lazily: the pallas route never touches the source
        b_arr = lambda: (jnp.swapaxes(prep.source, -1, -2)
                         if prep.transposed else prep.source)
    else:
        b_shape = b.shape
        b_arr = lambda: b
    if a.shape[-1] != b_shape[0]:
        raise ValueError(f"contraction mismatch: {a.shape} @ "
                         f"{tuple(b_shape)}")
    if len(b_shape) != 2:
        raise ValueError(f"rhs must be 2D (K, N), got {tuple(b_shape)}")
    mode = mode or _DEFAULT_MODE
    if mode == "standard":
        out = _standard(a, b_arr(), preferred or sq.accum_dtype(a.dtype))
    elif mode == "square_virtual":
        out = pm_matmul_virtual(a, b_arr(), preferred)
    elif mode == "square_exact":
        out = pm_matmul_exact(a, b_arr())
    elif mode == "square_scan":
        out = pm_matmul_scan(a, b_arr())
    elif mode == "square_pallas":
        from repro.kernels import ops as kops    # lazy: avoid import cycle
        from repro.kernels import routing
        import numpy as np
        m_rows = int(np.prod(a.shape[:-1], dtype=np.int64))
        k = a.shape[-1]
        n = b_shape[-1]
        route = routing.select_matmul_route(m_rows, n, k, dtype=a.dtype)
        if route.name == "virtual":
            out = pm_matmul_virtual(a, b_arr(), preferred)
        else:
            out = kops.sq_matmul(a, b)
    else:
        raise ValueError(f"unknown matmul mode {mode!r}; expected one of {MODES}")
    return out
