"""Pallas TPU kernel: fused window-streaming 2D square-convolution (§5.1).

The paper's §5.1 2D engine slides an (Mk, Nk) window over the input and
pushes every window element through the PM datapath -- square of ``x + w``
minus the shared ``x^2``, plus the precomputed kernel correction ``Sw``.
The previous implementation reduced this to a matmul by **materializing**
the im2col patch tensor (every input pixel copied ``kh*kw`` times into an
O(oh*ow*kh*kw) HBM buffer) before calling ``sq_matmul``.  This kernel is
the fused form: that patch tensor never exists.

Dataflow (window streaming, implicit GEMM)
------------------------------------------
Outputs are tiled over a 5D grid ``(batch, oh/bh, ow/bw, cout/bf,
cin/bk)``; the input-channel axis is the grid minor ("arbitrary")
reduction axis, exactly like ``sq_matmul``'s K axis.  One grid step walks
the ``kh*kw`` taps in a ``fori_loop``:

- each tap reads its shifted (and, for sh/sv > 1, strided) view of the
  tile's input window straight from the VMEM-resident input plane --
  each input element reaches the step from VMEM, instead of being
  duplicated ``kh*kw`` times in HBM;
- the (bh*bw, bk) view (and the tap's (bk, bf) filter block) is staged
  in a VMEM scratch slab -- the tile-local
  im2col that implicit-GEMM convolutions form in SRAM, bounded by the
  tile size, not the image size -- and routed through the chunked
  block-PM contraction (:func:`repro.kernels.pm_blocks.pm_block_accum`)
  against that tap's (bk, bf) filter block: ``kc``-wide rank-2 broadcast
  squaring in (bm, kc, bf) PM blocks, accumulating into a VMEM scratch tile
  that is live across the whole channel walk;
- the data-side correction (the view's ``-x^2`` terms, shared by all
  ``bf`` filters of the step) is folded in one rank-2 pass -- O(M*K),
  not O(M*K*N).

The taps loop rather than unroll: Mosaic gives every unrolled PM block
its own VMEM allocation, and nine of them overrun the scoped VMEM.

The accumulator is initialized with the per-filter kernel correction
``Sw_f = -sum_{c,i,j} w^2`` at the first channel step (the paper's
"initialise the register" move, Fig.1b/Fig.5b) and the final channel step
applies the paper's right shift (x0.5, arithmetic shift on int paths).

Zero padding is exact by construction: a padded ``x = 0`` contributes
``(0 + w)^2 - 0^2 = w^2``, exactly cancelled by the ``-w^2`` the ``Sw``
init already carries for that tap.  The same argument covers padded
channels and padded filters (both sides zero), so the wrapper in
:mod:`repro.kernels.ops` pads freely to tile multiples.

The input block keeps the full (padded) spatial plane of one batch
element resident per step (windows of adjacent output tiles overlap, so
spatial blocking would re-DMA the halos); at CNN-layer scales a
channel-sliced plane slab is a few hundred KB and on real TPU silicon it
is double-buffered by the pipeline.  Strided output (sh, sv > 1) reads
strided views of the same resident plane.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pm_blocks import pm_block_accum

__all__ = ["sq_conv2d_kernel", "sq_conv2d_pallas"]


def sq_conv2d_kernel(x_ref, w_ref, sw_ref, out_ref, acc_ref, slab_ref,
                     wtap_ref, *, nc: int, kc: int, bh: int, bw: int,
                     sh: int, sv: int, is_int: bool):
    """One (b, i, j, f, c) grid step of the fused 2D square-convolution.

    x_ref: (1, Hp, Wp, bk) this batch element's plane, channel-sliced;
    w_ref: (kh, kw, bk, bf) tap block; sw_ref: (1, bf) filter corrections;
    out_ref: (1, bh, bw, bf); VMEM scratch acc_ref: (bh*bw, bf), and
    slab_ref: (bh*bw, bk) / wtap_ref: (bk, bf), one tap's operands staged
    as plain 2D refs so the chunk walk slices them with provably aligned
    starts.
    """
    i = pl.program_id(1)                 # output-row tile
    j = pl.program_id(2)                 # output-col tile
    c = pl.program_id(4)                 # input-channel step (reduction)
    kh, kw, bk, bf = w_ref.shape
    bm = bh * bw

    @pl.when(c == 0)
    def _init():
        # Accumulator init = Sw_f (paper eq 14 Sw): the per-filter kernel
        # correction, broadcast to every output pixel of the tile.
        acc_ref[...] = jnp.broadcast_to(sw_ref[...], (bm, bf))

    def tap(t, acc):
        di, dj = t // kw, t % kw
        # This tap's shifted view of the tile's input window.
        xs = x_ref[0, pl.ds(i * (bh * sh) + di, bh, sh),
                   pl.ds(j * (bw * sv) + dj, bw, sv), :]   # (bh, bw, bk)
        slab_ref[...] = xs.reshape(bm, bk)
        wtap_ref[...] = w_ref[di, dj]
        acc = pm_block_accum(acc, slab_ref, wtap_ref, kc=kc)
        # Data-side correction (-x^2, paper eq 14 Sx): rank-2, shared by
        # all bf filters of the step -- O(M*K), not O(M*K*N).
        a = slab_ref[...]
        return acc - jnp.sum(a * a, axis=1, keepdims=True)

    acc_ref[...] = jax.lax.fori_loop(0, kh * kw, tap, acc_ref[...])

    @pl.when(c == nc - 1)
    def _finalize():
        accf = acc_ref[...]
        if is_int:
            res = jax.lax.shift_right_arithmetic(accf, jnp.ones_like(accf))
        else:
            res = accf * 0.5                        # the final right shift
        out_ref[...] = res.reshape(1, bh, bw, bf)


def sq_conv2d_pallas(x, w, sw, *, ohp: int, owp: int, bh: int, bw: int,
                     bk: int, bf: int, kc: int | None = None,
                     stride: tuple[int, int] = (1, 1),
                     interpret: bool = False):
    """Raw pallas_call wrapper for the fused 2D square-convolution.

    Operands must be pre-widened to the accumulator dtype and pre-padded
    (see kernels.ops): x (B, Hp, Wp, Cp) channels-last, w (kh, kw, Cp, Np)
    taps-major, sw (1, Np) per-filter ``-sum w^2`` corrections.  ``ohp`` /
    ``owp`` are the padded output extents (multiples of bh/bw); the padded
    input must cover every window: ``Hp >= (ohp-1)*sh + kh``.  ``kc``
    chunks each tap's bk-wide channel reduction and must divide ``bk``
    (defaults to one chunk).
    """
    nb, Hp, Wp, Cp = x.shape
    kh, kw, Cp2, Np = w.shape
    sh, sv = stride
    assert Cp == Cp2 and sw.shape == (1, Np), (x.shape, w.shape, sw.shape)
    assert ohp % bh == 0 and owp % bw == 0, (ohp, owp, bh, bw)
    assert Cp % bk == 0 and Np % bf == 0, (Cp, Np, bk, bf)
    assert Hp >= (ohp - 1) * sh + kh and Wp >= (owp - 1) * sv + kw, \
        (Hp, Wp, ohp, owp, stride, kh, kw)
    kc = bk if kc is None else kc
    assert bk % kc == 0, (bk, kc)
    nc = Cp // bk
    is_int = jnp.issubdtype(x.dtype, jnp.integer)

    kernel = functools.partial(sq_conv2d_kernel, nc=nc, kc=kc, bh=bh, bw=bw,
                               sh=sh, sv=sv, is_int=is_int)
    return pl.pallas_call(
        kernel,
        grid=(nb, ohp // bh, owp // bw, Np // bf, nc),
        in_specs=[
            # full spatial plane, channel-sliced (windows overlap tiles)
            pl.BlockSpec((1, Hp, Wp, bk), lambda b, i, j, f, c: (b, 0, 0, c)),
            pl.BlockSpec((kh, kw, bk, bf), lambda b, i, j, f, c: (0, 0, c, f)),
            pl.BlockSpec((1, bf), lambda b, i, j, f, c: (0, f)),
        ],
        out_specs=pl.BlockSpec((1, bh, bw, bf),
                               lambda b, i, j, f, c: (b, i, j, f)),
        out_shape=jax.ShapeDtypeStruct((nb, ohp, owp, Np), x.dtype),
        scratch_shapes=[pltpu.VMEM((bh * bw, bf), x.dtype),
                        pltpu.VMEM((bh * bw, bk), x.dtype),
                        pltpu.VMEM((bk, bf), x.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w, sw)
