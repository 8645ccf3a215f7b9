"""Jit'd public wrappers around the Pallas kernels.

Handles: dtype widening (paper's bit-growth rules), padding to tile
multiples, correction-term precomputation, tile planning (via
kernels.tuning -- cost-model ranked), and the
interpret-mode fallback on CPU (kernels target TPU; interpret=True executes
the kernel body in Python for bit-faithful validation).

The matmul prep pipeline is split into **prepare/execute halves** (the
paper's weight-stationary contract, §4-§5): :func:`prepare_matmul_rhs` /
:func:`prepare_conv2d_weights` perform the constant-operand work (widen,
column corrections, canonical layout, tile padding) and the ``_exec``
impls stream activations against the result.  Raw-array calls run
prepare-then-execute per call; passing a
:class:`repro.core.prepared.PreparedOperand` (built once via
:func:`repro.core.prepared.prepare_operand`) reuses the prepared half, so
both entry styles share one code path and are bit-identical by
construction.  Corrections are computed BEFORE padding (padded zeros
contribute zero anyway).  Each kernel runs its one PM schedule on every
backend (:func:`repro.kernels.tuning.pm_schedule`): interpret mode runs
the schedule the chip runs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import conv as conv_core
from repro.core import cost_model as cm
from repro.core import squares as sq
from repro.core.prepared import PreparedOperand
from repro.kernels import tuning
from repro.kernels.sq_matmul import sq_matmul_pallas, sq_matmul_batched_pallas
from repro.kernels.sq_grouped_matmul import sq_grouped_matmul_pallas
from repro.kernels.cpm3_matmul import cpm3_matmul_pallas
from repro.kernels.cpm4_matmul import cpm4_matmul_pallas
from repro.kernels.sq_conv import sq_conv_pallas
from repro.kernels.sq_conv2d import sq_conv2d_pallas

__all__ = ["sq_matmul", "sq_grouped_matmul", "cpm3_matmul", "cpm4_matmul", "sq_conv", "sq_conv2d",
           "sq_conv2d_im2col", "sq_conv2d_routed", "prepare_matmul_rhs",
           "prepare_conv2d_weights", "default_interpret"]

# Row-tile extent the batch-fold schedule targets per grid step: fb is
# picked so fb * bm rows of PM work amortize one step's issue overhead.
FOLD_ROW_TARGET = 256


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x, mult, axis):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _widen(*ts):
    """Widen operands to the shared accumulator dtype (bit-growth rules)."""
    acc = sq.accum_dtype(ts[0].dtype)
    return tuple(t.astype(acc) for t in ts)


def _pad_operands(plan, row_ops, col_ops, row_corrs, col_corrs):
    """Pad (m, k) row operands, (k, n) col operands and their (m, 1)/(1, n)
    correction vectors to the plan's tile multiples."""
    row_ops = [_pad_to(_pad_to(t, plan.bm, 0), plan.bk, 1) for t in row_ops]
    col_ops = [_pad_to(_pad_to(t, plan.bk, 0), plan.bn, 1) for t in col_ops]
    row_corrs = [_pad_to(t, plan.bm, 0) for t in row_corrs]
    col_corrs = [_pad_to(t, plan.bn, 1) for t in col_corrs]
    return row_ops, col_ops, row_corrs, col_corrs


# --------------------------------------------------------------------------
# Prepare halves (the constant-operand, weight-stationary work)
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("plan", "acc_dtype"))
def prepare_matmul_rhs(b, plan, acc_dtype):
    """The column-operand half of the matmul prep pipeline.

    b: raw (k, n) -- or batched (B, k, n) -- column operand.  Widens to
    ``acc_dtype``, computes the ``Sb`` column correction BEFORE padding,
    pads both to the plan's (bk, bn) tile multiples.  Returns
    ``(bw, sb)``: the kernel-ready column slab and its correction vector.
    This is the work :func:`repro.core.prepared.prepare_operand` amortizes
    across calls; raw-array dispatch runs it per call on the same code
    path.  It is one compiled program, dispatched on its own by both:
    inlined into the execute half, XLA would fuse the correction's
    reduction differently and the two styles would differ in the last
    bits.
    """
    bw = b.astype(acc_dtype)
    sb = sq.col_correction(bw, axis=-2)[..., None, :]       # (..., 1, n)
    bw = _pad_to(_pad_to(bw, plan.bk, -2), plan.bn, -1)
    sb = _pad_to(sb, plan.bn, -1)
    return bw, sb


def prepare_conv2d_weights(w4, acc_dtype):
    """The filter half of the conv2d prep pipeline.

    w4: raw (cout, cin, kh, kw) filters.  Returns ``(wt, sw, wmat, cmat)``:
    the widened channels-last plane stack (kh, kw, cin, cout) the fused
    kernel streams, its per-filter correction ``Sw`` (1, cout), the
    widened (cin*kh*kw, cout) im2col filter matrix, and that matrix's
    column correction.  Both conv routes draw from one prepared form.
    """
    ww = w4.astype(acc_dtype)
    cout = ww.shape[0]
    sw = -jnp.sum(sq.square(ww), axis=(1, 2, 3))[None, :]   # (1, cout)
    wt = jnp.transpose(ww, (2, 3, 1, 0))                    # (kh, kw, C, N)
    wmat = ww.reshape(cout, -1).T                           # (K, cout)
    cmat = sq.col_correction(wmat, axis=0)[None, :]
    return wt, sw, wmat, cmat


def _match_rhs_padding(prep: PreparedOperand, plan, acc_dtype):
    """Adapt a prepared column operand to the execution plan.

    When the prepared padding multiples match the plan's (the common case:
    prepare and execute resolved the same (bk, bn)), the canon/corr arrays
    are used as-is.  Otherwise the zero padding is sliced off and re-laid
    to the plan's multiples -- still skipping the O(K*N) widen/correct
    work, and bit-identical to raw dispatch because padding only appends
    exact zeros.  Returns None on a dtype mismatch (caller falls back to
    the raw source)."""
    if prep.canon.dtype != jnp.dtype(acc_dtype):
        return None
    k, n = prep.shape[-2], prep.shape[-1]
    if prep.transposed:
        k, n = n, k
    kt = k + (-k) % plan.bk
    nt = n + (-n) % plan.bn
    bw, sb = prep.canon, prep.corr
    if bw.shape[-2:] == (kt, nt):
        return bw, sb
    bw = bw[..., :k, :n]
    sb = sb[..., :, :n]
    return (_pad_to(_pad_to(bw, plan.bk, -2), plan.bn, -1),
            _pad_to(sb, plan.bn, -1))


# --------------------------------------------------------------------------
# Real square-based matmul
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n", "plan", "interpret"))
def _sq_matmul_exec(a, bw, sb, n, plan, interpret):
    """Execute half: stream the (m, k) row operand against a prepared
    (padded, widened, corrected) column operand."""
    aw = a.astype(bw.dtype)
    m = aw.shape[0]
    sa = sq.row_correction(aw, axis=-1)[:, None]            # (m, 1)
    aw = _pad_to(_pad_to(aw, plan.bm, 0), plan.bk, 1)
    sa = _pad_to(sa, plan.bm, 0)
    out = sq_matmul_pallas(aw, bw, sa, sb, bm=plan.bm, bn=plan.bn,
                           bk=plan.bk, kc=plan.kc, interpret=interpret)
    return out[:m, :n]


def _sq_matmul_impl(a, b, plan, interpret):
    """Raw-array path: prepare, then execute."""
    acc = sq.accum_dtype(a.dtype)
    bw, sb = prepare_matmul_rhs(b, plan, acc)
    return _sq_matmul_exec(a, bw, sb, b.shape[-1], plan, interpret)


@functools.partial(jax.jit, static_argnames=("n", "fb", "plan", "interpret"))
def _sq_matmul_batched_exec(a, bw, sb, n, fb, plan, interpret):
    aw = a.astype(bw.dtype)
    nb, m, k = aw.shape
    sa = sq.row_correction(aw, axis=-1)[..., None]          # (nb, m, 1)
    aw = _pad_to(_pad_to(aw, plan.bm, 1), plan.bk, 2)
    sa = _pad_to(sa, plan.bm, 1)
    if fb > 1:
        # zero batch elements are exact no-ops (0 PM terms, 0 corrections)
        aw, bw, sa, sb = (_pad_to(t, fb, 0) for t in (aw, bw, sa, sb))
    out = sq_matmul_batched_pallas(aw, bw, sa, sb, bm=plan.bm, bn=plan.bn,
                                   bk=plan.bk, kc=plan.kc, fb=fb,
                                   interpret=interpret)
    return out[:nb, :m, :n]


def _sq_matmul_batched_impl(a, b, fb, plan, interpret):
    acc = sq.accum_dtype(a.dtype)
    bw, sb = prepare_matmul_rhs(b, plan, acc)
    return _sq_matmul_batched_exec(a, bw, sb, b.shape[-1], fb, plan,
                                   interpret)


def _pick_fb(plan, nb: int) -> int:
    """Batch-fold width: enough elements per grid step that the folded row
    tile reaches ~FOLD_ROW_TARGET rows (the small-(M, N) large-B regime;
    see kernels.routing), holding no more accumulator vregs than one
    row-walk tile may."""
    fb = min(FOLD_ROW_TARGET // max(1, plan.bm),
             cm.KMN_ACC_VREGS // cm.kmn_acc_vregs(plan.bm, plan.bn))
    return max(1, min(nb, fb))


def sq_matmul(a, b, *, bm: int | None = None, bn: int | None = None,
              bk: int | None = None, kc: int | None = None,
              interpret: bool | None = None, fold: bool = False):
    """Square-based matmul via the Pallas systolic-emulation kernel.

    a: (m, k), b: (k, n); any float or int8/int16 dtype; returns the
    accumulator dtype (f32 for floats, int32 for small ints).  Tile sizes
    default to the kernels.tuning planner; explicit values are honored
    (clamped to the operand and alignment granules).

    ``b`` may be a :class:`repro.core.prepared.PreparedOperand` (built via
    :func:`repro.core.prepared.prepare_operand`): the widen/correct/pad
    half is then reused instead of recomputed -- bit-identical to the raw
    path, measurably faster under eager/interpret execution (weights are
    the paper's stationary operand).

    Batched form: a (B, m, k) with b (B, k, n) runs the batched kernel
    (leading batch grid axis) -- the einsum dispatcher's canonical
    (B, M, K) @ (B, K, N) shape.  ``fold=True`` additionally folds a block
    of batch elements into each grid step's row tile (the
    small-(M, N)-large-B route of :mod:`repro.kernels.routing`).  A
    rank>2 ``a`` against a 2D ``b`` keeps the dense-layer convention
    (leading dims collapse to rows).

    >>> import numpy as np, jax.numpy as jnp
    >>> from repro.kernels import ops
    >>> a = jnp.asarray(np.arange(6.0, dtype=np.float32).reshape(2, 3))
    >>> b = jnp.asarray(np.ones((3, 4), np.float32))
    >>> out = ops.sq_matmul(a, b)            # squares only, exact contract
    >>> bool(np.allclose(out, a @ b, atol=1e-5))
    True
    >>> ai = jnp.asarray([[3, -7]], jnp.int8)
    >>> bi = jnp.asarray([[5], [2]], jnp.int8)
    >>> int(ops.sq_matmul(ai, bi)[0, 0])     # int paths are bit-exact
    1
    """
    interpret_r = default_interpret() if interpret is None else interpret
    prep = b if isinstance(b, PreparedOperand) else None
    if prep is not None:
        if prep.kind not in ("matmul", "matmul_batched"):
            raise ValueError(f"sq_matmul got a {prep.kind!r} "
                             f"PreparedOperand; expected a matmul one")
        b_shape = (prep.shape[:-2] + (prep.shape[-1], prep.shape[-2])
                   if prep.transposed else prep.shape)
    else:
        b_shape = b.shape
    if len(b_shape) == 3:
        if a.ndim != 3 or a.shape[0] != b_shape[0] or a.shape[2] != b_shape[1]:
            raise ValueError(f"batched contraction mismatch: {a.shape} @ "
                             f"{tuple(b_shape)}")
        nb, m, k = a.shape
        n = b_shape[2]
        plan = tuning.plan_matmul(m, n, k, sq.accum_dtype(a.dtype), bm=bm,
                                  bn=bn, bk=bk, kc=kc)
        fb = _pick_fb(plan, nb) if fold else 1
        if prep is not None:
            matched = _match_rhs_padding(prep, plan, sq.accum_dtype(a.dtype))
            if matched is not None:
                return _sq_matmul_batched_exec(a, *matched, n, fb, plan,
                                               interpret_r)
            b = (jnp.swapaxes(prep.source, -1, -2) if prep.transposed
                 else prep.source)
        return _sq_matmul_batched_impl(a, b, fb, plan, interpret_r)
    if len(b_shape) != 2:
        raise ValueError(f"rhs must be 2D (K, N) or batched 3D (B, K, N), "
                         f"got {tuple(b_shape)}")
    if a.ndim != 2:
        # collapse leading batch dims to rows (dense-layer convention)
        lead = a.shape[:-1]
        out = sq_matmul(a.reshape(-1, a.shape[-1]), b, bm=bm, bn=bn, bk=bk,
                        kc=kc, interpret=interpret)
        return out.reshape(*lead, b_shape[-1])
    m, k = a.shape
    n = b_shape[1]
    plan = tuning.plan_matmul(m, n, k, sq.accum_dtype(a.dtype), bm=bm, bn=bn,
                              bk=bk, kc=kc)
    if prep is not None:
        matched = _match_rhs_padding(prep, plan, sq.accum_dtype(a.dtype))
        if matched is not None:
            return _sq_matmul_exec(a, *matched, n, plan, interpret_r)
        b = (jnp.swapaxes(prep.source, -1, -2) if prep.transposed
             else prep.source)
    return _sq_matmul_impl(a, b, plan, interpret_r)


@functools.partial(jax.jit, static_argnames=("plan", "interpret"))
def _sq_grouped_exec(x, w, tile_expert, n_used, plan, interpret):
    xw = x.astype(jnp.float32)
    sa = sq.row_correction(xw, axis=-1)[:, None]                 # (R, 1)
    sb = sq.col_correction(w.astype(jnp.float32), axis=-2)[:, None, :]
    return sq_grouped_matmul_pallas(
        xw, w, sa, sb, tile_expert, n_used, bm=plan.bm, bn=plan.bn,
        bk=plan.bk, kc=plan.kc, interpret=interpret)


def sq_grouped_matmul(x, w, tile_expert, n_used, *, bm: int,
                      interpret: bool | None = None):
    """Grouped square matmul: row tile ``t`` of ``x`` times the weights of
    expert ``tile_expert[t]`` (:mod:`repro.kernels.sq_grouped_matmul`).

    x: (R, K) rows sorted by expert in ``bm``-row tiles
    (:func:`repro.kernels.sq_grouped_matmul.group_rows`); w: (E, K, N)
    float weights, read in their stored dtype; tile_expert: (R // bm,)
    int32; n_used: (1,) int32 tiles that hold rows.  Returns (R, N) f32;
    rows of tiles past ``n_used`` are undefined.  Tiles come from
    :func:`repro.kernels.tuning.plan_grouped_matmul`: the weights are
    never padded.
    """
    interpret = default_interpret() if interpret is None else interpret
    if not jnp.issubdtype(w.dtype, jnp.floating):
        raise ValueError(f"sq_grouped_matmul is float-only, got {w.dtype}")
    R, K = x.shape
    N = w.shape[-1]
    if R % bm:
        raise ValueError(f"{R} rows are not whole {bm}-row tiles")
    plan = tuning.plan_grouped_matmul(bm, N, K)
    return _sq_grouped_exec(x, w, tile_expert, n_used, plan, interpret)


# --------------------------------------------------------------------------
# Complex square-based matmuls (CPM3 / CPM4)
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("plan", "interpret"))
def _cpm3_impl(a, b, c, s, plan, interpret):
    a, b, c, s = _widen(a, b, c, s)
    m, k = a.shape
    n = c.shape[1]
    # corrections, paper eqs 33 / 35
    sre = jnp.sum(-sq.square(a + b) + sq.square(b), axis=-1)[:, None]
    sim = jnp.sum(-sq.square(a + b) - sq.square(a), axis=-1)[:, None]
    scs = jnp.sum(-sq.square(c) + sq.square(c + s), axis=0)[None, :]
    ssc = jnp.sum(-sq.square(c) - sq.square(s - c), axis=0)[None, :]
    (a, b), (c, s), (sre, sim), (scs, ssc) = _pad_operands(
        plan, [a, b], [c, s], [sre, sim], [scs, ssc])
    re, im = cpm3_matmul_pallas(a, b, c, s, sre, sim, scs, ssc,
                                bm=plan.bm, bn=plan.bn, bk=plan.bk,
                                kc=plan.kc, interpret=interpret)
    return re[:m, :n], im[:m, :n]


def cpm3_matmul(x, y, *, bm: int | None = None, bn: int | None = None,
                bk: int | None = None, kc: int | None = None,
                interpret: bool | None = None):
    """Complex matmul with 3 squares per multiply via the Pallas kernel.

    x: (m, k) complex, y: (k, n) complex; returns (re, im) planes.
    """
    interpret = default_interpret() if interpret is None else interpret
    m, k = x.shape
    n = y.shape[1]
    plan = tuning.plan_matmul(m, n, k, sq.accum_dtype(jnp.real(x).dtype),
                              bm=bm, bn=bn, bk=bk, kc=kc, kind="cpm3_matmul")
    return _cpm3_impl(jnp.real(x), jnp.imag(x), jnp.real(y), jnp.imag(y),
                      plan, interpret)


@functools.partial(jax.jit, static_argnames=("plan", "interpret"))
def _cpm4_impl(a, b, c, s, plan, interpret):
    a, b, c, s = _widen(a, b, c, s)
    m, k = a.shape
    n = c.shape[1]
    # shared corrections, paper eq 18
    sx = -jnp.sum(sq.square(a) + sq.square(b), axis=-1)[:, None]
    sy = -jnp.sum(sq.square(c) + sq.square(s), axis=0)[None, :]
    (a, b), (c, s), (sx,), (sy,) = _pad_operands(
        plan, [a, b], [c, s], [sx], [sy])
    re, im = cpm4_matmul_pallas(a, b, c, s, sx, sy, bm=plan.bm, bn=plan.bn,
                                bk=plan.bk, kc=plan.kc, interpret=interpret)
    return re[:m, :n], im[:m, :n]


def cpm4_matmul(x, y, *, bm: int | None = None, bn: int | None = None,
                bk: int | None = None, kc: int | None = None,
                interpret: bool | None = None):
    """Complex matmul with 4 squares per multiply via the Pallas kernel."""
    interpret = default_interpret() if interpret is None else interpret
    m, k = x.shape
    n = y.shape[1]
    plan = tuning.plan_matmul(m, n, k, sq.accum_dtype(jnp.real(x).dtype),
                              bm=bm, bn=bn, bk=bk, kc=kc, kind="cpm4_matmul")
    return _cpm4_impl(jnp.real(x), jnp.imag(x), jnp.real(y), jnp.imag(y),
                      plan, interpret)


# --------------------------------------------------------------------------
# Square-based convolutions
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("bo", "tb", "interpret"))
def _sq_conv_impl(x, w, bo, tb, interpret):
    acc = sq.accum_dtype(x.dtype)
    xw = x.astype(acc)
    ww = w.astype(acc)
    L = xw.shape[0]
    n = ww.shape[0]
    k_out = L - n + 1
    # Zero-pad taps to the tap-block multiple (zero taps are exact no-ops)
    # and samples so (a) every tap-block window stays in range and (b) the
    # padded output length is a bo multiple (extra outputs are discarded).
    n_pad = (-n) % tb
    out_pad = (-k_out) % bo
    if n_pad:
        ww = jnp.pad(ww, (0, n_pad))
    need = (k_out + out_pad) + (n + n_pad) - 1
    if need > L:
        xw = jnp.pad(xw, (0, need - L))
    out = sq_conv_pallas(xw, ww, bo=bo, tb=tb, interpret=interpret)
    return out[:k_out]


def sq_conv(x, w, *, bo: int | None = None, tb: int | None = None,
            interpret: bool | None = None):
    """Square-based valid 1D correlation via the Pallas kernel."""
    interpret = default_interpret() if interpret is None else interpret
    L = x.shape[0]
    n = w.shape[0]
    pbo, ptb = tuning.plan_conv(L - n + 1, n, x.dtype, bo=bo, tb=tb,
                                interpret=interpret)
    return _sq_conv_impl(x, w, pbo, ptb, interpret)


def _conv2d_geometry(x4_shape, w4_shape, stride, padding):
    """Resolve stride/padding and the output extents for rank-4 operands."""
    strides = conv_core.resolve_stride(stride)
    pads = conv_core.resolve_padding(padding, x4_shape[2:], w4_shape[2:],
                                     strides)
    (sh, sv) = strides
    hp = x4_shape[2] + pads[0][0] + pads[0][1]
    wp = x4_shape[3] + pads[1][0] + pads[1][1]
    oh = (hp - w4_shape[2]) // sh + 1
    ow = (wp - w4_shape[3]) // sv + 1
    if oh <= 0 or ow <= 0:
        raise ValueError(f"kernel {w4_shape[2:]} larger than padded input "
                         f"({hp}, {wp})")
    return strides, pads, (hp, wp), (oh, ow)


def _normalize_conv_operands(x, w):
    """normalize_conv2d over a possibly-prepared filter operand: returns
    (x4, w4_or_prep, prep_or_None, w4_shape, kind)."""
    prep = w if isinstance(w, PreparedOperand) else None
    if prep is not None:
        if prep.kind != "conv2d":
            raise ValueError(f"conv2d got a {prep.kind!r} PreparedOperand; "
                             f"expected a conv2d one")
        x4, w4, kind = conv_core.normalize_conv2d(x, prep.source)
        return x4, w4, prep, w4.shape, kind
    x4, w4, kind = conv_core.normalize_conv2d(x, w)
    return x4, w4, None, w4.shape, kind


@functools.partial(jax.jit, static_argnames=("cout", "plan", "stride",
                                             "pads", "interpret"))
def _sq_conv2d_fused_exec(x, wt, sw, cout, plan, stride, pads, interpret):
    """Execute half of the fused path: widen + lay out the input, pad the
    prepared filter planes to tile multiples, run the window-streaming
    kernel.  The im2col patch tensor is never built."""
    sh, sv = stride
    xw = x.astype(wt.dtype)
    kh, kw = wt.shape[0], wt.shape[1]
    xt = jnp.transpose(xw, (0, 2, 3, 1))                       # (B, H, W, C)
    xt = jnp.pad(xt, ((0, 0), pads[0], pads[1], (0, 0)))
    hp, wp = xt.shape[1], xt.shape[2]
    oh = (hp - kh) // sh + 1
    ow = (wp - kw) // sv + 1
    # pad the *output* grid to tile multiples, then the input far enough
    # that every padded tile's window load stays in range (the extra
    # outputs read zeros and are sliced away)
    ohp = oh + (-oh) % plan.bh
    owp = ow + (-ow) % plan.bw
    need_h = (ohp - 1) * sh + kh
    need_w = (owp - 1) * sv + kw
    xt = jnp.pad(xt, ((0, 0), (0, max(0, need_h - hp)),
                      (0, max(0, need_w - wp)), (0, 0)))
    xt = _pad_to(xt, plan.bk, 3)                 # zero channels: exact no-ops
    wt = _pad_to(_pad_to(wt, plan.bk, 2), plan.bf, 3)
    sw = _pad_to(sw, plan.bf, 1)
    out = sq_conv2d_pallas(xt, wt, sw, ohp=ohp, owp=owp, bh=plan.bh,
                           bw=plan.bw, bk=plan.bk, bf=plan.bf, kc=plan.kc,
                           stride=stride, interpret=interpret)
    out = out[:, :oh, :ow, :cout]
    return jnp.transpose(out, (0, 3, 1, 2))      # back to (B, cout, oh, ow)


@functools.partial(jax.jit, static_argnames=("plan", "stride", "pads",
                                             "interpret"))
def _sq_conv2d_fused_impl(x, w, plan, stride, pads, interpret):
    """Raw-array fused path: prepare the filters, then execute."""
    acc = sq.accum_dtype(x.dtype)
    wt, sw, _, _ = prepare_conv2d_weights(w, acc)
    return _sq_conv2d_fused_exec(x, wt, sw, w.shape[0], plan, stride, pads,
                                 interpret)


def sq_conv2d(x, w, *, stride=1, padding="VALID", bh: int | None = None,
              bw: int | None = None, bk: int | None = None,
              kc: int | None = None, bf: int | None = None,
              interpret: bool | None = None):
    """Square-based 2D correlation via the FUSED window-streaming kernel.

    The paper's §5.1 engine streams input windows straight through the PM
    datapath; this wrapper runs its Pallas form
    (:mod:`repro.kernels.sq_conv2d`): every (bh, bw) output tile loads its
    input window once and slides the ``kh*kw`` shifted views through the
    same block-PM machinery as ``sq_matmul`` -- the O(oh*ow*kh*kw) im2col
    patch tensor is never materialized (that route survives as
    :func:`sq_conv2d_im2col`, the reference).

    x: (B, cin, H, W) -- or (cin, H, W), or plain (H, W) with rank-2/3
    filters (see :func:`repro.core.conv.normalize_conv2d`); w: (cout, cin,
    kh, kw), or a conv2d :class:`repro.core.prepared.PreparedOperand`
    (the widened/transposed planes and the ``Sw`` correction are then
    reused instead of recomputed -- the paper's weight-stationary
    contract).  ``stride`` is an int or (sh, sv); ``padding`` is "VALID",
    "SAME", an int, or explicit (lo, hi) pairs.  Tile sizes default to
    :func:`repro.kernels.tuning.plan_conv2d`.

    >>> import numpy as np, jax.numpy as jnp
    >>> from repro.kernels import ops
    >>> x = jnp.asarray(np.arange(36.0, dtype=np.float32).reshape(6, 6))
    >>> w = jnp.ones((3, 3), jnp.float32)
    >>> out = ops.sq_conv2d(x, w)           # 3x3 box filter, squares only
    >>> out.shape
    (4, 4)
    >>> bool(np.isclose(out[0, 0], x[:3, :3].sum()))
    True
    """
    interpret_r = default_interpret() if interpret is None else interpret
    x4, w4, prep, w4_shape, kind = _normalize_conv_operands(x, w)
    strides, pads, (hp, wp), _ = _conv2d_geometry(x4.shape, w4_shape,
                                                  stride, padding)
    cout, cin, kh, kw = w4_shape
    plan = tuning.plan_conv2d(
        hp, wp, kh, kw, cin, cout, sq.accum_dtype(x4.dtype),
        stride=strides, bh=bh, bw=bw, bk=bk, kc=kc, bf=bf)
    if prep is not None and prep.canon.dtype == sq.accum_dtype(x4.dtype):
        out = _sq_conv2d_fused_exec(x4, prep.canon, prep.corr, cout, plan,
                                    strides, pads, interpret_r)
    else:
        out = _sq_conv2d_fused_impl(x4, w4, plan, strides, pads, interpret_r)
    return conv_core.denormalize_conv2d(out, kind)


def sq_conv2d_routed(x, w, *, stride=1, padding="VALID",
                     interpret: bool | None = None):
    """Planner-routed 2D conv execution (conv2d mode ``square_pallas``).

    Resolves the geometry ONCE (the same :func:`_conv2d_geometry` the
    kernel wrappers use, so router and kernel can never size different
    shapes), asks :func:`repro.kernels.routing.select_conv2d_route` for
    the route, and dispatches to :func:`sq_conv2d` (fused) or
    :func:`sq_conv2d_im2col`.  ``w`` may be a conv2d PreparedOperand.
    """
    from repro.kernels import routing    # lazy: keep ops importable alone

    x4, _, _, w4_shape, _ = _normalize_conv_operands(x, w)
    _, _, _, (oh, ow) = _conv2d_geometry(x4.shape, w4_shape, stride,
                                         padding)
    cout, cin, kh, kw = w4_shape
    route = routing.select_conv2d_route(oh, ow, kh, kw, cin, cout,
                                        batch=x4.shape[0], dtype=x4.dtype)
    f = sq_conv2d if route.name == "fused" else sq_conv2d_im2col
    return f(x, w, stride=stride, padding=padding, interpret=interpret)


def sq_conv2d_im2col(x, w, *, stride=1, padding="VALID",
                     interpret: bool | None = None):
    """Square-based 2D correlation via im2col + the matmul kernel.

    The §5.1 windows are a matrix view of the input (each output pixel's
    receptive field flattened to a row), so the conv can route through
    ``sq_matmul`` on a materialized (B*oh*ow, cin*kh*kw) patch matrix.
    This is the *reference* route (conv2d mode ``square_exact``) and the
    planner-selected winner at tiny-K cache-resident shapes (see
    :mod:`repro.kernels.routing`): simple and lane-efficient, but it
    expands the input kh*kw-fold in HBM.  Accepts the same operand ranks /
    stride / padding as the fused path, and the same conv2d
    ``PreparedOperand`` (the im2col filter matrix and its correction are
    part of the prepared form).
    """
    interpret_r = default_interpret() if interpret is None else interpret
    x4, w4, prep, w4_shape, kind = _normalize_conv_operands(x, w)
    strides, pads, _, (oh, ow) = _conv2d_geometry(x4.shape, w4_shape,
                                                  stride, padding)
    cout, cin, kh, kw = w4_shape
    acc = sq.accum_dtype(x4.dtype)
    plan = tuning.plan_matmul(x4.shape[0] * oh * ow, cout, cin * kh * kw, acc)
    if prep is not None and prep.im2col is not None \
            and prep.im2col[0].dtype == acc:
        wmat, cmat = prep.im2col
        out = _sq_conv2d_im2col_prepared(x4, wmat, cmat, (kh, kw), plan,
                                         strides, pads, interpret_r)
    else:
        out = _sq_conv2d_im2col_impl(x4, w4, plan, strides, pads,
                                     interpret_r)
    return conv_core.denormalize_conv2d(out, kind)


def _im2col_patches(xp, kh, kw, stride):
    """(B, cin, hp, wp) padded input -> (B*oh*ow, cin*kh*kw) patch matrix,
    K axis ordered (cin, kh, kw) to match the prepared filter matrix."""
    sh, sv = stride
    B, cin, hp, wp = xp.shape
    oh = (hp - kh) // sh + 1
    ow = (wp - kw) // sv + 1
    # materialize the patch tensor from kh*kw shifted (strided) views --
    # each input pixel copied once per covering tap
    taps = [jax.lax.slice(xp, (0, 0, di, dj),
                          (B, cin, di + (oh - 1) * sh + 1,
                           dj + (ow - 1) * sv + 1), (1, 1, sh, sv))
            for di in range(kh) for dj in range(kw)]
    patches = jnp.stack(taps)                    # (kh*kw, B, cin, oh, ow)
    # -> (B, oh, ow, cin, kh*kw)
    patches = jnp.transpose(patches, (1, 3, 4, 2, 0))
    return patches.reshape(B * oh * ow, cin * kh * kw), (B, oh, ow)


def _im2col_exec(x, wmat, cmat, khw, plan, stride, pads, interpret):
    """Shared im2col execute half: patches stream against the prepared
    (widened, corrected) filter matrix through the shared matmul exec."""
    kh, kw = khw
    xp = jnp.pad(x, ((0, 0), (0, 0), pads[0], pads[1]))
    pmat, (B, oh, ow) = _im2col_patches(xp, kh, kw, stride)
    cout = wmat.shape[1]
    bw = _pad_to(_pad_to(wmat, plan.bk, 0), plan.bn, 1)
    sb = _pad_to(cmat, plan.bn, 1)
    out = _sq_matmul_exec(pmat, bw, sb, cout, plan, interpret)
    out = out.reshape(B, oh, ow, cout)
    return jnp.transpose(out, (0, 3, 1, 2))


_sq_conv2d_im2col_prepared = functools.partial(jax.jit, static_argnames=(
    "khw", "plan", "stride", "pads", "interpret"))(_im2col_exec)


@functools.partial(jax.jit, static_argnames=("plan", "stride", "pads",
                                             "interpret"))
def _sq_conv2d_im2col_impl(x, w, plan, stride, pads, interpret):
    """Raw-array im2col path: prepare the filter matrix, then execute."""
    kh, kw = w.shape[2], w.shape[3]
    acc = sq.accum_dtype(x.dtype)
    _, _, wmat, cmat = prepare_conv2d_weights(w, acc)
    return _im2col_exec(x, wmat, cmat, (kh, kw), plan, stride, pads,
                        interpret)
