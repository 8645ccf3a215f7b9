"""Weight-stationary prepared operands (paper §4-§5).

The paper's hardware payoff is *weight-stationary* operation: the
column-side correction sums (``Sb``/``Sw``) and the widened/laid-out
weight planes are computed once and amortized across every activation
streamed through the array.  The software datapath historically redid
that constant work per call: every ``fs_einsum`` / ``conv2d`` re-widened,
re-padded and re-reduced its weight operand (a full O(K*N) pass).

:func:`prepare_operand` performs the constant-operand half of the kernel
prep pipeline ONCE and returns a :class:`PreparedOperand` -- a pytree that
every dispatch entry point (``fs_einsum``, ``core.matmul.matmul``,
``core.conv.conv2d``, the ``kernels.ops`` wrappers) accepts in place of
the raw weight array:

- ``source`` keeps the original array (caller layout), so the multiplier
  baseline and the virtual/exact/scan modes stay bit-identical to the
  raw-array path;
- ``canon`` holds the widened weight in kernel-canonical layout -- the
  tile-padded ``(K, N)`` / ``(B, K, N)`` matrix for the matmul kernels,
  the ``(kh, kw, cin, cout)`` channels-last plane stack for the fused
  conv kernel;
- ``corr`` holds the precomputed column-side correction (``Sb`` (1, N)
  for matmuls, the per-filter ``Sw`` (1, cout) for convs);
- ``im2col`` (conv only) additionally carries the widened
  ``(cin*kh*kw, cout)`` filter matrix so the im2col route shares the
  amortization.

Plan resolution (which needs only shapes/dtypes) is memoized on the
operand's cache key ``(kind, shape, dtype, transposed, site)``: under
jit the whole prepare is traced once per cache entry; under eager/interpret
execution reusing one PreparedOperand across calls skips the O(K*N)
widen/correct/pad work entirely -- the measurable amortization
benchmarked in ``benchmarks/run.py`` (prepared-vs-raw rows).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import squares as sq

__all__ = ["PreparedOperand", "prepare_operand", "unwrap", "is_prepared",
           "clear_plan_cache"]

# Default row-extent hint used to resolve the prepare-time tile plan when
# the activation extent is unknown.  Execution re-plans for the ACTUAL M
# through the same planner (identically to raw dispatch -- that is what
# makes prepared and raw bit-identical); when the prepared (bk, bn)
# padding multiples match that plan's, the canon/corr arrays are reused
# as-is, otherwise the zero padding is re-laid (a copy, but never the
# O(K*N) widen/correct work -- see kernels.ops._match_rhs_padding).  The
# row walk's planned tiles pad K and N to the lane granule at every M, so
# only explicit execute-time tiles re-lay.
DEFAULT_M_HINT = 128

# Prepare-time plan memo, keyed by the operand cache key.  Keeps repeated
# eager prepares (and re-traces) from re-running the planner.
_PLAN_CACHE: dict = {}


def clear_plan_cache() -> None:
    _PLAN_CACHE.clear()


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PreparedOperand:
    """A constant operand with its kernel prep precomputed (see module
    docstring).  A pytree: the arrays are leaves, the metadata is static
    aux data, so prepared weights ride jit/scan/grad boundaries like any
    other param leaf."""
    source: Any                       # original array, caller layout
    canon: Any                        # widened canonical-layout weight
    corr: Any                         # column-side correction (Sb / Sw)
    im2col: Any                       # conv only: widened (K, cout) matrix
    grad: Any                         # opposite-layout prep for dL/dx (or None)
    kind: str                         # "matmul" | "matmul_batched" | "conv2d"
    plan: Any                         # prepare-time TilePlan (matmul kinds)
    transposed: bool                  # canon built from source.T
    site: Optional[str]
    key: Tuple                  # (kind, shape, dtype, transposed, site)

    # -- array-protocol conveniences (shape checks in the dispatchers) --
    @property
    def shape(self):
        return self.source.shape

    @property
    def dtype(self):
        return self.source.dtype

    @property
    def ndim(self):
        return self.source.ndim

    def tree_flatten(self):
        leaves = (self.source, self.canon, self.corr, self.im2col, self.grad)
        aux = (self.kind, self.plan, self.transposed, self.site, self.key)
        return leaves, aux

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, *aux)


def is_prepared(x) -> bool:
    return isinstance(x, PreparedOperand)


def unwrap(x):
    """The raw source array of a PreparedOperand (identity otherwise)."""
    return x.source if isinstance(x, PreparedOperand) else x


def _matmul_key(kind: str, shape, dtype, transposed: bool,
                site: Optional[str]) -> Tuple:
    return (kind, tuple(shape), jnp.dtype(dtype).name, transposed, site)


def _prepare_matmul(w, *, transpose: bool, m_hint: Optional[int],
                    site: Optional[str],
                    prepare_grads: bool = False) -> PreparedOperand:
    from repro.kernels import ops as kops    # lazy: avoid import cycle
    from repro.kernels import tuning

    batched = w.ndim == 3
    mat = jnp.swapaxes(w, -1, -2) if transpose else w
    k, n = mat.shape[-2], mat.shape[-1]
    acc = sq.accum_dtype(w.dtype)
    kind = "matmul_batched" if batched else "matmul"
    key = _matmul_key(kind, w.shape, w.dtype, transpose, site)
    plan = _PLAN_CACHE.get((key, m_hint))
    if plan is None:
        # the planner kernels.ops.sq_matmul runs, so prepare and execute
        # pad alike
        plan = tuning.plan_matmul(m_hint or DEFAULT_M_HINT, n, k, acc)
        _PLAN_CACHE[(key, m_hint)] = plan
    canon, corr = kops.prepare_matmul_rhs(mat, plan, acc)
    # dL/dx consumes the weight with the contraction/output axes swapped,
    # so the gradient prep is the SAME source prepared the other way
    # around (batched preps fall back to their raw source in backward --
    # the batched kernel route only takes (B, K, N)-layout preps).
    gradp = None
    if prepare_grads and not batched:
        gsite = f"{site}.bwd_x" if site else None
        gradp = _prepare_matmul(w, transpose=not transpose, m_hint=m_hint,
                                site=gsite)
    return PreparedOperand(w, canon, corr, None, gradp, kind, plan, transpose,
                           site, key)


def _prepare_conv2d(w, *, site: Optional[str]) -> PreparedOperand:
    from repro.kernels import ops as kops    # lazy: avoid import cycle

    # normalize the filter rank shorthands without touching the input side
    if w.ndim == 2:
        w4 = w[None, None]
    elif w.ndim == 3:
        w4 = w[:, None]
    elif w.ndim == 4:
        w4 = w
    else:
        raise ValueError(f"conv2d filters must be rank 2-4, got {w.shape}")
    acc = sq.accum_dtype(w.dtype)
    wt, sw, wmat, cmat = kops.prepare_conv2d_weights(w4, acc)
    key = _matmul_key("conv2d", w.shape, w.dtype, False, site)
    return PreparedOperand(w, wt, sw, (wmat, cmat), None, "conv2d", None,
                           False, site, key)


def prepare_operand(w, *, for_: str = "matmul", transpose: bool = False,
                    m_hint: Optional[int] = None, site: Optional[str] = None,
                    prepare_grads: bool = False) -> "PreparedOperand":
    """Precompute the constant-operand half of the kernel prep pipeline.

    ``for_``: ``"matmul"`` (2D ``(K, N)`` weights, or 3D ``(B, K, N)``
    batched weights such as stacked MoE experts) or ``"conv2d"``
    (``(cout, cin, kh, kw)`` filters, rank shorthands accepted).

    ``transpose`` (matmul only): the call site contracts the *last* axis
    of the weight (e.g. the tied-embedding vocab GEMM ``bsd,vd->bsv``), so
    the canonical ``(K, N)`` form is the transpose.  The transpose is
    materialized once, at prepare time.

    ``m_hint``: expected activation row extent -- resolves the
    prepare-time tile plan with the planner ``kernels.ops.sq_matmul``
    uses.  Execution always re-plans for the actual M (identically to
    raw dispatch, preserving bit-identity) and reuses the prepared
    padding when the (bk, bn) multiples agree, as they do for every
    planned tile; on a mismatch (explicit execute-time tiles) the zero
    padding is re-laid per call (a copy -- the O(K*N) widen/correct work
    is still skipped).

    ``prepare_grads`` (2D matmul only): also prepare the *opposite-layout*
    form of the same source under ``<site>.bwd_x`` and carry it on the
    ``grad`` field -- the fs_einsum custom VJP consumes it for the
    activation gradient dL/dx, so forward and backward share one prepare
    instead of re-preparing per trace.  Batched/conv preps keep
    ``grad=None`` (their backward falls back to the raw source).

    Idempotent: passing an already-prepared operand returns it unchanged.

    >>> import numpy as np, jax.numpy as jnp
    >>> from repro.core.prepared import prepare_operand
    >>> from repro.kernels import ops
    >>> w = jnp.asarray(np.ones((5, 7), np.float32))
    >>> prep = prepare_operand(w, site="dense")
    >>> a = jnp.asarray(np.arange(10.0, dtype=np.float32).reshape(2, 5))
    >>> bool(np.array_equal(ops.sq_matmul(a, prep), ops.sq_matmul(a, w)))
    True
    >>> gp = prepare_operand(w, site="dense", prepare_grads=True)
    >>> gp.grad.transposed, gp.grad.site        # dL/dx form rides along
    (True, 'dense.bwd_x')
    """
    if isinstance(w, PreparedOperand):
        return w
    w = jnp.asarray(w)
    if for_ == "conv2d":
        return _prepare_conv2d(w, site=site)
    if for_ != "matmul":
        raise ValueError(f"unknown prepare target {for_!r}; expected "
                         f"'matmul' or 'conv2d'")
    if w.ndim not in (2, 3):
        raise ValueError(f"matmul prepare needs a 2D (K, N) or 3D (B, K, N) "
                         f"operand, got {w.shape}")
    return _prepare_matmul(w, transpose=transpose, m_hint=m_hint, site=site,
                           prepare_grads=prepare_grads)
