"""Tile planner for the Pallas square-kernel suite.

Picks the ``(bm, bn, bk, kc)`` block plan for every kernel call site:

- ``bm`` x ``bn`` is the VMEM-resident output tile (``bm`` rounded to the
  8-sublane granule, ``bn``/``bk`` to the 128-lane granule whenever the
  operand is large enough to allow it);
- ``bk`` is the K-slab streamed per grid step;
- ``kc`` is the chunk width of the rank-2 broadcast squaring inside a step
  (the live PM intermediate is (bm, kc, bn)).  A TPU ("mkn") plan chunks
  in whole 128-lane groups or takes the slab in one chunk: Mosaic only
  lowers a dynamic chunk start on the lane axis when it is a multiple of
  128 (see kernels.pm_blocks).

Two modes:

**Model mode (default).**  Candidates are ranked by the analytical cost in
:mod:`repro.core.cost_model` (``pm_grid_cost``): VPU lane-ops plus per-grid-
step and per-chunk issue overheads, subject to a VMEM budget.  Deterministic,
zero-warmup, good enough to avoid pathological plans.

**Empirical mode.**  :func:`autotune_matmul` sweeps candidate plans through
the wall-clock harness in ``benchmarks/kernel_timing.py`` and caches winners
to a JSON table keyed by ``(kind, m, n, k, dtype)``.  The planner consults
the cache first (path from ``$REPRO_TUNING_CACHE`` or the package-local
``tuning_cache.json``), so a one-off autotune run upgrades every later call
with the same shape.

User-supplied ``bm``/``bn``/``bk``/``kc`` always win over both modes.
They are clamped to the (padded) operand extent and aligned to the
hardware granules -- which may round a value *up* to the next sublane/lane
multiple (e.g. bm=100 -> 104): padding to an aligned tile is cheaper than
the layout penalty of a misaligned one.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import warnings
from typing import Iterable, Optional

import jax.numpy as jnp

from repro.core import cost_model as cm
from repro.core import squares as sq
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

__all__ = ["TilePlan", "Conv2DPlan", "PagedAttnPlan", "plan_matmul",
           "plan_conv", "plan_conv2d", "plan_paged_attn",
           "candidate_plans", "candidate_conv2d_plans",
           "autotune_matmul", "autotune_conv2d", "autotune_paged_attn",
           "load_cache", "save_cache",
           "cache_path", "clear_cache", "autotune_enabled"]

SUBLANE = 8            # f32 sublane granule (second-minor axis)
LANE = 128             # lane granule (minor axis)
VMEM_BUDGET = 12 * 1024 * 1024      # leave headroom under the ~16 MB v5e VMEM
# For the "mnk" (minor-axis-reduce) layout the live (bm, bn, kc) chunk is
# walked like a dot-product loop nest; keeping it inside the L2-ish working
# set is what makes that layout fast on CPU interpret runs.  Reduction
# depths beyond ~32 stop vectorizing well (measured: kc=32 beats both
# kc=128 and kc=8 by 2-5x at 128^3 f32), so mnk plans cap kc there.
CACHE_BUDGET = 2 * 1024 * 1024
KC_MNK_MAX = 32
KC_CANDIDATES = (8, 16, 32, 64, 128)
# Operand/accumulator multiplicities per kernel kind: the CPM kernels
# stream two row planes + two column planes and hold two scratch
# accumulators, so their VMEM feasibility is ~2x a plain sq_matmul's.
KIND_COUNTS = {
    "sq_matmul": (1, 1, 1),
    "cpm3_matmul": (2, 2, 2),
    "cpm4_matmul": (2, 2, 2),
}


@dataclasses.dataclass(frozen=True)
class TilePlan:
    bm: int
    bn: int
    bk: int
    kc: int
    pm_layout: str = "mkn"      # "mkn": TPU-native; "mnk": minor-axis reduce

    def astuple(self):
        return (self.bm, self.bn, self.bk, self.kc)


@dataclasses.dataclass(frozen=True)
class PagedAttnPlan:
    """Chunk plan for the fused paged-attention kernel.

    The kernel's tile geometry is fixed by the call (the query tile is
    the whole (S*G, hd) panel of one head, the K/V tile ``tile`` tokens
    of that head), so the only free knobs are the PM chunk widths of its
    two contractions: ``kc_qk`` chunks the head_dim reduction of the
    score block, ``kc_pv`` the tile-token reduction of the PV block.
    Each must divide its axis.
    """
    kc_qk: int
    kc_pv: int
    pm_layout: str = "mkn"


@dataclasses.dataclass(frozen=True)
class Conv2DPlan:
    """Block plan for the fused window-streaming 2D conv kernel.

    ``bh`` x ``bw`` is the output tile streamed per grid step (the input
    window loaded once per step covers its ``(bh-1)*sh+kh`` x
    ``(bw-1)*sv+kw`` receptive field); ``bk`` input channels are reduced
    per step in ``kc``-wide PM chunks; ``bf`` filters share each window.
    """
    bh: int
    bw: int
    bk: int
    kc: int
    bf: int
    pm_layout: str = "mkn"

    def astuple(self):
        return (self.bh, self.bw, self.bk, self.kc, self.bf)


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _align_bm(bm: int, m: int) -> int:
    """Clamp ``bm`` to the row extent, rounded to the sublane granule.

    For m >= SUBLANE the tile is always a multiple of 8 so Mosaic layouts
    hold (padding covers the remainder, e.g. m=100 -> bm=104, not 100);
    tiny operands keep their exact extent (interpret mode tolerates it).
    """
    if m >= SUBLANE:
        return min(_round_up(bm, SUBLANE), _round_up(m, SUBLANE))
    return min(bm, m)


def _align_lane(b: int, extent: int) -> int:
    """Clamp a minor-axis tile to the extent, keeping 128-lane alignment
    whenever the operand itself spans at least one lane group."""
    if extent >= LANE:
        return min(_round_up(b, LANE), _round_up(extent, LANE))
    return min(b, extent)


def _align_kc(kc: int, bk: int) -> int:
    """kc must divide bk so the chunk loop has no ragged tail."""
    kc = max(1, min(kc, bk))
    while bk % kc:
        kc -= 1
    return kc


def _kc_ladder(bk: int, pm_layout: str) -> list[int]:
    """The chunk widths a plan may take for a ``bk``-wide slab: every
    :data:`KC_CANDIDATES` width (aligned to divide ``bk``) for "mnk";
    whole lane groups dividing ``bk``, or the whole slab, for "mkn"."""
    if pm_layout == "mkn":
        return sorted({bk} | {c for c in range(LANE, bk, LANE)
                              if bk % c == 0})
    return sorted({_align_kc(c, bk) for c in KC_CANDIDATES})


def candidate_plans(m: int, n: int, k: int,
                    *, itemsize: int = 4, n_row_ops: int = 1,
                    n_col_ops: int = 1, n_acc: int = 1,
                    pm_layout: str = "mkn",
                    vmem_budget: int = VMEM_BUDGET) -> list[TilePlan]:
    """Enumerate aligned, budget-feasible plans for an (m, n, k) contraction.

    Every plan respects the VMEM budget; "mnk"-layout plans additionally
    cap ``kc`` at :data:`KC_MNK_MAX` and keep the hot loop-nest panel (the
    transposed (bn, kc) column slab plus a sublane row stripe) inside
    :data:`CACHE_BUDGET`.  (An earlier rule bounded the whole (bm, bn, kc)
    chunk, which wrongly pruned large-bm single-grid-step plans -- the
    measured winners on tall-skinny shapes like the im2col matmuls, where
    one grid step with a streamed chunk beats many small tiles by ~8x in
    interpret mode.)

    The ladders always include the full-extent tile on every axis (a
    single-grid-step plan pays zero padding waste and no pipeline
    overhead; VMEM feasibility prunes it where it cannot fit).
    """
    bms = sorted({_align_bm(c, m) for c in (8, 32, 64, 128, 256, 512)}
                 | {_align_bm(m, m)})
    bns = sorted({_align_lane(c, n) for c in (128, 256, 512)}
                 | {_align_lane(n, n)})
    bks = sorted({_align_lane(c, k) for c in (128, 256, 512)}
                 | {_align_lane(k, k)})
    plans = []
    for bm in bms:
        for bn in bns:
            for bk in bks:
                for kc in _kc_ladder(bk, pm_layout):
                    if pm_layout == "mnk" and kc > 1 and (
                            kc > KC_MNK_MAX or
                            (bn + SUBLANE) * kc * itemsize > CACHE_BUDGET):
                        continue
                    cost = cm.pm_grid_cost(
                        m, n, k, bm, bn, bk, kc, itemsize=itemsize,
                        n_row_ops=n_row_ops, n_col_ops=n_col_ops, n_acc=n_acc)
                    if cost.vmem_bytes <= vmem_budget:
                        plans.append(TilePlan(bm, bn, bk, kc, pm_layout))
    if not plans:      # degenerate shapes: fall back to a single minimal plan
        bm = _align_bm(8, m)
        bn = _align_lane(LANE, n)
        bk = _align_lane(LANE, k)
        plans = [TilePlan(bm, bn, bk, _align_kc(8, bk), pm_layout)]
    return plans


def _divisor_near(target: int, extent: int) -> int:
    """Largest tile <= ``target`` whose padded waste over ``extent`` is
    small: prefer exact divisors of the extent, else the target itself."""
    t = max(1, min(target, extent))
    for cand in range(t, 0, -1):
        if extent % cand == 0:
            return cand
        if cand <= t - 4:        # nothing nearby divides: accept padding
            break
    return t


# The matmul "mnk" plans keep the live chunk inside CACHE_BUDGET; for the
# fused conv that cap is measurably wrong -- the empirical winner at CNN
# shapes is a full-plane tile whose (bh*bw, bf, kc) chunk far exceeds it
# (the slab is walked once, not re-swept per grid step) -- so conv "mnk"
# candidates get a looser ceiling and autotune arbitrates.
CONV_MNK_CHUNK_BUDGET = 8 * 1024 * 1024


def candidate_conv2d_plans(oh: int, ow: int, kh: int, kw: int, cin: int,
                           cout: int, *, stride=(1, 1), itemsize: int = 4,
                           pm_layout: str = "mkn",
                           vmem_budget: int = VMEM_BUDGET
                           ) -> list["Conv2DPlan"]:
    """Enumerate budget-feasible plans for a fused 2D conv call.

    Spatial tiles include the exact (oh, ow) extents (a full-plane tile
    has zero padding waste and maximal window reuse); channel/filter
    tiles follow the matmul K/N candidate ladders.  ``kc`` chunks each
    tap's bk-wide channel reduction; "mnk" plans cap it at
    :data:`KC_MNK_MAX` like the matmul planner.  "mkn" (TPU) plans keep
    the Mosaic block rules: ``bw`` a sublane multiple (the kernel folds
    (bh, bw) into rows), and ``bk`` the whole channel extent or one
    128-lane group (Mosaic refuses a tap view at an unaligned dynamic
    sublane offset that spans more lane groups), taken in one chunk.
    """
    sh, sv = stride
    bhs = sorted({max(1, min(c, oh)) for c in (4, 8, 16, 32)} | {oh})
    if pm_layout == "mkn":
        bws = sorted({min(c, _round_up(ow, SUBLANE))
                      for c in (8, 16, 32, 64, 128)})
        bks = [min(cin, LANE)]
    else:
        bws = sorted({_divisor_near(c, ow) for c in (8, 16, 32, 64, 128)}
                     | {ow})
        bks = sorted({max(1, min(c, cin)) for c in (8, 32, 64, 128)}
                     | {cin})
    bfs = sorted({_align_lane(c, cout) for c in (64, 128)}
                 | {max(1, min(cout, 256))})
    plans = []
    for bh in bhs:
        for bw in bws:
            for bk in bks:
                for bf in bfs:
                    for kc in _kc_ladder(bk, pm_layout):
                        if pm_layout == "mnk" and kc > 1 and (
                                kc > KC_MNK_MAX or
                                bh * bw * bf * kc * itemsize
                                > CONV_MNK_CHUNK_BUDGET):
                            continue
                        cost = cm.conv2d_grid_cost(
                            oh, ow, kh, kw, cin, cout, bh, bw, bk, kc, bf,
                            sh, sv, itemsize=itemsize)
                        if cost.vmem_bytes <= vmem_budget:
                            plans.append(
                                Conv2DPlan(bh, bw, bk, kc, bf, pm_layout))
    if not plans:      # degenerate shapes: one minimal feasible plan
        bk = max(1, min(8, cin))
        plans = [Conv2DPlan(max(1, min(4, oh)), max(1, min(8, ow)), bk,
                            _align_kc(8, bk), max(1, min(cout, 64)),
                            pm_layout)]
    return plans


@functools.lru_cache(maxsize=1024)
def _model_pick_conv2d(oh: int, ow: int, kh: int, kw: int, cin: int,
                       cout: int, *, stride: tuple, itemsize: int,
                       pm_layout: str) -> "Conv2DPlan":
    sh, sv = stride
    plans = candidate_conv2d_plans(oh, ow, kh, kw, cin, cout, stride=stride,
                                   itemsize=itemsize, pm_layout=pm_layout)
    return min(plans, key=lambda p: cm.conv2d_grid_cost(
        oh, ow, kh, kw, cin, cout, *p.astuple(), sh, sv,
        itemsize=itemsize).weighted)


@functools.lru_cache(maxsize=1024)
def _model_pick(m: int, n: int, k: int, *, itemsize: int, n_row_ops: int,
                n_col_ops: int, n_acc: int, pm_layout: str) -> TilePlan:
    plans = candidate_plans(m, n, k, itemsize=itemsize, n_row_ops=n_row_ops,
                            n_col_ops=n_col_ops, n_acc=n_acc,
                            pm_layout=pm_layout)
    costs = {
        p: cm.pm_grid_cost(m, n, k, *p.astuple(), itemsize=itemsize,
                           n_row_ops=n_row_ops, n_col_ops=n_col_ops,
                           n_acc=n_acc).weighted
        for p in plans
    }
    return min(plans, key=lambda p: costs[p])


# --------------------------------------------------------------------------
# Empirical cache
# --------------------------------------------------------------------------

# In-process memo of loaded cache files, keyed by path -- an autotune
# against an explicit scratch path must not repoint default-path lookups.
_CACHE: dict[str, dict] = {}
# Cache keys already warned about (warn ONCE per key per process).
_WARNED_MISS: set[str] = set()
# Autotune-cache lookup outcomes, published to the process-default obs
# registry (per-engine/per-trainer registries track run-scoped state; the
# plan cache is process-wide, so its counters are too).  Bound once: the
# planners run per eager GEMM call and must not pay a registry lookup.
_HIT_COUNTER = obs_metrics.default_registry().counter(
    "tuning_cache_hits_total", help="autotune-cache lookups served")
_MISS_COUNTER = obs_metrics.default_registry().counter(
    "tuning_cache_misses_total",
    help="autotune-cache lookups that fell back to the cost model")


def autotune_enabled() -> bool:
    """``REPRO_AUTOTUNE=0`` disables the autotune cache entirely: no file
    lookup, no miss warning -- pure cost-model planning (the escape hatch
    for hermetic runs and for benchmarking the model-mode planner)."""
    return os.environ.get("REPRO_AUTOTUNE", "1") != "0"


def cache_path() -> str:
    return os.environ.get(
        "REPRO_TUNING_CACHE",
        os.path.join(os.path.dirname(__file__), "tuning_cache.json"))


def _key(kind: str, m: int, n: int, k: int, dtype, batch: int = 1) -> str:
    base = f"{kind}:{m}x{n}x{k}:{jnp.dtype(dtype).name}"
    return f"{kind}:{batch}b:{m}x{n}x{k}:{jnp.dtype(dtype).name}" \
        if batch > 1 else base


def _note_cache_lookup(key: str, hit: bool) -> None:
    """Publish one autotune-cache lookup outcome (trace event + default-
    registry counters)."""
    obs_trace.event("tuning.cache", cat="dispatch", key=key, hit=hit)
    (_HIT_COUNTER if hit else _MISS_COUNTER).inc()


def _warn_cache_miss(key: str, plan_entry: Optional[dict] = None) -> None:
    if key in _WARNED_MISS:
        return
    _WARNED_MISS.add(key)
    if key.startswith("sq_conv2d:"):
        fn = "autotune_conv2d"
    elif key.startswith("sq_paged_attn:"):
        fn = "autotune_paged_attn"
    else:
        fn = "autotune_matmul"
    # the ready-to-paste JSON cache entry (the cost-model pick this call
    # will serve): drop it into tuning_cache.json to pin the plan, or
    # replace it with an autotune winner later -- no key re-derivation
    paste = ""
    if plan_entry is not None:
        paste = (f"  Cost-model entry, ready to paste into "
                 f"{cache_path()}: "
                 + json.dumps({key: plan_entry}, sort_keys=True))
    warnings.warn(
        f"autotune cache miss for {key}; falling back to the cost-model "
        f"plan.  Run kernels.tuning.{fn} once for this shape to "
        f"cache an empirical winner, or set REPRO_AUTOTUNE=0 to silence."
        + paste,
        stacklevel=3)


def load_cache(path: Optional[str] = None) -> dict:
    p = path or cache_path()
    if p not in _CACHE:
        try:
            with open(p) as f:
                _CACHE[p] = json.load(f)
        except (OSError, ValueError):
            _CACHE[p] = {}
    return _CACHE[p]


def save_cache(cache: dict, path: Optional[str] = None) -> str:
    p = path or cache_path()
    with open(p, "w") as f:
        json.dump(cache, f, indent=1, sort_keys=True)
    _CACHE[p] = dict(cache)
    return p


def clear_cache() -> None:
    """Drop the in-process cache memo and the warn-once ledger (tests;
    after external file edits)."""
    _CACHE.clear()
    _WARNED_MISS.clear()


# --------------------------------------------------------------------------
# Public planning entry points
# --------------------------------------------------------------------------

def plan_matmul(m: int, n: int, k: int, dtype=jnp.float32, *,
                bm: Optional[int] = None, bn: Optional[int] = None,
                bk: Optional[int] = None, kc: Optional[int] = None,
                pm_layout: str = "mkn", kind: str = "sq_matmul",
                n_row_ops: int = 1, n_col_ops: int = 1,
                n_acc: int = 1, batch: int = 1) -> TilePlan:
    """Pick the (bm, bn, bk, kc, pm_layout) plan for a matmul-shaped call.

    ``pm_layout`` is backend-driven, not cost-modelled: callers pass "mnk"
    for interpret/CPU execution and "mkn" for real TPU lowering (see
    kernels.sq_matmul for what each means).

    ``batch`` > 1 plans a batched GEMM (leading batch grid axis, one
    element per grid step).  The per-step working set is identical to the
    unbatched case -- the batch axis multiplies every candidate's grid
    count uniformly, so cost-model *ranking* is batch-invariant -- but the
    autotune cache is keyed per batch size (pipelining behaviour differs).

    Precedence: explicit user tiles > autotune cache > cost model.  On an
    autotune-cache miss the planner warns ONCE per (kind, shape, dtype)
    key and falls back to the cost-model plan; ``REPRO_AUTOTUNE=0``
    disables cache consultation (and the warning) entirely.  Explicit
    values are still clamped to the (padded) operand extent and aligned to
    the hardware granules, which may round them up (see module docstring).

    Fully-specified plans skip cache and model (alignment still applies,
    e.g. bm=100 rounds up to the next sublane multiple)::

        >>> from repro.kernels import tuning
        >>> tuning.plan_matmul(256, 256, 512, bm=64, bn=128, bk=128, kc=32)
        TilePlan(bm=64, bn=128, bk=128, kc=32, pm_layout='mkn')
        >>> tuning.plan_matmul(256, 256, 512, bm=100, bn=128, bk=128).bm
        104
    """
    if bm is not None and bn is not None and bk is not None:
        # Fully specified: no enumeration, no cache consult.  Kept cheap on
        # purpose -- benchmark/autotune loops plan on every call.
        pbk = _align_lane(bk, k)
        return TilePlan(_align_bm(bm, m), _align_lane(bn, n), pbk,
                        _align_kc(kc if kc is not None else pbk, pbk),
                        pm_layout)
    itemsize = jnp.dtype(dtype).itemsize
    use_cache = autotune_enabled()
    key = _key(kind, m, n, k, dtype, batch)
    cached = load_cache().get(key) if use_cache else None
    if cached is not None and bm is None and bn is None and bk is None \
            and kc is None \
            and str(cached.get("pm_layout", pm_layout)) == pm_layout:
        # Serve the cache only for the requested layout: an autotune run on
        # a CPU host must not dictate "mnk" to a TPU caller.
        _note_cache_lookup(key, hit=True)
        return TilePlan(*(int(cached[f]) for f in ("bm", "bn", "bk", "kc")),
                        pm_layout)
    base = _model_pick(m, n, k, itemsize=itemsize, n_row_ops=n_row_ops,
                       n_col_ops=n_col_ops, n_acc=n_acc, pm_layout=pm_layout)
    pbm = _align_bm(bm if bm is not None else base.bm, m)
    pbn = _align_lane(bn if bn is not None else base.bn, n)
    pbk = _align_lane(bk if bk is not None else base.bk, k)
    pkc = _align_kc(kc if kc is not None else base.kc, pbk)
    plan = TilePlan(pbm, pbn, pbk, pkc, pm_layout)
    if use_cache and cached is None and bm is None and bn is None \
            and bk is None and kc is None:
        _note_cache_lookup(key, hit=False)
        _warn_cache_miss(key, {"bm": plan.bm, "bn": plan.bn, "bk": plan.bk,
                               "kc": plan.kc, "pm_layout": plan.pm_layout})
    return plan


def plan_conv(k_out: int, n_taps: int, dtype=jnp.float32, *,
              bo: Optional[int] = None, tb: Optional[int] = None,
              interpret: bool = False) -> tuple[int, int]:
    """Pick (bo, tb) for the 1D conv kernel: ``bo`` outputs per grid step,
    ``tb`` taps folded per vectorized chunk (the tap-block width).

    The tap-block width is backend-driven like the matmul pm_layout: on
    TPU a tb-wide (tb, bo) PM block keeps the VPU lanes busy, but under
    interpret/CPU execution the rank-1 tap walk is measurably faster
    (the stacked shifted windows materialize to no benefit), so interpret
    plans default to tb=1.
    """
    del dtype
    pbo = bo if bo is not None else 256
    pbo = max(1, min(pbo, _round_up(k_out, LANE) if k_out >= LANE else k_out))
    ptb = tb if tb is not None else (1 if interpret else 8)
    ptb = max(1, min(ptb, n_taps))
    return pbo, ptb


def _conv2d_key(h: int, w: int, kh: int, kw: int, cin: int, cout: int,
                dtype, stride=(1, 1), batch: int = 1) -> str:
    sh, sv = stride
    base = (f"sq_conv2d:{h}x{w}:k{kh}x{kw}:s{sh}x{sv}:c{cin}->{cout}:"
            f"{jnp.dtype(dtype).name}")
    return f"{base}:b{batch}" if batch > 1 else base


def plan_conv2d(h: int, w: int, kh: int, kw: int, cin: int, cout: int,
                dtype=jnp.float32, *, stride=(1, 1), batch: int = 1,
                bh: Optional[int] = None, bw: Optional[int] = None,
                bk: Optional[int] = None, kc: Optional[int] = None,
                bf: Optional[int] = None,
                pm_layout: str = "mkn") -> Conv2DPlan:
    """Pick the (bh, bw, bk, kc, bf, pm_layout) plan for a fused 2D conv.

    ``h`` / ``w`` are the *padded* input spatial extents the kernel will
    see (user padding already applied); the output extents follow from
    ``kh``/``kw`` and ``stride``.  ``dtype`` is the resolved *accumulator*
    dtype (callers widen via ``sq.accum_dtype`` first, exactly like
    :func:`plan_matmul` -- it keys the cache and sizes the VMEM terms,
    and is not re-widened here).  Like :func:`plan_matmul`: explicit
    user tiles > autotune cache (keyed on (h, w, kh, kw, cin, cout,
    stride, dtype) and served only layout-matched) > the cost model
    (:func:`repro.core.cost_model.conv2d_grid_cost` -- PM lane-ops plus
    window-load traffic, so plans maximizing per-step window reuse win).
    On a cache miss the planner warns once per key; ``REPRO_AUTOTUNE=0``
    silences (see :func:`autotune_enabled`).

    Fully-specified plans skip cache and model entirely (``kc`` is still
    clamped to divide the per-tap ``bk`` reduction axis)::

        >>> from repro.kernels import tuning
        >>> tuning.plan_conv2d(34, 34, 3, 3, 64, 64, bh=16, bw=32, bk=64,
        ...                    kc=32, bf=64, pm_layout="mnk")
        Conv2DPlan(bh=16, bw=32, bk=64, kc=32, bf=64, pm_layout='mnk')
    """
    sh, sv = stride
    oh = (h - kh) // sh + 1
    ow = (w - kw) // sv + 1
    if oh <= 0 or ow <= 0:
        raise ValueError(f"kernel {kh}x{kw} larger than padded input "
                         f"{h}x{w}")
    explicit = (bh, bw, bk, bf)
    if all(v is not None for v in explicit):
        pbk = max(1, min(bk, cin))
        return Conv2DPlan(max(1, min(bh, oh)), max(1, min(bw, ow)), pbk,
                          _align_kc(kc if kc is not None else pbk, pbk),
                          max(1, min(bf, cout)), pm_layout)
    itemsize = jnp.dtype(dtype).itemsize
    use_cache = autotune_enabled()
    key = _conv2d_key(h, w, kh, kw, cin, cout, dtype, stride, batch)
    cached = load_cache().get(key) if use_cache else None
    no_user = all(v is None for v in (bh, bw, bk, kc, bf))
    if cached is not None and no_user \
            and str(cached.get("pm_layout", pm_layout)) == pm_layout:
        _note_cache_lookup(key, hit=True)
        return Conv2DPlan(*(int(cached[f])
                            for f in ("bh", "bw", "bk", "kc", "bf")),
                          pm_layout)
    base = _model_pick_conv2d(oh, ow, kh, kw, cin, cout, stride=(sh, sv),
                              itemsize=itemsize, pm_layout=pm_layout)
    pbh = max(1, min(bh if bh is not None else base.bh, oh))
    # an "mkn" tile may overhang ow up to the sublane granule (the wrapper
    # pads the output grid); clamping it to ow would break that granule
    ow_cap = _round_up(ow, SUBLANE) if pm_layout == "mkn" else ow
    pbw = max(1, min(bw if bw is not None else base.bw, ow_cap))
    pbk = max(1, min(bk if bk is not None else base.bk, cin))
    pbf = max(1, min(bf if bf is not None else base.bf, cout))
    pkc = _align_kc(kc if kc is not None else base.kc, pbk)
    plan = Conv2DPlan(pbh, pbw, pbk, pkc, pbf, pm_layout)
    if use_cache and cached is None and no_user:
        _note_cache_lookup(key, hit=False)
        _warn_cache_miss(key, {"bh": plan.bh, "bw": plan.bw, "bk": plan.bk,
                               "kc": plan.kc, "bf": plan.bf,
                               "pm_layout": plan.pm_layout})
    return plan


def _paged_key(rows: int, hd: int, tile: int, kv_heads: int, dtype,
               dv: Optional[int] = None) -> str:
    """Cache key of a paged-attention plan: the KV head count leads, so no
    entry of the one-head-one-block kernel's ``rows x hd x block_size``
    keys is ever served to the whole-tile kernel.  A value width other
    than the head dim (latent mode) follows the head dim as ``/<dv>``."""
    d = hd if dv is None or dv == hd else f"{hd}/{dv}"
    return (f"sq_paged_attn:{kv_heads}kv:{rows}x{d}x{tile}:"
            f"{jnp.dtype(dtype).name}")


def plan_paged_attn(rows: int, hd: int, tile: int,
                    dtype=jnp.float32, *, kv_heads: int = 1,
                    kc_qk: Optional[int] = None,
                    kc_pv: Optional[int] = None,
                    pm_layout: str = "mkn",
                    dv: Optional[int] = None) -> PagedAttnPlan:
    """Pick the (kc_qk, kc_pv, pm_layout) plan for a fused paged-attention
    call.  ``rows`` is the score-tile row count (``S * G``: query tile x
    GQA group), ``hd`` the head dim, ``tile`` the tokens one grid step
    walks (:func:`repro.kernels.sq_paged_attn.tile_blocks` table entries
    of ``block_size``), ``dtype`` the pools' stored dtype,
    ``kv_heads`` the heads one step serves and ``dv`` the value width
    (default ``hd``; the latent kernel's p.v runs over ``dv < hd``).

    Same precedence as :func:`plan_matmul`: explicit knobs > autotune
    cache (keyed ``sq_paged_attn:<kv_heads>kv:<rows>x<hd>x<tile>:<dtype>``,
    served layout-matched) > the model pick.  The model pick mirrors the
    matmul kc rule: "mnk" caps the chunk at :data:`KC_MNK_MAX` (the
    measured interpret-mode sweet spot); "mkn" takes the full axis (the
    rank-2 PM broadcast is widest-is-best on the VPU, and a chunked walk
    would need lane-aligned chunks).  On a cache miss the planner warns
    once per key; ``REPRO_AUTOTUNE=0`` silences.

    Fully-specified plans skip cache and model (each kc is still clamped
    to divide its axis)::

        >>> from repro.kernels import tuning
        >>> tuning.plan_paged_attn(8, 64, 128, kc_qk=32, kc_pv=16,
        ...                        pm_layout="mnk")
        PagedAttnPlan(kc_qk=32, kc_pv=16, pm_layout='mnk')
    """
    if kc_qk is not None and kc_pv is not None:
        return PagedAttnPlan(_align_kc(kc_qk, hd), _align_kc(kc_pv, tile),
                             pm_layout)
    use_cache = autotune_enabled()
    key = _paged_key(rows, hd, tile, kv_heads, dtype, dv)
    cached = load_cache().get(key) if use_cache else None
    if cached is not None and kc_qk is None and kc_pv is None \
            and str(cached.get("pm_layout", pm_layout)) == pm_layout:
        _note_cache_lookup(key, hit=True)
        return PagedAttnPlan(int(cached["kc_qk"]), int(cached["kc_pv"]),
                             pm_layout)
    if pm_layout == "mnk":
        base_qk = _align_kc(min(KC_MNK_MAX, hd), hd)
        base_pv = _align_kc(min(KC_MNK_MAX, tile), tile)
    else:
        base_qk, base_pv = hd, tile
    plan = PagedAttnPlan(
        _align_kc(kc_qk if kc_qk is not None else base_qk, hd),
        _align_kc(kc_pv if kc_pv is not None else base_pv, tile),
        pm_layout)
    if use_cache and cached is None and kc_qk is None and kc_pv is None:
        _note_cache_lookup(key, hit=False)
        _warn_cache_miss(key, {"kc_qk": plan.kc_qk, "kc_pv": plan.kc_pv,
                               "pm_layout": plan.pm_layout})
    return plan


# --------------------------------------------------------------------------
# Empirical autotune
# --------------------------------------------------------------------------

def autotune_matmul(shapes: Iterable[tuple[int, int, int]],
                    dtype=jnp.float32, *, kind: str = "sq_matmul",
                    pm_layouts: tuple[str, ...] = ("mnk", "mkn"),
                    max_candidates: int = 8, reps: int = 3,
                    path: Optional[str] = None, batch: int = 1,
                    verbose: bool = False) -> dict:
    """Sweep candidate plans through the wall-clock harness; cache winners.

    For each (m, n, k) the model-ranked top ``max_candidates`` plans *per
    layout* are timed via :func:`benchmarks.kernel_timing.time_plan` and the
    fastest is written to the JSON cache that :func:`plan_matmul` consults.
    Returns the updated cache dict.

    ``dtype`` is the *input* dtype the kernel will be fed (operands are
    generated in it); candidate feasibility and the cache key both use the
    accumulator dtype, matching what kernels.ops looks up at plan time,
    and candidate generation uses the kind's operand/accumulator counts
    (a cpm plan is costed as a cpm plan, not as a sq_matmul one).

    ``batch`` > 1 tunes the batched (leading-batch-grid-axis) kernel and
    writes the batch-keyed cache entry that ``plan_matmul(batch=...)``
    looks up (sq_matmul only -- the cpm kernels have no batched path).
    """
    from benchmarks import kernel_timing as kt     # lazy: benchmarks optional

    acc_dtype = sq.accum_dtype(jnp.dtype(dtype))
    itemsize = jnp.dtype(acc_dtype).itemsize
    nro, nco, nacc = KIND_COUNTS.get(kind, (1, 1, 1))
    cache = dict(load_cache(path))
    for (m, n, k) in shapes:
        best, best_us = None, float("inf")
        for layout in pm_layouts:
            plans = candidate_plans(m, n, k, itemsize=itemsize,
                                    n_row_ops=nro, n_col_ops=nco,
                                    n_acc=nacc, pm_layout=layout)
            plans.sort(key=lambda p: cm.pm_grid_cost(
                m, n, k, *p.astuple(), itemsize=itemsize, n_row_ops=nro,
                n_col_ops=nco, n_acc=nacc).weighted)
            for plan in plans[:max_candidates]:
                us = kt.time_plan(kind, m, n, k, dtype, plan, reps=reps,
                                  batch=batch)
                if verbose:
                    print(f"  {kind} {m}x{n}x{k} {plan} -> {us:.1f}us")
                if us < best_us:
                    best, best_us = plan, us
        cache[_key(kind, m, n, k, acc_dtype, batch)] = {
            "bm": best.bm, "bn": best.bn, "bk": best.bk, "kc": best.kc,
            "pm_layout": best.pm_layout, "us_per_call": best_us,
        }
    save_cache(cache, path)
    return cache


def autotune_conv2d(shapes: Iterable[tuple[int, int, int, int, int, int]],
                    dtype=jnp.float32, *, stride=(1, 1),
                    pm_layouts: tuple[str, ...] = ("mnk", "mkn"),
                    max_candidates: int = 8, reps: int = 3,
                    path: Optional[str] = None, batch: int = 1,
                    verbose: bool = False) -> dict:
    """Sweep fused-conv2d candidate plans; cache winners.

    ``shapes`` holds (h, w, kh, kw, cin, cout) tuples where h/w are the
    *padded* input extents (what :func:`plan_conv2d` keys on).  The
    model-ranked top ``max_candidates`` plans per layout are timed via
    :func:`benchmarks.kernel_timing.time_conv2d_plan`; the fastest is
    written to the same JSON cache the planner consults.
    """
    from benchmarks import kernel_timing as kt     # lazy: benchmarks optional

    acc_dtype = sq.accum_dtype(jnp.dtype(dtype))
    itemsize = jnp.dtype(acc_dtype).itemsize
    sh, sv = stride
    cache = dict(load_cache(path))
    for (h, w, kh, kw, cin, cout) in shapes:
        oh = (h - kh) // sh + 1
        ow = (w - kw) // sv + 1
        best, best_us = None, float("inf")
        for layout in pm_layouts:
            plans = candidate_conv2d_plans(
                oh, ow, kh, kw, cin, cout, stride=stride, itemsize=itemsize,
                pm_layout=layout)
            plans.sort(key=lambda p: cm.conv2d_grid_cost(
                oh, ow, kh, kw, cin, cout, p.bh, p.bw, p.bk, p.kc, p.bf,
                sh, sv, itemsize=itemsize).weighted)
            for plan in plans[:max_candidates]:
                us = kt.time_conv2d_plan(h, w, kh, kw, cin, cout, dtype,
                                         plan, stride=stride, reps=reps,
                                         batch=batch)
                if verbose:
                    print(f"  sq_conv2d {h}x{w} k{kh}x{kw} c{cin}->{cout} "
                          f"{plan} -> {us:.1f}us")
                if us < best_us:
                    best, best_us = plan, us
        cache[_conv2d_key(h, w, kh, kw, cin, cout, acc_dtype, stride,
                          batch)] = {
            "bh": best.bh, "bw": best.bw, "bk": best.bk, "kc": best.kc,
            "bf": best.bf, "pm_layout": best.pm_layout,
            "us_per_call": best_us,
        }
    save_cache(cache, path)
    return cache


def autotune_paged_attn(shapes: Iterable[tuple[int, ...]],
                        dtype=jnp.float32, *, nb: int = 8,
                        pm_layouts: tuple[str, ...] = ("mnk", "mkn"),
                        reps: int = 3, path: Optional[str] = None,
                        verbose: bool = False) -> dict:
    """Sweep the fused paged-attention kc knobs; cache winners.

    ``shapes`` holds (rows, hd, block_size) or (rows, hd, block_size,
    kv_heads) tuples (one KV head by default).  Timing is self-contained:
    a synthetic single-sequence pool of ``dtype`` walked over ``nb``
    table entries, every one live, so each grid step does the
    shape-exact contraction work of a full tile and the kc ranking
    transfers to any batch/table length.  Winners land in the same JSON
    cache the planner consults, keyed on the tile the kernel walks.
    """
    import time as _time

    import jax
    import numpy as np

    from repro.kernels.sq_paged_attn import sq_paged_attn, tile_blocks

    cache = dict(load_cache(path))
    for shape in shapes:
        rows, hd, block_size, kv = (tuple(shape) + (1,))[:4]
        tile = tile_blocks(block_size, nb) * block_size
        pool = nb * block_size
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=(1, rows, kv, 1, hd)), jnp.float32)
        kp = jnp.asarray(rng.normal(size=(pool, kv, hd)), dtype)
        vp = jnp.asarray(rng.normal(size=(pool, kv, hd)), dtype)
        tables = jnp.arange(nb, dtype=jnp.int32)[None, :]
        pos_pool = jnp.arange(pool, dtype=jnp.int32)
        q_pos = jnp.full((1, rows), pool - 1, jnp.int32)
        best, best_us = None, float("inf")
        for layout in pm_layouts:
            qk_cands = sorted({_align_kc(c, hd) for c in KC_CANDIDATES})
            pv_cands = sorted({_align_kc(c, tile) for c in KC_CANDIDATES})
            if layout == "mnk":
                qk_cands = [c for c in qk_cands if c <= KC_MNK_MAX] or [1]
                pv_cands = [c for c in pv_cands if c <= KC_MNK_MAX] or [1]
            for kc_qk in qk_cands:
                for kc_pv in pv_cands:
                    fn = jax.jit(functools.partial(
                        sq_paged_attn, block_size=block_size,
                        kc_qk=kc_qk, kc_pv=kc_pv, pm_layout=layout))
                    fn(q, kp, vp, tables, pos_pool,
                       q_pos).block_until_ready()      # compile
                    t0 = _time.perf_counter()
                    for _ in range(reps):
                        fn(q, kp, vp, tables, pos_pool,
                           q_pos).block_until_ready()
                    us = (_time.perf_counter() - t0) / reps * 1e6
                    if verbose:
                        print(f"  sq_paged_attn {kv}kv {rows}x{hd}x{tile} "
                              f"kc_qk={kc_qk} kc_pv={kc_pv} {layout} "
                              f"-> {us:.1f}us")
                    if us < best_us:
                        best = PagedAttnPlan(kc_qk, kc_pv, layout)
                        best_us = us
        cache[_paged_key(rows, hd, tile, kv, dtype)] = {
            "kc_qk": best.kc_qk, "kc_pv": best.kc_pv,
            "pm_layout": best.pm_layout, "us_per_call": best_us,
        }
    save_cache(cache, path)
    return cache
