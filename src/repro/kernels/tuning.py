"""Tile planner for the Pallas square-kernel suite.

Every kernel runs one PM schedule (:mod:`repro.kernels.pm_blocks`), on
every backend, named by :func:`pm_schedule` from the kernel kind alone:

- ``sq_matmul`` walks K one row at a time with its output tile in vregs
  (:data:`ROW_WALK`, ``pm_blocks.pm_row_walk``);
- every other kernel -- CPM3/CPM4, the fused conv2d, the grouped-expert
  and the paged-attention kernels -- reduces chunked PM blocks
  (:data:`CHUNKED`, ``pm_blocks.pm_chunked_reduce``).

The planner picks the ``(bm, bn, bk, kc)`` block plan for every kernel
call site:

- ``bm`` x ``bn`` is the VMEM-resident output tile (``bm`` rounded to the
  8-sublane granule, ``bn``/``bk`` to the 128-lane granule whenever the
  operand is large enough to allow it);
- ``bk`` is the K-slab streamed per grid step;
- ``kc`` is the chunk width of the rank-2 broadcast squaring inside a step
  (the live PM block is (bm, kc, bn)), or for the row walk the K group
  one loop iteration unrolls.  A plan chunks in whole 128-lane groups or
  takes the slab in one chunk: Mosaic only lowers a dynamic chunk start
  on the lane axis when it is a multiple of 128 (see kernels.pm_blocks).

A plan is a pure function of the call's shape and dtype: candidates are
ranked by the analytical cost in :mod:`repro.core.cost_model`
(``pm_walk_grid_cost`` for the row walk, ``pm_grid_cost`` for the chunked
blocks, ``conv2d_grid_cost`` for the fused conv): VPU lane-ops plus
per-grid-step and per-chunk issue overheads, subject to a VMEM budget
(and, for the row walk, a vreg budget).

User-supplied ``bm``/``bn``/``bk``/``kc`` win over the cost model.  They
are clamped to the (padded) operand extent and aligned to the hardware
granules -- which may round a value *up* to the next sublane/lane
multiple (e.g. bm=100 -> 104): padding to an aligned tile is cheaper than
the layout penalty of a misaligned one.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax.numpy as jnp

from repro.core import cost_model as cm
from repro.kernels.pm_blocks import KMN_GROUP

__all__ = ["TilePlan", "Conv2DPlan", "PagedAttnPlan", "pm_schedule",
           "plan_matmul", "plan_grouped_matmul", "plan_conv", "plan_conv2d",
           "plan_paged_attn", "candidate_plans", "candidate_conv2d_plans"]

SUBLANE = 8            # f32 sublane granule (second-minor axis)
LANE = 128             # lane granule (minor axis)
VMEM_BUDGET = 12 * 1024 * 1024      # leave headroom under the ~16 MB v5e VMEM
# The two PM schedules (kernels.pm_blocks), by their loop order.
ROW_WALK = "kmn"
CHUNKED = "mkn"
# Operand/accumulator multiplicities per kernel kind: the CPM kernels
# stream two row planes + two column planes and hold two scratch
# accumulators, so their VMEM feasibility is ~2x a plain sq_matmul's.
KIND_COUNTS = {
    "sq_matmul": (1, 1, 1),
    "cpm3_matmul": (2, 2, 2),
    "cpm4_matmul": (2, 2, 2),
}


def pm_schedule(kind: str) -> str:
    """The PM schedule the ``kind`` kernel runs: :data:`ROW_WALK` for
    ``sq_matmul``, :data:`CHUNKED` for every other kernel.  On the TPU
    the row walk ran up to 7.2x faster than the chunked blocks at the
    benchmark cells' ``sq_matmul`` shapes; the other kernels' tiles were
    never timed on it."""
    return ROW_WALK if kind == "sq_matmul" else CHUNKED


@dataclasses.dataclass(frozen=True)
class TilePlan:
    bm: int
    bn: int
    bk: int
    kc: int

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


def _lane_divisors(extent: int, cap: int) -> set[int]:
    """Whole-lane tiles up to ``cap`` that divide the lane-multiple
    ``extent`` exactly."""
    return {c for c in range(LANE, min(cap, extent) + 1, LANE)
            if extent % c == 0}


def _kc_ladder(bk: int, schedule: str) -> list[int]:
    """The chunk widths a plan may take for a ``bk``-wide slab: one lane
    group (the whole slab when it is narrower) for the row walk; whole
    lane groups dividing ``bk``, or the whole slab, for the chunked
    blocks."""
    if schedule == ROW_WALK:
        return [KMN_GROUP if bk % KMN_GROUP == 0 else bk]
    return sorted({bk} | {c for c in range(LANE, bk, LANE) if bk % c == 0})


def _plan_cost(kind: str, m: int, n: int, k: int, plan: TilePlan,
               itemsize: int) -> cm.TileCost:
    """The cost of a matmul plan under ``kind``'s schedule."""
    if pm_schedule(kind) == ROW_WALK:
        return cm.pm_walk_grid_cost(m, n, k, *plan.astuple(), itemsize)
    return cm.pm_grid_cost(m, n, k, *plan.astuple(), itemsize,
                           *KIND_COUNTS.get(kind, (1, 1, 1)))


def candidate_plans(m: int, n: int, k: int, *, kind: str = "sq_matmul",
                    itemsize: int = 4,
                    vmem_budget: int = VMEM_BUDGET) -> list[TilePlan]:
    """Enumerate aligned, budget-feasible plans for an (m, n, k)
    contraction of the ``kind`` kernel.

    Every plan respects the VMEM budget.  Row-walk plans also keep their
    accumulator tile within :data:`repro.core.cost_model.KMN_ACC_VREGS`
    vregs, and take as ``bn``/``bk`` the whole-lane divisors of their
    lane-aligned axis, ``bn`` up to 4096 lanes (one lane broadcast of
    ``a`` then serves more PM vregs).

    The ladders always include the full-extent tile on every axis (a
    single-grid-step plan pays zero padding waste and no pipeline
    overhead; VMEM feasibility prunes it where it cannot fit).
    """
    schedule = pm_schedule(kind)
    bms = sorted({_align_bm(c, m) for c in (8, 32, 64, 128, 256, 512)}
                 | {_align_bm(m, m)})
    bns = sorted({_align_lane(c, n) for c in (128, 256, 512)}
                 | {_align_lane(n, n)})
    bks = sorted({_align_lane(c, k) for c in (128, 256, 512)}
                 | {_align_lane(k, k)})
    if schedule == ROW_WALK:
        # whole-lane tiles that pad nothing past the lane granule: the
        # walk's cost is per padded term, and a prepared weight laid out
        # at that granule is then reused as it is
        bms = sorted(set(bms) | {_align_bm(16, m)})
        bns = sorted(_lane_divisors(_round_up(n, LANE), 4096)) \
            if n >= LANE else [n]
        bks = sorted(_lane_divisors(_round_up(k, LANE), _round_up(k, LANE))) \
            if k >= LANE else [k]
    plans = []
    for bm in bms:
        for bn in bns:
            if schedule == ROW_WALK and \
                    cm.kmn_acc_vregs(bm, bn) > cm.KMN_ACC_VREGS:
                continue
            for bk in bks:
                for kc in _kc_ladder(bk, schedule):
                    plan = TilePlan(bm, bn, bk, kc)
                    cost = _plan_cost(kind, m, n, k, plan, itemsize)
                    if cost.vmem_bytes <= vmem_budget:
                        plans.append(plan)
    if not plans:      # degenerate shapes: fall back to a single minimal plan
        bm = _align_bm(8, m)
        bn = _align_lane(LANE, n)
        bk = _align_lane(LANE, k)
        plans = [TilePlan(bm, bn, bk, _align_kc(8, bk))]
    return plans


def candidate_conv2d_plans(oh: int, ow: int, kh: int, kw: int, cin: int,
                           cout: int, *, stride=(1, 1), itemsize: int = 4,
                           vmem_budget: int = VMEM_BUDGET
                           ) -> list["Conv2DPlan"]:
    """Enumerate budget-feasible plans for a fused 2D conv call.

    Spatial tiles include the exact ``oh`` extent (a full-height tile has
    zero padding waste and maximal window reuse); filter tiles follow the
    matmul N ladder.  Plans keep the Mosaic block rules: ``bw`` a sublane
    multiple (the kernel folds (bh, bw) into rows), and ``bk`` the whole
    channel extent or one 128-lane group (Mosaic refuses a tap view at an
    unaligned dynamic sublane offset that spans more lane groups), taken
    in one chunk.
    """
    sh, sv = stride
    bhs = sorted({max(1, min(c, oh)) for c in (4, 8, 16, 32)} | {oh})
    bws = sorted({min(c, _round_up(ow, SUBLANE)) for c in (8, 16, 32, 64, 128)})
    bk = min(cin, LANE)
    bfs = sorted({_align_lane(c, cout) for c in (64, 128)}
                 | {max(1, min(cout, 256))})
    plans = []
    for bh in bhs:
        for bw in bws:
            for bf in bfs:
                for kc in _kc_ladder(bk, CHUNKED):
                    cost = cm.conv2d_grid_cost(
                        oh, ow, kh, kw, cin, cout, bh, bw, bk, kc, bf,
                        sh, sv, itemsize=itemsize)
                    if cost.vmem_bytes <= vmem_budget:
                        plans.append(Conv2DPlan(bh, bw, bk, kc, bf))
    if not plans:      # degenerate shapes: one minimal feasible plan
        bk = max(1, min(8, cin))
        plans = [Conv2DPlan(max(1, min(4, oh)), max(1, min(8, ow)), bk,
                            _align_kc(8, bk), max(1, min(cout, 64)))]
    return plans


@functools.lru_cache(maxsize=1024)
def _model_pick_conv2d(oh: int, ow: int, kh: int, kw: int, cin: int,
                       cout: int, *, stride: tuple,
                       itemsize: int) -> "Conv2DPlan":
    sh, sv = stride
    plans = candidate_conv2d_plans(oh, ow, kh, kw, cin, cout, stride=stride,
                                   itemsize=itemsize)
    return min(plans, key=lambda p: cm.conv2d_grid_cost(
        oh, ow, kh, kw, cin, cout, *p.astuple(), sh, sv,
        itemsize=itemsize).weighted)


@functools.lru_cache(maxsize=1024)
def _model_pick(m: int, n: int, k: int, *, itemsize: int,
                kind: str) -> TilePlan:
    plans = candidate_plans(m, n, k, kind=kind, itemsize=itemsize)
    costs = {p: _plan_cost(kind, m, n, k, p, itemsize).weighted
             for p in plans}
    return min(plans, key=lambda p: costs[p])


# --------------------------------------------------------------------------
# Public planning entry points
# --------------------------------------------------------------------------

def plan_matmul(m: int, n: int, k: int, dtype=jnp.float32, *,
                bm: Optional[int] = None, bn: Optional[int] = None,
                bk: Optional[int] = None, kc: Optional[int] = None,
                kind: str = "sq_matmul") -> TilePlan:
    """Pick the (bm, bn, bk, kc) plan for a matmul-shaped call of the
    ``kind`` kernel, under the schedule :func:`pm_schedule` names for it.

    ``dtype`` is the *accumulator* dtype (callers widen via
    ``sq.accum_dtype`` first); it sizes the VMEM terms.  A batched call
    plans its per-element (m, n, k): the batch axis multiplies every
    candidate's grid count uniformly, so the ranking is batch-invariant.

    Precedence: explicit user tiles > the cost model.  Explicit values
    are clamped to the (padded) operand extent and aligned to the
    hardware granules, which may round them up (see module docstring).
    A row-walk ``kc`` is further held to one lane group (it is unrolled).

    Fully-specified plans skip the model (alignment still applies, e.g.
    bm=100 rounds up to the next sublane multiple)::

        >>> from repro.kernels import tuning
        >>> tuning.plan_matmul(256, 256, 512, bm=64, bn=128, bk=128, kc=32,
        ...                    kind="cpm3_matmul")
        TilePlan(bm=64, bn=128, bk=128, kc=32)
        >>> tuning.plan_matmul(256, 256, 512, bm=100, bn=128, bk=128).bm
        104
    """
    schedule = pm_schedule(kind)
    if bm is not None and bn is not None and bk is not None:
        # Fully specified: no enumeration.  Kept cheap on purpose --
        # benchmark loops plan on every call.
        pbk = _align_lane(bk, k)
        return TilePlan(_align_bm(bm, m), _align_lane(bn, n), pbk,
                        _plan_kc(kc if kc is not None else pbk, pbk,
                                 schedule))
    base = _model_pick(m, n, k, itemsize=jnp.dtype(dtype).itemsize,
                       kind=kind)
    pbm = _align_bm(bm if bm is not None else base.bm, m)
    pbn = _align_lane(bn if bn is not None else base.bn, n)
    pbk = _align_lane(bk if bk is not None else base.bk, k)
    pkc = _plan_kc(kc if kc is not None else base.kc, pbk, schedule)
    return TilePlan(pbm, pbn, pbk, pkc)


def _plan_kc(kc: int, bk: int, schedule: str) -> int:
    """``kc`` aligned to divide ``bk``; a row-walk group at most one lane
    group wide, since the walk unrolls it."""
    if schedule == ROW_WALK:
        kc = min(kc, KMN_GROUP)
    return _align_kc(kc, bk)


def _lane_divisor(b: int, extent: int) -> int:
    """The largest whole-lane tile <= ``b`` that divides ``extent``, else
    the whole extent (a block that spans its dim is always legal)."""
    if extent % b == 0:
        return b
    for c in range(b - b % LANE, 0, -LANE):
        if extent % c == 0:
            return c
    return extent


def plan_grouped_matmul(bm: int, n: int, k: int) -> TilePlan:
    """The plan of a grouped-expert call (``sq_grouped_matmul``) with
    ``bm``-row tiles against (E, k, n) float weights: the matmul
    planner's column and K tiles at ``m = bm``, moved to divisors of N
    and K so the weights are never padded."""
    base = plan_matmul(bm, n, k, jnp.float32, kind="sq_grouped_matmul")
    bn, bk = _lane_divisor(base.bn, n), _lane_divisor(base.bk, k)
    kc = bk if base.kc == base.bk else _align_kc(base.kc, bk)
    return TilePlan(bm, bn, bk, kc)


def plan_conv(k_out: int, n_taps: int, dtype=jnp.float32, *,
              bo: Optional[int] = None, tb: Optional[int] = None,
              interpret: bool = False) -> tuple[int, int]:
    """Pick (bo, tb) for the 1D conv kernel: ``bo`` outputs per grid step,
    ``tb`` taps folded per vectorized chunk (the tap-block width).

    The tap-block width is backend-driven: on TPU a tb-wide (tb, bo) PM
    block keeps the VPU lanes busy, but under interpret/CPU execution the
    rank-1 tap walk is measurably faster (the stacked shifted windows
    materialize to no benefit), so interpret plans default to tb=1.
    """
    del dtype
    pbo = bo if bo is not None else 256
    pbo = max(1, min(pbo, _round_up(k_out, LANE) if k_out >= LANE else k_out))
    ptb = tb if tb is not None else (1 if interpret else 8)
    ptb = max(1, min(ptb, n_taps))
    return pbo, ptb


def plan_conv2d(h: int, w: int, kh: int, kw: int, cin: int, cout: int,
                dtype=jnp.float32, *, stride=(1, 1),
                bh: Optional[int] = None, bw: Optional[int] = None,
                bk: Optional[int] = None, kc: Optional[int] = None,
                bf: Optional[int] = None) -> Conv2DPlan:
    """Pick the (bh, bw, bk, kc, bf) plan for a fused 2D conv.

    ``h`` / ``w`` are the *padded* input spatial extents the kernel will
    see (user padding already applied); the output extents follow from
    ``kh``/``kw`` and ``stride``.  ``dtype`` is the resolved *accumulator*
    dtype (callers widen via ``sq.accum_dtype`` first, exactly like
    :func:`plan_matmul` -- it sizes the VMEM terms, and is not re-widened
    here).  Like :func:`plan_matmul`: explicit user tiles > the cost model
    (:func:`repro.core.cost_model.conv2d_grid_cost` -- PM lane-ops plus
    window-load traffic, so plans maximizing per-step window reuse win).

    Fully-specified plans skip the model (``kc`` is still clamped to
    divide the per-tap ``bk`` reduction axis)::

        >>> from repro.kernels import tuning
        >>> tuning.plan_conv2d(34, 34, 3, 3, 64, 64, bh=16, bw=32, bk=64,
        ...                    kc=32, bf=64)
        Conv2DPlan(bh=16, bw=32, bk=64, kc=32, bf=64)
    """
    sh, sv = stride
    oh = (h - kh) // sh + 1
    ow = (w - kw) // sv + 1
    if oh <= 0 or ow <= 0:
        raise ValueError(f"kernel {kh}x{kw} larger than padded input "
                         f"{h}x{w}")
    if all(v is not None for v in (bh, bw, bk, bf)):
        pbk = max(1, min(bk, cin))
        return Conv2DPlan(max(1, min(bh, oh)), max(1, min(bw, ow)), pbk,
                          _align_kc(kc if kc is not None else pbk, pbk),
                          max(1, min(bf, cout)))
    base = _model_pick_conv2d(oh, ow, kh, kw, cin, cout, stride=(sh, sv),
                              itemsize=jnp.dtype(dtype).itemsize)
    pbh = max(1, min(bh if bh is not None else base.bh, oh))
    # a tile may overhang ow up to the sublane granule (the wrapper pads
    # the output grid); clamping it to ow would break that granule
    pbw = max(1, min(bw if bw is not None else base.bw,
                     _round_up(ow, SUBLANE)))
    pbk = max(1, min(bk if bk is not None else base.bk, cin))
    pbf = max(1, min(bf if bf is not None else base.bf, cout))
    pkc = _align_kc(kc if kc is not None else base.kc, pbk)
    return Conv2DPlan(pbh, pbw, pbk, pkc, pbf)


def plan_paged_attn(hd: int, tile: int, *, kc_qk: Optional[int] = None,
                    kc_pv: Optional[int] = None) -> PagedAttnPlan:
    """Pick the (kc_qk, kc_pv) plan for a fused paged-attention call
    over a ``hd``-wide head (the latent kernel's K width) and ``tile``
    tokens per grid step (:func:`repro.kernels.sq_paged_attn.tile_blocks`
    table entries of ``block_size``).

    Explicit knobs > the model pick, which takes each full axis: the
    rank-2 PM broadcast is widest-is-best on the VPU, and a chunked walk
    would need lane-aligned chunks.  Each kc is clamped to divide its
    axis::

        >>> from repro.kernels import tuning
        >>> tuning.plan_paged_attn(64, 128, kc_qk=32, kc_pv=16)
        PagedAttnPlan(kc_qk=32, kc_pv=16)
    """
    return PagedAttnPlan(_align_kc(hd if kc_qk is None else kc_qk, hd),
                         _align_kc(tile if kc_pv is None else kc_pv, tile))
