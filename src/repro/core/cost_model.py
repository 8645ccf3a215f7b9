"""Analytical hardware cost model for the paper's architectures.

The paper's headline claim is a *gate-count* saving: "an n-bit squaring
circuit requires about half the gate count of an nxn multiplier" (paper ref
[1], Chen et al., "Exact and Approximate Squarers for Error-Tolerant
Applications").  This module provides an area/power proxy model (in
full-adder-equivalent units, the standard array-arithmetic accounting) for:

- multiplier-based vs square-based MACs (paper Fig.1a vs Fig.1b)
- MAC vs PM systolic arrays (paper §3.2, Fig.2/3)
- MAC vs PM tensor cores (paper §3.3, Fig.4/5)
- complex multipliers (3-mult Karatsuba form, paper Fig.9b) vs CPM4 / CPM3
  blocks (paper Fig.9a / Fig.12a)

Model conventions (documented, conservative):
- array multiplier  area(n x n)  = n^2            FA-equivalents
- squarer           area(n)      = n^2 / 2        (paper ref [1]: ~half)
- ripple/CLA adder  area(n)      = n
- register          area(n)      = n              (flop ~ FA proxy)
- PM operand adder works on (n+1) bits; the squarer sees n+1 bits;
  accumulators are sized 2n + log2(K) for a K-deep reduction.
"""
from __future__ import annotations

import dataclasses
import math

__all__ = ["ArithCost", "mac_cost", "pm_mac_cost", "complex_mac_cost",
           "cpm4_cost", "cpm3_cost", "systolic_array_cost",
           "tensor_core_cost", "savings_table",
           "TileCost", "vmem_tile_elems", "pm_tile_vmem_bytes",
           "pm_tile_vpu_ops",
           "pm_grid_cost", "conv2d_window_elems", "conv2d_patch_bytes",
           "conv2d_grid_cost", "paged_attn_gather_bytes"]


@dataclasses.dataclass(frozen=True)
class ArithCost:
    name: str
    area: float          # FA-equivalents
    squarers: int = 0
    multipliers: int = 0
    adders: int = 0

    def ratio_to(self, other: "ArithCost") -> float:
        return self.area / other.area


def _mult_area(n: int) -> float:
    return float(n * n)


def _sq_area(n: int) -> float:
    return float(n * n) / 2.0


def _add_area(n: int) -> float:
    return float(n)


def _acc_bits(n: int, depth: int) -> int:
    return 2 * n + max(1, math.ceil(math.log2(max(2, depth))))


def mac_cost(n: int, depth: int = 1024) -> ArithCost:
    """Multiplier MAC (paper Fig.1a): n x n multiplier + accumulator adder."""
    acc = _acc_bits(n, depth)
    area = _mult_area(n) + _add_area(acc) + acc
    return ArithCost("mac", area, multipliers=1, adders=1)


def pm_mac_cost(n: int, depth: int = 1024) -> ArithCost:
    """Partial-multiplication MAC (paper Fig.1b): operand adder + squarer +
    accumulator.  The squarer sees n+1 bits (sum growth)."""
    acc = _acc_bits(n + 1, depth)
    area = _add_area(n + 1) + _sq_area(n + 1) + _add_area(acc) + acc
    return ArithCost("pm_mac", area, squarers=1, adders=2)


def complex_mac_cost(n: int, depth: int = 1024) -> ArithCost:
    """Complex MAC via 3 real multipliers (paper Fig.9b, Karatsuba form)."""
    acc = _acc_bits(n + 1, depth)
    area = 3 * _mult_area(n + 1) + 5 * _add_area(n + 1) + 2 * (_add_area(acc) + acc)
    return ArithCost("complex_mac3", area, multipliers=3, adders=7)


def cpm4_cost(n: int, depth: int = 1024) -> ArithCost:
    """CPM with 4 squarers (paper Fig.9a): 4 operand adders + 4 squarers +
    2 combine adders + 2 accumulators."""
    acc = _acc_bits(n + 1, depth)
    area = 4 * (_add_area(n + 1) + _sq_area(n + 1)) + 2 * _add_area(2 * (n + 1)) \
        + 2 * (_add_area(acc) + acc)
    return ArithCost("cpm4", area, squarers=4, adders=8)


def cpm3_cost(n: int, depth: int = 1024) -> ArithCost:
    """CPM3 (paper Fig.12a): 3 squarers on (n+2)-bit three-operand sums,
    shared square reused by both output planes."""
    acc = _acc_bits(n + 2, depth)
    area = 3 * (_sq_area(n + 2)) + 5 * _add_area(n + 2) + 2 * _add_area(2 * (n + 2)) \
        + 2 * (_add_area(acc) + acc)
    return ArithCost("cpm3", area, squarers=3, adders=9)


def systolic_array_cost(rows: int, cols: int, n: int, square: bool,
                        depth: int = 1024) -> ArithCost:
    """Weight-stationary systolic array (paper Fig.2/3).

    Each PE holds REGA + mux + compute; the square version adds the Sa/Sb
    injection path (one adder) at the array periphery per column.
    """
    pe = pm_mac_cost(n, depth) if square else mac_cost(n, depth)
    periph = cols * _add_area(_acc_bits(n + 1, depth)) if square else 0.0
    area = rows * cols * (pe.area + n) + periph          # + REGA register
    return ArithCost("sq_systolic" if square else "mac_systolic", area,
                     squarers=pe.squarers * rows * cols,
                     multipliers=pe.multipliers * rows * cols)


def tensor_core_cost(m: int, n_dim: int, k: int, n: int, square: bool,
                     depth: int = 1024) -> ArithCost:
    """Tensor core (paper Fig.4/5): M*P PEs each with a K-wide dot-product
    reduction tree; square version initializes accumulators with Sa+Sb."""
    acc = _acc_bits(n + 1, depth)
    if square:
        unit = _add_area(n + 1) + _sq_area(n + 1)        # PM unit
    else:
        unit = _mult_area(n)
    tree = (k - 1) * _add_area(acc)
    pe = k * unit + tree + _add_area(acc) + acc
    area = m * n_dim * pe
    return ArithCost("sq_tensor_core" if square else "mac_tensor_core", area,
                     squarers=(k * m * n_dim if square else 0),
                     multipliers=(0 if square else k * m * n_dim))


# --------------------------------------------------------------------------
# Kernel-tile cost terms (TPU mapping of the PM datapaths).
#
# The gate-level model above prices the paper's silicon; the terms below
# price our Pallas *emulation* of it: a (bm, bn) output tile walked along K
# in bk-wide grid steps, each step processing the slab in kc-wide chunks of
# rank-2 broadcast squaring.  kernels/tuning.py consumes these to rank
# candidate (bm, bn, bk, kc) plans -- the same area-vs-throughput accounting
# style as the FA-equivalent model, but in VMEM bytes and VPU lane-ops.
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TileCost:
    """Cost of one (bm, bn, bk, kc) kernel plan over a full (m, n, k) call."""
    vmem_bytes: int      # peak VMEM residency of one grid step
    vpu_ops: float       # total VPU lane-ops across the whole grid
    grid_steps: int      # total grid invocations (pipeline overhead proxy)
    chunk_steps: int     # total inner-loop chunk iterations (issue overhead)

    @property
    def weighted(self) -> float:
        """Scalar ranking: lane-ops plus fixed per-step issue overheads.

        The constants are deliberately coarse -- they only need to order
        plans, not predict wall time.  Each grid step costs ~one tile of
        pipeline work; each chunk iteration costs a loop-issue bubble.
        """
        return self.vpu_ops + 4096.0 * self.grid_steps + 256.0 * self.chunk_steps


def vmem_tile_elems(*shape: int) -> int:
    """Elements a VMEM buffer of ``shape`` occupies: 32-bit data lives in
    (8, 128) tiles, so the two minor axes round up to 8 sublanes and 128
    lanes (a (bm, 1) correction column costs a full lane row per row)."""
    *lead, rows, cols = (1,) * max(0, 2 - len(shape)) + tuple(shape)
    n = -(-rows // 8) * 8 * (-(-cols // 128)) * 128
    for d in lead:
        n *= d
    return n


def pm_tile_vmem_bytes(bm: int, bn: int, bk: int, kc: int, itemsize: int = 4,
                       n_row_ops: int = 1, n_col_ops: int = 1,
                       n_acc: int = 1) -> int:
    """Peak VMEM bytes of one grid step of the chunked PM kernel.

    Counts the streamed operand slabs (``n_row_ops`` of (bm, bk) and
    ``n_col_ops`` of (bk, bn)), the scratch accumulator planes
    (``n_acc`` of (bm, bn)), the live rank-3 PM intermediate
    (bm, kc, bn) -- one per accumulator, since the CPM bodies keep a
    block per output plane -- and the (bm, 1)/(1, bn) correction
    vectors, every buffer at its (8, 128)-tiled size.  Double-buffering
    of the streamed slabs is included (x2).
    """
    slabs = 2 * (n_row_ops * vmem_tile_elems(bm, bk)
                 + n_col_ops * vmem_tile_elems(bk, bn))
    accs = n_acc * vmem_tile_elems(bm, bn) * 2      # scratch + out block
    interm = n_acc * vmem_tile_elems(bm, kc, bn)
    corr = 2 * (n_acc * vmem_tile_elems(bm, 1) + vmem_tile_elems(1, bn))
    return (slabs + accs + interm + corr) * itemsize


def pm_tile_vpu_ops(m: int, n: int, k: int, kc: int,
                    ops_per_pm: int = 3) -> float:
    """Total VPU lane-ops for the PM contraction of an (m, n, k) call.

    Every (i, j, kk) PM term costs ``ops_per_pm`` lane-ops (operand add,
    square, accumulate -- the Fig.1b PE datapath); the kc-chunked reduction
    adds one extra (bm, bn)-plane add per chunk to fold the partial sums,
    i.e. ``1/kc`` extra ops per PM term.
    """
    return float(m) * n * k * (ops_per_pm + 1.0 / max(1, kc))


def pm_grid_cost(m: int, n: int, k: int, bm: int, bn: int, bk: int, kc: int,
                 itemsize: int = 4, n_row_ops: int = 1, n_col_ops: int = 1,
                 n_acc: int = 1, ops_per_pm: int = 3) -> TileCost:
    """Full-call cost of a (bm, bn, bk, kc) plan (padded-shape accounting)."""
    gm = -(-m // bm)
    gn = -(-n // bn)
    gk = -(-k // bk)
    grid = gm * gn * gk
    chunks = grid * (-(-bk // kc))
    pm = pm_tile_vpu_ops(gm * bm, gn * bn, gk * bk, kc, ops_per_pm)
    vmem = pm_tile_vmem_bytes(bm, bn, bk, kc, itemsize, n_row_ops,
                              n_col_ops, n_acc)
    return TileCost(vmem_bytes=vmem, vpu_ops=pm, grid_steps=grid,
                    chunk_steps=chunks)


def conv2d_window_elems(bh: int, bw: int, kh: int, kw: int, bk: int,
                        sh: int = 1, sv: int = 1) -> int:
    """Input elements one fused-conv2d grid step loads: the shared window
    covering every shifted view of a (bh, bw) output tile, ``bk`` channels
    deep.  The im2col alternative would touch ``bh*bw*kh*kw*bk`` -- the
    ratio of the two is the window-reuse factor the fused kernel banks."""
    return ((bh - 1) * sh + kh) * ((bw - 1) * sv + kw) * bk


def conv2d_patch_bytes(oh: int, ow: int, kh: int, kw: int, cin: int,
                       batch: int = 1, itemsize: int = 4) -> int:
    """Bytes of the materialized im2col patch matrix
    ``(B*oh*ow, cin*kh*kw)`` -- the O(oh*ow*kh*kw) HBM blowup the fused
    kernel exists to avoid (paper §5.1).  The route planner keys the
    fused-vs-im2col choice on whether this stays cache-resident."""
    return batch * oh * ow * cin * kh * kw * itemsize


def paged_attn_gather_bytes(t: int, kv_heads: int, hd: int, *,
                            batch: int = 1, itemsize: int = 4) -> int:
    """Bytes the dense paged read moves to materialize the gathered
    ``(B, T, KV, hd)`` K and V windows (read from the pool + write of the
    gathered copy, both tensors) -- the traffic the fused block-streaming
    kernel avoids.  Scales with the pool-length ceiling ``t``, not live
    context, which is why the gather loses at long ``t``."""
    return 2 * 2 * batch * t * kv_heads * hd * itemsize


def conv2d_grid_cost(oh: int, ow: int, kh: int, kw: int, cin: int, cout: int,
                     bh: int, bw: int, bk: int, kc: int, bf: int,
                     sh: int = 1, sv: int = 1, itemsize: int = 4,
                     ops_per_pm: int = 3) -> TileCost:
    """Full-call cost of a (bh, bw, bk, kc, bf) fused-conv2d plan.

    Same accounting style as :func:`pm_grid_cost` (padded-shape VPU
    lane-ops + per-step issue overheads under a VMEM ceiling), with the
    conv-specific terms added:

    - a grid step contracts its (bh*bw, kh*kw*bk) shifted-view slab
      against a (kh*kw*bk, bf) tap block in ``kc``-wide chunks, so the
      padded PM volume is ``M * (kh*kw*K) * N``;
    - the data-side ``-x^2`` correction is folded at rank 2 once per
      filter *block* (it is shared by the bf filters of a step), costing
      ``2 * M * kh*kw*K`` lane-ops per cout walk;
    - window loads are charged per step: overlapping windows mean a step
      loads ``conv2d_window_elems`` rather than ``bh*bw*kh*kw*bk``
      elements, so plans maximizing per-step reuse (larger tiles, all
      filters in one block) genuinely score cheaper;
    - VMEM holds the kernel's actual input block -- the FULL padded
      spatial plane, ``bk`` channels deep (windows of adjacent tiles
      overlap, so the kernel stages the plane, not a per-tile window) --
      plus the tile-local slab (one tap's in-SRAM im2col), tap block,
      accumulator and live PM chunk, each at its (8, 128)-tiled size.
    """
    gm = -(-oh // bh) * (-(-ow // bw))
    gf = -(-cout // bf)
    gc = -(-cin // bk)
    grid = gm * gf * gc
    ktot = kh * kw * bk                      # flattened per-step K axis
    chunks = grid * kh * kw * (-(-bk // kc))
    m_pad = -(-oh // bh) * bh * (-(-ow // bw)) * bw
    k_pad = gc * ktot
    n_pad = gf * bf
    pm = float(m_pad) * k_pad * n_pad * (ops_per_pm + 1.0 / max(1, kc))
    corr = 2.0 * m_pad * k_pad * gf
    window = conv2d_window_elems(bh, bw, kh, kw, bk, sh, sv)
    loads = float(grid) * window
    # the kernel's in_spec block: the whole padded plane, channel-sliced.
    # Sized from the TILE-padded output extents (ohp = ceil(oh/bh)*bh):
    # the wrapper pads the input until every padded tile's window load is
    # in range, so that is what actually sits in VMEM.
    ohp = -(-oh // bh) * bh
    owp = -(-ow // bw) * bw
    hp = (ohp - 1) * sh + kh
    wp = (owp - 1) * sv + kw
    vmem = (2 * hp * vmem_tile_elems(wp, bk)     # double-buffered plane
            + 2 * kh * kw * vmem_tile_elems(bk, bf)        # tap block
            + 3 * vmem_tile_elems(bh * bw, bf)   # scratch + out tile
            + vmem_tile_elems(bh * bw, bk)       # one tap's view slab
            + vmem_tile_elems(bh * bw, kc, bf)   # live rank-3 PM chunk
            + vmem_tile_elems(1, bf)) * itemsize
    return TileCost(vmem_bytes=vmem, vpu_ops=pm + corr + loads,
                    grid_steps=grid, chunk_steps=chunks)


def savings_table(bitwidths=(8, 16, 32), depth: int = 1024):
    """Area ratios (square-based / multiplier-based) per paper architecture."""
    rows = []
    for n in bitwidths:
        rows.append({
            "bits": n,
            "pm_mac/mac": pm_mac_cost(n, depth).ratio_to(mac_cost(n, depth)),
            "cpm4/cmac3": cpm4_cost(n, depth).ratio_to(complex_mac_cost(n, depth)),
            "cpm3/cmac3": cpm3_cost(n, depth).ratio_to(complex_mac_cost(n, depth)),
            "sq_systolic/mac_systolic(128x128)":
                systolic_array_cost(128, 128, n, True, depth).ratio_to(
                    systolic_array_cost(128, 128, n, False, depth)),
            "sq_tcore/mac_tcore(8x8x8)":
                tensor_core_cost(8, 8, 8, n, True, depth).ratio_to(
                    tensor_core_cost(8, 8, 8, n, False, depth)),
        })
    return rows
