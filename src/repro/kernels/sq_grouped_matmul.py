"""Pallas TPU kernel: grouped (per-expert) square matmul for dropless MoE.

A mixture-of-experts layer that holds ``E`` experts multiplies each row
of its routed activations by the weights of the expert the row was sent
to.  The rows arrive sorted by expert, each expert's group padded to the
row tile ``bm`` (:func:`group_rows` builds that layout), so every ``bm``
-row tile belongs to exactly one expert:

    out[r, :] = x[r, :] @ w[tile_expert[r // bm]]

Grid ``(n_tiles, N / bn, K / bk)``, K innermost.  Two scalar-prefetched
operands steer it: ``tile_expert`` (the expert of every tile) and
``n_used`` (the tiles that hold rows).  The row buffer is sized for the
static worst case (every pick on a held expert, every group ending one
row into a tile), so most tiles of a small step are past ``n_used``:
their steps skip the compute, and their index maps point the row and
weight DMAs at the last used tile's final blocks, which the pipeline
already holds -- no weight crosses HBM for a tile that holds no rows
(the dead-column trick of :mod:`repro.kernels.sq_paged_attn`).  Their
output rows are left as they are; no pick reads them.

Arithmetic is :mod:`repro.kernels.sq_matmul`'s: the accumulator starts at
the corrections ``Sa_i + Sb_j`` (``-sum_k x^2`` per row, ``-sum_k w^2``
per expert column, computed outside), each K step adds ``sum_k (x +
w)^2`` through :func:`repro.kernels.sq_matmul.pm_block_accum`, and the
last halves.  Weights stay in their stored dtype in HBM and are widened
to f32 in VMEM; rows and corrections are f32.  Float only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sq_matmul import pm_block_accum

__all__ = ["sq_grouped_matmul_kernel", "sq_grouped_matmul_pallas",
           "group_rows", "max_tiles"]


def max_tiles(n_picks: int, n_experts: int, bm: int) -> int:
    """The most row tiles ``n_picks`` picks over ``n_experts`` groups can
    fill (the static worst case): as many groups as there can be, each
    ending one row into a tile."""
    g = min(n_experts, n_picks)
    return g + (n_picks - g) // bm


def group_rows(expert, n_experts: int, bm: int):
    """Row layout of picks sorted by expert, each group padded to ``bm``.

    ``expert``: (P,) int32 expert of each pick, ``n_experts`` (or more)
    marking a pick that no group takes.  Returns ``(dest, tile_expert,
    n_used)``: each pick's row (picks no group takes get row 0, which the
    caller masks), the expert of each of the :func:`max_tiles` tiles (a
    tile past ``n_used`` repeats the last used tile's expert) and the
    count of tiles that hold rows."""
    P = expert.shape[0]
    n_tiles = max_tiles(P, n_experts, bm)
    held = expert < n_experts
    e = jnp.where(held, expert, n_experts)
    counts = jnp.bincount(e, length=n_experts + 1)[:n_experts]
    tiles = (counts + bm - 1) // bm                    # tiles per group
    tile_end = jnp.cumsum(tiles)
    row_start = (tile_end - tiles) * bm                # first row of each
    first = jnp.cumsum(counts) - counts                # first sorted slot
    order = jnp.argsort(e, stable=True)
    rank = jnp.zeros((P,), jnp.int32).at[order].set(
        jnp.arange(P, dtype=jnp.int32))
    ec = jnp.minimum(e, n_experts - 1)
    dest = jnp.where(held, row_start[ec] + rank - first[ec], 0)
    n_used = tile_end[-1].astype(jnp.int32)
    # the expert of tile t is the first whose tiles end past t; a tile
    # past the used ones takes the last used tile's expert
    t = jnp.minimum(jnp.arange(n_tiles, dtype=jnp.int32),
                    jnp.maximum(n_used - 1, 0))
    owner = jnp.sum(tile_end[None, :] <= t[:, None], axis=1)
    tile_expert = jnp.minimum(owner, n_experts - 1).astype(jnp.int32)
    return dest.astype(jnp.int32), tile_expert, n_used.reshape(1)


def sq_grouped_matmul_kernel(te_ref, used_ref, x_ref, w_ref, sa_ref, sb_ref,
                             out_ref, acc_ref, wf_ref, *, nk: int, kc: int,
                             pm_layout: str):
    """One (tile, column block, K block) step.

    x_ref: (bm, bk) f32 rows; w_ref: (1, bk, bn) the tile's expert's
    weights in their stored dtype; sa_ref: (bm, 1); sb_ref: (1, 1, bn);
    out_ref and the accumulator acc_ref: (bm, bn); wf_ref: (bk, bn) f32,
    the widened weight block.
    """
    del te_ref                        # consumed by the BlockSpec index maps
    i, kk = pl.program_id(0), pl.program_id(2)
    live = i < used_ref[0]

    @pl.when(live & (kk == 0))
    def _init():
        acc_ref[...] = sa_ref[...] + sb_ref[0]

    @pl.when(live)
    def _accumulate():
        wf_ref[...] = w_ref[0].astype(jnp.float32)
        acc_ref[...] = pm_block_accum(acc_ref[...], x_ref, wf_ref, kc=kc,
                                      pm_layout=pm_layout)

    @pl.when(live & (kk == nk - 1))
    def _finalize():
        out_ref[...] = acc_ref[...] * 0.5


def sq_grouped_matmul_pallas(x, w, sa, sb, tile_expert, n_used, *, bm: int,
                             bn: int, bk: int, kc: int | None = None,
                             pm_layout: str = "mkn",
                             interpret: bool = False):
    """x (R, K) f32 rows grouped by tile; w (E, K, N) in its stored dtype;
    sa (R, 1) and sb (E, 1, N) f32 corrections; tile_expert
    (R // bm,) int32; n_used (1,) int32.  ``bn`` must divide N and ``bk``
    divide K (no weight padding); ``kc`` divides ``bk``.  Returns (R, N)
    f32, whose rows in tiles past ``n_used`` are undefined."""
    R, K = x.shape
    E, K2, N = w.shape
    assert K == K2 and R % bm == 0 and N % bn == 0 and K % bk == 0, \
        (x.shape, w.shape, bm, bn, bk)
    assert sa.shape == (R, 1) and sb.shape == (E, 1, N)
    kc = bk if kc is None else kc
    assert bk % kc == 0, (bk, kc)
    nk, nn = K // bk, N // bn
    n_tiles = R // bm

    def last(used):
        return jnp.maximum(used[0] - 1, 0)

    def x_map(i, j, kk, te, used):
        dead = i >= used[0]
        return (jnp.where(dead, last(used), i), jnp.where(dead, nk - 1, kk))

    def w_map(i, j, kk, te, used):
        dead = i >= used[0]
        return (te[jnp.minimum(i, last(used))], jnp.where(dead, nk - 1, kk),
                jnp.where(dead, nn - 1, j))

    def sa_map(i, j, kk, te, used):
        return (jnp.where(i >= used[0], last(used), i), 0)

    def sb_map(i, j, kk, te, used):
        dead = i >= used[0]
        return (te[jnp.minimum(i, last(used))], 0, jnp.where(dead, nn - 1, j))

    kernel = functools.partial(sq_grouped_matmul_kernel, nk=nk, kc=kc,
                               pm_layout=pm_layout)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles, nn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), x_map),
            pl.BlockSpec((1, bk, bn), w_map),
            pl.BlockSpec((bm, 1), sa_map),
            pl.BlockSpec((1, 1, bn), sb_map),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk, te, used: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bk, bn), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "parallel", "arbitrary")),
        interpret=interpret,
    )(tile_expert, n_used, x, w, sa, sb)
