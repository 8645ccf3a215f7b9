"""Pallas TPU kernel: fused paged-attention through the square PM datapath.

The serving engine's gather-based read path materializes every sequence's
full logical window as a dense ``(B, T, KV, hd)`` view per layer per step
(``models.attention.paged_gather_indices`` + ``jnp.take``) before the
score/PV contractions even start -- memory traffic that scales with the
pool-length ceiling, not with live context.  This kernel is the paper's
square-systolic/tensor-core story (§3.2/§3.3) applied to the attention
inner loop: the block table is indexed *inside* the grid (scalar-prefetch
index maps, the same trick the ``sq_matmul`` fold route uses for batch),
K/V blocks stream from the shared pool in their stored layout and dtype,
and the gathered window never exists.

Grid and dataflow
-----------------
Grid ``(B, n_steps)`` -- sequence x tile of ``tpb`` block-table columns
(:func:`tile_blocks`: at least 128 tokens a step, at most the whole
table), the tile axis ``"arbitrary"`` (sequential).  One step serves every
KV head of its sequence: a stored pool block ``(bs, KV, hd)`` carries all
of them.  The pools enter as a free reshape of their stored ``(P, KV, hd)``
layout, ``(nblk, bs, KV, hd)``, in their stored dtype; each step widens
its ``(tpb * bs, KV, hd)`` tile to f32 in VMEM and splits it per head.
Each of the ``tpb`` table entries of a step is its own pool operand (K, V
and the ``(nblk, 1, bs)`` positions), whose index map reads one column of
the scalar-prefetched table.

Only live columns are walked.  Sequence ``i`` walks columns ``[lo_i,
hi_i)``: ``hi_i = ceil((max q_pos[i] + 1) / bs)`` over its valid rows,
``lo_i = 0``, or with a sliding window the first column the window can
reach (exact: eviction zeroes only leading columns whose positions have
all aged out, ``serve/paged.py``).  The wrapper rewrites each dead
column's table entry to the block its operand last (or next) holds
(:func:`_walk_tables`), so the pipeline sees an unchanged block index and
issues no DMA for it; steps outside the range skip their compute, and a
sequence with no valid query walks nothing (its output is 0, discarded as
padding).  Columns of a live step that lie outside ``[lo, hi)`` hold
another column's block: they are zeroed and masked, so it never reaches
the result.

Every block obeys Mosaic's rule that the two minor block dims are (8,
128)-divisible or span the whole array dim: whole ``(KV, hd)`` pool
blocks, whole ``(rows, hd)`` query panels, whole ``(1, bs)`` positions.

Per head, both contractions run through the shared square-PM machinery
(:func:`repro.kernels.sq_matmul.pm_block_accum`):

- **scores**: ``2 * (q @ k^T)`` accumulated as ``sum_h (q + k)^2`` with
  the rank-2 corrections ``-sum q^2`` / ``-sum k^2`` as the accumulator
  init (paper Fig.1b), then the paper's final halving;
- **PV**: ``2 * (p @ v)`` the same way over the tile's token axis, with
  ``p`` staged in VMEM scratch so its chunks are ref slices.

An online-softmax carry (running max ``m``, normalizer ``l``, and the
output accumulator -- flash-attention's recurrence) lives in VMEM scratch
across the tile walk, so masking, softcap, and renormalization all happen
on one ``(S*G, tile)`` score tile at a time.  Masking is by absolute
position from ``pos_pool`` (causal ``kv_pos <= q_pos``, the never-attend
sentinel bound, and the optional sliding-window distance) -- identical
semantics to the gather path for every row with a valid key.

Latent mode (``v_pool=None``, multi-head latent attention): one pool
holds each token's ``Dk``-wide row, read as the key of every query row
and, in its first ``Dv`` columns, as the value -- V is taken from the K
tile already in VMEM, so each latent block crosses HBM once.  The query
rows of all heads ride one latent "KV head" (``KV = 1``, ``G`` = heads),
q.k runs over ``Dk`` and p.v over ``Dv``, and the output's last dim is
``Dv``.  With a V pool, ``Dv`` is the head dim and nothing changes.

Float-only: the softmax path is inherently floating-point (the int8
square datapath stops at the logits).  Operands are taken in any float
dtype and computed in f32, matching the gather path's accumulation.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pm_blocks import PM_LAYOUTS
from repro.kernels.sq_matmul import pm_block_accum

__all__ = ["sq_paged_attn", "sq_paged_attn_kernel", "tile_blocks",
           "walk_bounds", "TILE_TOKENS"]

NEG_INF = -1e30
# Least tokens one grid step spans: a full 128-lane score tile.
TILE_TOKENS = 128


def tile_blocks(block_size: int, nb: int) -> int:
    """Table columns one grid step walks: enough for :data:`TILE_TOKENS`
    tokens, at most the whole ``nb``-column table."""
    return max(1, min(nb, TILE_TOKENS // block_size))


def walk_bounds(q_pos, block_size: int, window: Optional[int] = None):
    """Per-sequence live column range ``(lo, hi)`` of a (B, S) query-
    position array (-1 marks padding): ``hi = ceil((max valid q_pos + 1) /
    block_size)``; ``lo = 0``, or with ``window`` the first column holding
    a position the earliest valid query can reach.  A row with no valid
    query gets ``(0, 0)``.  Works on numpy and jax arrays alike."""
    valid = q_pos >= 0
    any_valid = valid.any(axis=1)
    q_hi = (q_pos * valid).max(axis=1)
    hi = (q_hi + block_size) // block_size * any_valid
    if window is None:
        return 0 * hi, hi
    q_lo = (q_pos * valid + (1 - valid) * q_hi[:, None]).min(axis=1)
    lo = (q_lo - window + 1).clip(0) // block_size * any_valid
    return lo, hi


def _walk_tables(tables, lo, hi, tpb: int):
    """Block tables with every dead column's entry replaced by the block
    its operand (column residue mod ``tpb``) holds at its nearest live
    column, so the pipeline never fetches a dead column.  A residue with
    no live column takes column ``hi - 1``; a sequence with nothing live
    holds its column 0 throughout (nothing is computed for it)."""
    B, nb = tables.shape
    c = jnp.arange(nb, dtype=jnp.int32)[None, :]
    lo, hi = lo[:, None], hi[:, None]
    first = lo + (c - lo) % tpb                      # first live c' == c
    last = first + (hi - 1 - first) // tpb * tpb     # last live c' == c
    tgt = jnp.where(first < hi, jnp.clip(c, first, last), hi - 1)
    tgt = jnp.where(hi > lo, tgt, 0)
    return jnp.take_along_axis(tables, tgt, axis=1)


def sq_paged_attn_kernel(tables_ref, q_ref, qpos_ref, *refs, tpb: int,
                         kv_heads: int, kc_qk: int, kc_pv: int,
                         pm_layout: str, window: Optional[int],
                         softcap: float, attend_limit: int,
                         latent_dv: Optional[int] = None):
    """One (sequence, tile) grid step.

    ``q_ref``: (1, KV, rows, hd) queries, rows ordered (query, group) and
    pre-scaled by ``hd**-0.5``; ``qpos_ref``: (1, rows, 1) their positions
    (-1 padding).  ``refs`` holds, per table column ``j`` of the tile,
    its positions (1, 1, bs), then its K blocks (1, bs, KV, hd), then its
    V blocks (none in latent mode, ``latent_dv`` set: V is the first
    ``latent_dv`` columns of K), all as the index maps resolved them; then
    the (B, 2) SMEM walk bounds, the (1, KV, rows, Dv) output, and the
    scratch: per-head keys transposed (KV, hd, T) and values (KV, T, Dv)
    in f32, the probability tile (rows, T), and the running
    max/normalizer (KV, rows, 1) and output accumulator (KV, rows, Dv)
    carried across the walk.
    """
    del tables_ref                    # consumed by the BlockSpec index maps
    n_v = 0 if latent_dv else tpb
    pos_refs, k_refs, v_refs = refs[:tpb], refs[tpb:2 * tpb], \
        refs[2 * tpb:2 * tpb + n_v]
    (bounds_ref, out_ref, kt_ref, v_ref, p_ref, m_ref, l_ref,
     acc_ref) = refs[2 * tpb + n_v:]
    i, step = pl.program_id(0), pl.program_id(1)
    lo, hi = bounds_ref[i, 0], bounds_ref[i, 1]
    f32 = jnp.float32

    @pl.when(step == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((step >= lo // tpb) & (step * tpb < hi))
    def _walk():
        live = [(step * tpb + j >= lo) & (step * tpb + j < hi)
                for j in range(tpb)]
        # widen the stored blocks in VMEM; columns of this tile outside
        # [lo, hi) hold another column's block: zero and mask them
        kf = jnp.concatenate(
            [jnp.where(live[j], k_refs[j][0].astype(f32), 0.0)
             for j in range(tpb)], axis=0)                  # (T, KV, hd)
        if latent_dv:
            vf = kf[:, :, :latent_dv]
        else:
            vf = jnp.concatenate(
                [jnp.where(live[j], v_refs[j][0].astype(f32), 0.0)
                 for j in range(tpb)], axis=0)
        for h in range(kv_heads):
            kt_ref[h] = kf[:, h, :].T                       # (hd, T)
            v_ref[h] = vf[:, h, :]                          # (T, hd)
        kp = jnp.concatenate(
            [jnp.where(live[j], pos_refs[j][0], attend_limit)
             for j in range(tpb)], axis=1)                  # (1, T)
        # absolute-position mask (causal + sentinel + optional window),
        # shared by every head
        qp = qpos_ref[0]                                    # (rows, 1)
        mask = (kp < attend_limit) & (kp <= qp)
        if window is not None:
            mask &= (qp - kp) < window

        def head(h, carry):
            # -- scores: 2 * (q @ k^T) via the PM identity, corrections
            # in-kernel: acc init = -sum q^2 - sum k^2 (the Fig.1b
            # register preload), each K step adds (q + k)^2, the end
            # applies the paper's right shift.
            q, kt, v = q_ref.at[0, h], kt_ref.at[h], v_ref.at[h]
            qv, ktv = q[...], kt[...]
            sq_row = -jnp.sum(qv * qv, axis=1, keepdims=True)   # (rows, 1)
            sk_col = -jnp.sum(ktv * ktv, axis=0, keepdims=True)  # (1, T)
            s = 0.5 * pm_block_accum(sq_row + sk_col, q, kt, kc=kc_qk,
                                     pm_layout=pm_layout)
            if softcap and softcap > 0.0:
                s = jnp.tanh(s / softcap) * softcap
            s = jnp.where(mask, s, NEG_INF)

            # -- online-softmax update (flash recurrence).
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)                          # (rows, T)
            if p_ref.shape[0] == 1:
                # Mosaic cannot sum a one-row tile across lanes in the
                # layout the exp leaves it in; read back from VMEM, it can
                p_ref[...] = p
                p = p_ref[...]
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=1, keepdims=True)
            m_ref[h] = m_new

            # -- PV: 2 * (p @ v) through the same PM machinery, over the
            # tile's token axis.
            p_ref[...] = p
            vv = v[...]
            sp_row = -jnp.sum(p * p, axis=1, keepdims=True)     # (rows, 1)
            sv_col = -jnp.sum(vv * vv, axis=0, keepdims=True)   # (1, hd)
            pv = 0.5 * pm_block_accum(sp_row + sv_col, p_ref, v, kc=kc_pv,
                                      pm_layout=pm_layout)
            acc_ref[h] = acc_ref[h] * corr + pv
            return carry

        jax.lax.fori_loop(0, kv_heads, head, 0)

    @pl.when(step == pl.num_programs(1) - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        out_ref[...] = out[None]


def sq_paged_attn(q, k_pool, v_pool, tables, pos_pool, q_pos, *,
                  block_size: int, window: Optional[int] = None,
                  softcap: float = 0.0, attend_limit: int = 2 ** 29,
                  kc_qk: Optional[int] = None, kc_pv: Optional[int] = None,
                  pm_layout: Optional[str] = None,
                  interpret: Optional[bool] = None,
                  dv: Optional[int] = None):
    """Fused paged attention: softmax(q @ K^T) @ V over block tables.

    ``q``: (B, S, KV, G, hd) queries, already scaled by ``hd**-0.5``
    (matching the gather path); ``k_pool``/``v_pool``: the shared
    (P, KV, hd) pools, read in their stored dtype; ``tables``: (B, nb)
    int32 block tables with absolute column addressing (column ``c``
    holds positions ``[c*bs, (c+1)*bs)``); ``pos_pool``: (P,) absolute
    positions (EMPTY sentinel on unwritten slots); ``q_pos``: (B, S) query
    positions with -1 marking padding.  Returns (B, S, KV, G, Dv)
    float32, ``Dv`` = hd.  Latent mode: ``v_pool=None`` and ``dv`` <= hd,
    V being the first ``dv`` columns of each K row (module docstring).  The new K/V must already be scattered into the pools (the
    engine scatters once per step).

    ``kc_qk`` chunks the head_dim reduction of the score PM block,
    ``kc_pv`` the tile-token reduction of the PV PM block (defaults:
    unchunked) -- the :func:`repro.kernels.tuning.plan_paged_attn` knobs.
    """
    B, S, KV, G, hd = q.shape
    P = k_pool.shape[0]
    if P % block_size:
        raise ValueError(f"pool of {P} slots is not a whole number of "
                         f"{block_size}-token blocks")
    num_blocks = P // block_size
    nb = tables.shape[1]
    if not jnp.issubdtype(q.dtype, jnp.floating):
        raise ValueError(f"sq_paged_attn is float-only (softmax path), "
                         f"got {q.dtype}")
    if interpret is None:
        from repro.kernels.ops import default_interpret
        interpret = default_interpret()
    if pm_layout is None:
        pm_layout = "mnk" if interpret else "mkn"
    if pm_layout not in PM_LAYOUTS:
        raise ValueError(f"unknown pm_layout {pm_layout!r}; expected one "
                         f"of {PM_LAYOUTS}")
    latent = v_pool is None
    if latent and not (dv and 0 < dv <= hd):
        raise ValueError(f"latent mode (no V pool) needs 0 < dv <= {hd}, "
                         f"got dv={dv}")
    Dv = dv if latent else hd
    tpb = tile_blocks(block_size, nb)
    T = tpb * block_size
    kc_qk = hd if kc_qk is None else kc_qk
    kc_pv = T if kc_pv is None else kc_pv
    if hd % kc_qk or T % kc_pv:
        raise ValueError(f"kc_qk {kc_qk} must divide head_dim {hd} and "
                         f"kc_pv {kc_pv} must divide the {T}-token tile")

    f32, i32 = jnp.float32, jnp.int32
    rows = S * G
    n_steps = pl.cdiv(nb, tpb)
    # head-major queries (module docstring): whole minor dims per block
    qf = q.astype(f32).transpose(0, 2, 1, 3, 4).reshape(B, KV, rows, hd)
    q_pos = q_pos.astype(i32)
    qpos = jnp.repeat(q_pos, G, axis=1)[:, :, None]
    lo, hi = walk_bounds(q_pos, block_size, window)
    hi = jnp.minimum(hi, nb)
    bounds = jnp.stack([lo, hi], axis=1).astype(i32)
    walk = _walk_tables(tables.astype(i32), lo, hi, tpb)
    # free reshapes of the stored layouts: no widening, no transpose
    kr = k_pool.reshape(num_blocks, block_size, KV, hd)
    vr = None if latent else v_pool.reshape(num_blocks, block_size, KV, hd)
    posr = pos_pool.astype(i32).reshape(num_blocks, 1, block_size)

    def col(j):
        # the walk table's entry for column j of tile s; a last tile that
        # overhangs the table repeats its final column
        return lambda i, s, t: t[i, jnp.minimum(s * tpb + j, nb - 1)]

    def pos_map(j):
        c = col(j)
        return lambda i, s, t: (c(i, s, t), 0, 0)

    def kv_map(j):
        c = col(j)
        return lambda i, s, t: (c(i, s, t), 0, 0, 0)

    kernel = functools.partial(
        sq_paged_attn_kernel, tpb=tpb, kv_heads=KV, kc_qk=kc_qk,
        kc_pv=kc_pv, pm_layout=pm_layout, window=window, softcap=softcap,
        attend_limit=attend_limit, latent_dv=Dv if latent else None)
    pos_specs = [pl.BlockSpec((1, 1, block_size), pos_map(j))
                 for j in range(tpb)]
    kv_specs = [pl.BlockSpec((1, block_size, KV, hd), kv_map(j))
                for j in range(tpb)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, n_steps),
        in_specs=[
            pl.BlockSpec((1, KV, rows, hd), lambda i, s, t: (i, 0, 0, 0)),
            pl.BlockSpec((1, rows, 1), lambda i, s, t: (i, 0, 0)),
            *pos_specs, *kv_specs, *([] if latent else kv_specs),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, KV, rows, Dv),
                               lambda i, s, t: (i, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, hd, T), f32),         # keys, per head, k^T
            pltpu.VMEM((KV, T, Dv), f32),         # values, per head
            pltpu.VMEM((rows, T), f32),           # probability tile
            pltpu.VMEM((KV, rows, 1), f32),       # running max
            pltpu.VMEM((KV, rows, 1), f32),       # running normalizer
            pltpu.VMEM((KV, rows, Dv), f32),      # output accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, rows, Dv), f32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(walk, qf, qpos, *([posr] * tpb), *([kr] * tpb),
      *([] if latent else [vr] * tpb), bounds)
    return out.reshape(B, KV, S, G, Dv).transpose(0, 2, 1, 3, 4)
