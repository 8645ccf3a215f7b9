"""Pallas TPU kernel: fused paged-attention through the square PM datapath.

The serving engine's gather-based read path materializes every sequence's
full logical window as a dense ``(B, T, KV, hd)`` view per layer per step
(``models.attention.paged_gather_indices`` + ``jnp.take``) before the
score/PV contractions even start -- memory traffic that scales with the
pool-length ceiling, not with live context.  This kernel is the paper's
square-systolic/tensor-core story (§3.2/§3.3) applied to the attention
inner loop: the block table is indexed *inside* the grid (scalar-prefetch
index maps, the same trick the ``sq_matmul`` fold route uses for batch),
K/V blocks stream from the shared pool one block-table entry at a time,
and the gathered window never exists.

Grid and dataflow
-----------------
Grid ``(B, KV, nb)`` -- sequence x kv-head x block-table column, with the
block axis ``"arbitrary"`` (sequential).  The block tables ride as a
scalar-prefetch operand, so the K/V/position BlockSpec index maps read
``tables[i, b]`` and Mosaic prefetches pool block ``tables[i, b]``
directly; a NULL table entry (0) fetches the reserved null block, whose
``pos_pool`` entries hold the EMPTY sentinel and mask to nothing.

Every block obeys Mosaic's rule that the two minor block dims are (8,
128)-divisible or span the whole array dim.  The wrapper therefore lays
the operands out head-major: queries as (B, KV, S*G, hd) with one
position per query row, the pool as (nblk, KV, hd, bs) keys (already
transposed for the score contraction) and (nblk, KV, bs, hd) values,
positions as (nblk, 1, bs) -- so each grid step sees whole (S*G, hd),
(hd, bs) and (bs, hd) tiles.  ``hd = 120`` is legal as a whole dim.

Per grid step, both contractions run through the shared square-PM
machinery (:func:`repro.kernels.sq_matmul.pm_block_accum`):

- **scores**: ``2 * (q @ k^T)`` accumulated as ``sum_h (q + k)^2`` with
  the rank-2 corrections ``-sum q^2`` / ``-sum k^2`` as the accumulator
  init (paper Fig.1b), then the paper's final halving;
- **PV**: ``2 * (p @ v)`` the same way over the block's token axis, with
  ``p`` staged in VMEM scratch so its chunks are ref slices.

An online-softmax carry (running max ``m``, normalizer ``l``, and the
output accumulator -- flash-attention's recurrence) lives in VMEM scratch
across the block walk, so masking, softcap, and renormalization all
happen on one ``(S*G, block_size)`` score tile at a time.  Masking is by
absolute position from ``pos_pool`` (causal ``kv_pos <= q_pos``, the
never-attend sentinel bound, and the optional sliding-window distance) --
identical semantics to the gather path, including the all-masked-row
convention (uniform weights; such rows are padding and are discarded).

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

__all__ = ["sq_paged_attn", "sq_paged_attn_kernel"]

NEG_INF = -1e30


def sq_paged_attn_kernel(tables_ref, q_ref, qpos_ref, kt_ref, v_ref,
                         kpos_ref, out_ref, m_ref, l_ref, acc_ref, p_ref, *,
                         nb: int, kc_qk: int, kc_pv: int, pm_layout: str,
                         window: Optional[int], softcap: float,
                         attend_limit: int):
    """One (sequence, kv-head, block) grid step.

    ``q_ref``: (1, 1, rows, hd) queries of this head's group, rows ordered
    (query, group) and pre-scaled by ``hd**-0.5``; ``qpos_ref``: (1, rows,
    1) their positions (-1 padding); ``kt_ref``: (1, 1, hd, bs) and
    ``v_ref``: (1, 1, bs, hd) the pool block the scalar-prefetch index
    map resolved for this table column; ``kpos_ref``: (1, 1, bs) its
    absolute positions.  Scratch: running max/normalizer (rows, 1),
    output accumulator (rows, hd) and the probability tile (rows, bs),
    carried across the sequential block axis.
    """
    del tables_ref                    # consumed by the BlockSpec index maps
    b = pl.program_id(2)

    @pl.when(b == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q, kt, v = q_ref.at[0, 0], kt_ref.at[0, 0], v_ref.at[0, 0]

    # -- scores: 2 * (q @ k^T) via the PM identity, corrections in-kernel.
    # acc init = -sum q^2 - sum k^2 (the Fig.1b register preload), each
    # K step adds (q + k)^2, the end applies the paper's right shift.
    qv, ktv = q[...], kt[...]
    sq_row = -jnp.sum(qv * qv, axis=1, keepdims=True)    # (rows, 1)
    sk_col = -jnp.sum(ktv * ktv, axis=0, keepdims=True)  # (1, bs)
    s = 0.5 * pm_block_accum(sq_row + sk_col, q, kt, kc=kc_qk,
                             pm_layout=pm_layout)
    if softcap and softcap > 0.0:
        s = jnp.tanh(s / softcap) * softcap

    # -- absolute-position mask from the pos_pool block (causal + sentinel
    # + optional sliding window).
    qp = qpos_ref[0]                                     # (rows, 1)
    kp = kpos_ref[0]                                     # (1, bs)
    mask = (kp < attend_limit) & (kp <= qp)
    if window is not None:
        mask &= (qp - kp) < window
    s = jnp.where(mask, s, NEG_INF)

    # -- online-softmax update (flash recurrence).
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)                               # (rows, bs)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = m_new

    # -- PV: 2 * (p @ v) through the same PM machinery, over the block's
    # token axis.
    p_ref[...] = p
    vv = v[...]
    sp_row = -jnp.sum(p * p, axis=1, keepdims=True)      # (rows, 1)
    sv_col = -jnp.sum(vv * vv, axis=0, keepdims=True)    # (1, hd)
    pv = 0.5 * pm_block_accum(sp_row + sv_col, p_ref, v, kc=kc_pv,
                              pm_layout=pm_layout)
    acc_ref[...] = acc_ref[...] * corr + pv

    @pl.when(b == nb - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        out_ref[...] = out[None, None]


def sq_paged_attn(q, k_pool, v_pool, tables, pos_pool, q_pos, *,
                  block_size: int, window: Optional[int] = None,
                  softcap: float = 0.0, attend_limit: int = 2 ** 29,
                  kc_qk: Optional[int] = None, kc_pv: Optional[int] = None,
                  pm_layout: Optional[str] = None,
                  interpret: Optional[bool] = None):
    """Fused paged attention: softmax(q @ K^T) @ V over block tables.

    ``q``: (B, S, KV, G, hd) queries, already scaled by ``hd**-0.5``
    (matching the gather path); ``k_pool``/``v_pool``: the shared
    (P, KV, hd) pools; ``tables``: (B, nb) int32 block tables;
    ``pos_pool``: (P,) absolute positions (EMPTY sentinel on unwritten
    slots); ``q_pos``: (B, S) query positions with -1 marking padding.
    Returns (B, S, KV, G, hd) float32.  The new K/V must already be
    scattered into the pools (the engine scatters once per step).

    ``kc_qk`` chunks the head_dim reduction of the score PM block,
    ``kc_pv`` the block-token reduction of the PV PM block (defaults:
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
    kc_qk = hd if kc_qk is None else kc_qk
    kc_pv = block_size if kc_pv is None else kc_pv
    if hd % kc_qk or block_size % kc_pv:
        raise ValueError(f"kc_qk {kc_qk} must divide head_dim {hd} and "
                         f"kc_pv {kc_pv} must divide block_size "
                         f"{block_size}")

    f32 = jnp.float32
    rows = S * G
    # head-major layouts (module docstring): whole minor dims per block
    qf = q.astype(f32).transpose(0, 2, 1, 3, 4).reshape(B, KV, rows, hd)
    qpos = jnp.repeat(q_pos.astype(jnp.int32), G, axis=1)[:, :, None]
    kt = k_pool.astype(f32).reshape(num_blocks, block_size, KV, hd)
    kt = kt.transpose(0, 2, 3, 1)                        # (nblk, KV, hd, bs)
    vr = v_pool.astype(f32).reshape(num_blocks, block_size, KV, hd)
    vr = vr.transpose(0, 2, 1, 3)                        # (nblk, KV, bs, hd)
    posr = pos_pool.astype(jnp.int32).reshape(num_blocks, 1, block_size)

    kernel = functools.partial(
        sq_paged_attn_kernel, nb=nb, kc_qk=kc_qk, kc_pv=kc_pv,
        pm_layout=pm_layout, window=window, softcap=softcap,
        attend_limit=attend_limit)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KV, nb),
        in_specs=[
            pl.BlockSpec((1, 1, rows, hd), lambda i, kv, b, t: (i, kv, 0, 0)),
            pl.BlockSpec((1, rows, 1), lambda i, kv, b, t: (i, 0, 0)),
            pl.BlockSpec((1, 1, hd, block_size),
                         lambda i, kv, b, t: (t[i, b], kv, 0, 0)),
            pl.BlockSpec((1, 1, block_size, hd),
                         lambda i, kv, b, t: (t[i, b], kv, 0, 0)),
            pl.BlockSpec((1, 1, block_size),
                         lambda i, kv, b, t: (t[i, b], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, hd),
                               lambda i, kv, b, t: (i, kv, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), f32),           # running max
            pltpu.VMEM((rows, 1), f32),           # running normalizer
            pltpu.VMEM((rows, hd), f32),          # output accumulator
            pltpu.VMEM((rows, block_size), f32),  # probability tile
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, rows, hd), f32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(tables.astype(jnp.int32), qf, qpos, kt, vr, posr)
    return out.reshape(B, KV, S, G, hd).transpose(0, 2, 1, 3, 4)
