"""Attention: GQA/MHA with RoPE, sliding windows, cross-attention, and a
memory-bounded chunked (flash-style) softmax for long-context prefill.

Every contraction -- projections AND the softmax-path score/PV einsums --
routes through the fair-square einsum dispatch (``fs_einsum``), with
per-site policy overrides: sites ``attn_qkv`` / ``attn_out`` for the
weight GEMMs and ``attn_scores`` / ``attn_pv`` for the softmax path (the
pair a :data:`repro.configs.base.SQUARE_GEMMS_POLICY` keeps on the
multiplier baseline).

Layouts: activations (B, S, D); q (B, S, KV, G, hd) with G = H // KV
(grouped-query); k/v (B, T, KV, hd).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import counting
from repro.core import prepared
from repro.core.einsum import fs_einsum
from repro.layers import basic
from repro.layers.param import ParamSpec

__all__ = ["attn_spec", "attn_forward", "attn_decode", "chunked_attention",
           "init_paged_kv_cache", "paged_slots", "paged_gather_indices",
           "paged_read",
           "EMPTY_POS", "ATTEND_POS_LIMIT"]

# Sentinel position of an unwritten / freed / padded physical cache slot.
# Any value >= ATTEND_POS_LIMIT is treated as "never attend" by the decode
# masks (the dense cache uses the same convention for its ``pos`` buffer).
# The limit is a named bound so the masks and the allocator bookkeeping
# (serve/paged.py writes EMPTY_POS into recycled blocks) cannot drift:
# every mask tests ``pos < ATTEND_POS_LIMIT`` and every sentinel write
# uses EMPTY_POS, which sits safely above it.
EMPTY_POS = 2 ** 30
ATTEND_POS_LIMIT = 2 ** 29

NEG_INF = -1e30


def attn_spec(cfg, stack: int = 0, cross: bool = False):
    """Projections carry explicit (heads, head_dim) axes so the sharding
    rules shard the HEAD axis and never split a head_dim (which would break
    rope pairing and turn every score into a cross-device partial sum).
    kv=1 archs simply replicate K/V projections (rule dropped)."""
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    h, kv = cfg.n_heads, cfg.n_kv_heads
    dt = jnp.dtype(cfg.dtype)
    bias = cfg.attn_bias

    def proj(shape, axes):
        if stack:
            shape = (stack,) + shape
            axes = ("layers",) + axes
        return {"w": ParamSpec(shape, axes, dtype=dt, fan_in=d)}

    def pbias(shape, axes):
        if stack:
            shape = (stack,) + shape
            axes = ("layers",) + axes
        return {"b": ParamSpec(shape, axes, dtype=dt, init="zeros")}

    spec = {
        "wq": proj((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": proj((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wv": proj((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wo": proj((h, hd, d), ("heads", "head_dim", "embed")),
    }
    if bias:
        spec["wq"].update(pbias((h, hd), ("heads", "head_dim")))
        spec["wk"].update(pbias((kv, hd), ("kv_heads", "head_dim")))
        spec["wv"].update(pbias((kv, hd), ("kv_heads", "head_dim")))
        spec["wo"].update(pbias((d,), ("embed",)))
    return spec


def _proj_in(p, x, n, hd, mode, policy=None):
    """x[..., d] @ w[d, n, hd] -> (..., n, hd), through fair-square dispatch.

    ``p["w"]`` may be a PreparedOperand holding the already-reshaped
    (d, n*hd) projection (see :meth:`repro.models.lm.LM.prepare_params`)."""
    w = p["w"]
    if not isinstance(w, prepared.PreparedOperand):
        w = w.reshape(w.shape[-3], n * hd)
    out = basic.dense_apply({"w": w}, x, mode=mode,
                            policy=policy, site="attn_qkv")
    out = out.reshape(*x.shape[:-1], n, hd)
    if "b" in p:
        out = out + p["b"].astype(out.dtype)
    return out


def _proj_out(p, x, mode, out_dtype, tp_reduce: bool = False, policy=None):
    """x[..., h, hd] @ w[h, hd, d] -> (..., d)."""
    w = p["w"]
    if isinstance(w, prepared.PreparedOperand):
        h_hd = w.shape[0]                       # prepared as (h*hd, d)
        p2 = {"w": w}
        xf = x.reshape(*x.shape[:-2], h_hd)
    else:
        h, hd, d = w.shape[-3:]
        p2 = {"w": w.reshape(h * hd, d)}
        xf = x.reshape(*x.shape[:-2], h * hd)
    if tp_reduce:
        out = basic.dense_tp_reduce(p2, xf, mode=mode, policy=policy,
                                    site="attn_out")
    else:
        out = basic.dense_apply(p2, xf, mode=mode, policy=policy,
                                site="attn_out")
    if "b" in p:
        out = out + p["b"].astype(out.dtype)
    return out.astype(out_dtype)


def _split_heads(x, n, hd):
    return x.reshape(*x.shape[:-1], n, hd)


def _softcap(scores, cap: float):
    if cap and cap > 0.0:
        return jnp.tanh(scores / cap) * cap
    return scores


def chunked_attention(q, k, v, q_pos, kv_pos, *, causal: bool,
                      window: Optional[int], chunk_q: int, chunk_kv: int,
                      softcap: float = 0.0, block_skip: bool = False,
                      p_bf16: bool = False, fold_q: bool = False,
                      mode: Optional[str] = None, policy=None):
    """Online-softmax attention, O(chunk_q * chunk_kv) live scores.

    q: (B, S, KV, G, hd); k: (B, T, KV, hd); v: (B, T, KV, hv) (``hv``
    may differ from ``hd``, as latent attention's does); positions are
    absolute.  Returns (B, S, KV, G, hv) in q.dtype.

    ``block_skip``: causal block-diagonal skipping -- q block i only visits
    kv chunks 0..i (a STATIC triangular schedule: each q block gets its own
    fixed-trip inner scan, so both autodiff and trip-count-aware flop
    accounting stay exact).  Halves attention flops for long causal
    prefill/training at the cost of O(n_q_blocks) HLO size.
    """
    B, S, KV, G, hd = q.shape
    T = k.shape[1]
    hv = v.shape[-1]
    cq = min(chunk_q, S)
    ck = min(chunk_kv, T)
    pad_q = (-S) % cq
    pad_k = (-T) % ck
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0), (0, 0)))
    qpos = jnp.pad(q_pos, (0, pad_q), constant_values=-1)
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    kpos = jnp.pad(kv_pos, (0, pad_k), constant_values=EMPTY_POS)
    nq, nk = qp.shape[1] // cq, kp.shape[1] // ck

    scale = hd ** -0.5
    qb = jnp.moveaxis(qp.reshape(B, nq, cq, KV, G, hd), 1, 0)   # (nq,B,cq,KV,G,hd)
    qposb = qpos.reshape(nq, cq)
    kb = jnp.moveaxis(kp.reshape(B, nk, ck, KV, hd), 1, 0)      # (nk,B,ck,KV,hd)
    vb = jnp.moveaxis(vp.reshape(B, nk, ck, KV, hv), 1, 0)
    kposb = kpos.reshape(nk, ck)

    def q_block(qc, qpc, n_kv: Optional[int] = None):
        """Process one q chunk against kv chunks [0, n_kv) (default: all)."""
        qf = (qc.astype(jnp.float32) * scale)

        def kv_step(carry, kv_in):
            m, l, acc = carry
            kc, vc, kpc = kv_in
            s = fs_einsum("bqkgh,bckh->bkgqc", qf, kc.astype(jnp.float32),
                          mode=mode, policy=policy, site="attn_scores")
            s = _softcap(s, softcap)
            mask = kpc[None, :] < ATTEND_POS_LIMIT   # padded kv never attend
            if causal:
                mask &= kpc[None, :] <= qpc[:, None]
            if window is not None:
                mask &= (qpc[:, None] - kpc[None, :]) < window
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            if p_bf16:
                # halve the HBM round-trip of the probability tensor:
                # accumulate stays f32 (preferred_element_type)
                pv = fs_einsum("bkgqc,bckh->bkgqh", p.astype(jnp.bfloat16),
                               vc, mode=mode, policy=policy, site="attn_pv",
                               preferred=jnp.float32)
            else:
                pv = fs_einsum("bkgqc,bckh->bkgqh", p,
                               vc.astype(jnp.float32),
                               mode=mode, policy=policy, site="attn_pv")
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, cq), jnp.float32)
        a0 = jnp.zeros((B, KV, G, cq, hv), jnp.float32)
        xs = ((kb, vb, kposb) if n_kv is None
              else (kb[:n_kv], vb[:n_kv], kposb[:n_kv]))
        with counting.count_scale(nk if n_kv is None else n_kv):
            (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), xs)
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return jnp.moveaxis(out, 3, 1)                          # (B,cq,KV,G,hv)

    if fold_q:
        # Fold the q-chunk axis into a vmapped batch dim and shard it over
        # the MODEL axis: archs whose head count does not divide the model
        # axis (paligemma 8H, whisper 20H, starcoder2 24H, recurrentgemma
        # 10H) otherwise run attention fully REPLICATED across the 16-way
        # model axis.  (nq, B) 2D-shards over (model, data); K/V stay
        # data-sharded and broadcast over model -- cheap for small-kv archs.
        from repro.distributed import context as dctx
        from repro.distributed import sharding as shd
        mesh = dctx.current_mesh()
        if mesh is not None:
            qb = shd.constrain(qb, mesh, "q_chunks", "batch")
        with counting.count_scale(nq):
            outs = jax.vmap(q_block)(qb, qposb)
        if mesh is not None:
            outs = shd.constrain(outs, mesh, "q_chunks", "batch")
    elif block_skip and causal and window is None:
        # static triangular schedule: q block i visits kv chunks 0..ceil end
        blocks = []
        for qi in range(nq):
            n_kv = min(nk, ((qi + 1) * cq + ck - 1) // ck)
            blocks.append(q_block(qb[qi], qposb[qi], n_kv=n_kv))
        outs = jnp.stack(blocks)
    else:
        with counting.count_scale(nq):
            outs = jax.lax.map(lambda args: q_block(*args), (qb, qposb))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, nq * cq, KV, G, hv)
    return out[:, :S].astype(q.dtype)


def attn_forward(p, x, *, cfg, positions, causal: bool = True,
                 window: Optional[int] = None, cross_x=None,
                 cross_positions=None, mode: Optional[str] = None,
                 policy=None):
    """Full-sequence attention (train / prefill).  Returns (out, (k, v)) so
    callers can seed KV caches.  ``cross_x`` switches to cross-attention
    (K/V from the encoder stream; no causal mask, no rope on K)."""
    B, S, D = x.shape
    hd = cfg.resolved_head_dim
    H, KV = cfg.n_heads, cfg.n_kv_heads
    G = H // KV

    q = _proj_in(p["wq"], x, H, hd, mode, policy)
    kv_src = cross_x if cross_x is not None else x
    k = _proj_in(p["wk"], kv_src, KV, hd, mode, policy)
    v = _proj_in(p["wv"], kv_src, KV, hd, mode, policy)
    k = k.astype(jnp.dtype(cfg.dtype))
    v = v.astype(jnp.dtype(cfg.dtype))
    q = q.astype(jnp.dtype(cfg.dtype))

    if cross_x is None:
        q = basic.rope(q, positions, cfg.rope_theta)
        k = basic.rope(k, positions, cfg.rope_theta)
        kv_pos = positions
        is_causal = causal
    else:
        kv_pos = cross_positions
        is_causal = False
        window = None

    qg = q.reshape(B, S, KV, G, hd)
    out = chunked_attention(qg, k, v, positions, kv_pos, causal=is_causal,
                            window=window, chunk_q=cfg.attn_chunk_q,
                            chunk_kv=cfg.attn_chunk_kv,
                            softcap=cfg.attn_logit_softcap,
                            block_skip=cfg.attn_block_skip,
                            p_bf16=cfg.attn_p_bf16,
                            fold_q=cfg.attn_fold_q,
                            mode=mode, policy=policy)
    out = out.reshape(B, S, H, hd)
    return _proj_out(p["wo"], out, mode, x.dtype,
                     tp_reduce=cfg.tp_bf16_reduce, policy=policy), (k, v)


def attn_decode(p, x, cache, pos, *, cfg, window: Optional[int] = None,
                cross_cache=None, mode: Optional[str] = None, policy=None,
                paged=None):
    """Single-token decode.  x: (B, 1, D); cache: dict(k, v) with layout
    (B, T, KV, hd) (ring buffer when ``window``).

    ``pos``: absolute position of the new token.  A SCALAR pos means
    lockstep decoding (the whole batch at one position): the cache update
    lowers to a ``dynamic_update_slice``, which SPMD-partitions cleanly.  A
    per-row ``(B,)`` pos (continuous batching with ragged positions) uses a
    batched scatter -- correct everywhere, but GSPMD lowers it with a full
    cache all-gather (measured 2.1 GB x 96 per step on moonshot decode), so
    the distributed launcher always decodes in lockstep.

    ``paged`` switches to the paged-KV-cache path (the serving engine):
    ``cache`` is then a POOL ``{"k": (P, KV, hd), "v": (P, KV, hd)}``
    shared by every sequence, ``x`` may carry a multi-token chunk
    ``(B, S, D)`` (chunked prefill) and ``pos`` is ``(B, S)`` absolute
    positions with ``-1`` marking padding.  See :func:`_attn_paged_step`.
    """
    if paged is not None:
        return _attn_paged_step(p, x, cache, pos, cfg=cfg, window=window,
                                mode=mode, policy=policy, paged=paged)
    B, _, D = x.shape
    hd = cfg.resolved_head_dim
    H, KV = cfg.n_heads, cfg.n_kv_heads
    G = H // KV
    dt = jnp.dtype(cfg.dtype)
    lockstep = (jnp.ndim(pos) == 0)
    pos_b = jnp.broadcast_to(pos, (B,)) if lockstep else pos

    q = _proj_in(p["wq"], x, H, hd, mode, policy).astype(dt)

    if cross_cache is not None:
        k, v = cross_cache["k"], cross_cache["v"]
        T = k.shape[1]
        valid = jnp.ones((B, T), dtype=bool)
        qr = q
        new_cache = cache
    else:
        k1 = _proj_in(p["wk"], x, KV, hd, mode, policy).astype(dt)
        v1 = _proj_in(p["wv"], x, KV, hd, mode, policy).astype(dt)
        qr = basic.rope(q, pos_b[:, None], cfg.rope_theta)
        k1 = basic.rope(k1, pos_b[:, None], cfg.rope_theta)
        T = cache["k"].shape[1]
        if lockstep:
            slot = (pos % T) if window is not None else jnp.minimum(pos, T - 1)
            k = jax.lax.dynamic_update_slice(
                cache["k"], k1.astype(cache["k"].dtype), (0, slot, 0, 0))
            v = jax.lax.dynamic_update_slice(
                cache["v"], v1.astype(cache["v"].dtype), (0, slot, 0, 0))
            kv_abs = jax.lax.dynamic_update_slice(
                cache["pos"], jnp.broadcast_to(pos, (B, 1)).astype(jnp.int32),
                (0, slot))
        else:
            # ring for SWA; the no-window clamp must match the lockstep
            # branch -- an unclamped past-capacity pos silently scatters
            # out of bounds (dropped update) instead of pinning to the
            # last slot like dynamic_update_slice does
            slot = (pos % T) if window is not None \
                else jnp.minimum(pos, T - 1)
            bidx = jnp.arange(B)
            k = cache["k"].at[bidx, slot].set(k1[:, 0])
            v = cache["v"].at[bidx, slot].set(v1[:, 0])
            kv_abs = cache["pos"].at[bidx, slot].set(pos)
        from repro.distributed import context as dctx
        from repro.distributed import sharding as shd
        mesh = dctx.current_mesh()
        if mesh is not None:
            # pin the decode-cache layout: (batch->data, kv_heads->model);
            # without this GSPMD loses the kv sharding across the layer-scan
            # ys buffer and all-gathers every layer's cache slice
            k = shd.constrain(k, mesh, "batch", None, "kv_heads", None)
            v = shd.constrain(v, mesh, "batch", None, "kv_heads", None)
        new_cache = {"k": k, "v": v, "pos": kv_abs}
        valid = kv_abs <= pos_b[:, None]
        if window is not None:
            valid &= (pos_b[:, None] - kv_abs) < window

    qf = qr.reshape(B, 1, KV, G, hd).astype(jnp.float32) * hd ** -0.5
    s = fs_einsum("bqkgh,btkh->bkgqt", qf, k.astype(jnp.float32),
                  mode=mode, policy=policy, site="attn_scores")
    s = _softcap(s, cfg.attn_logit_softcap)
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = fs_einsum("bkgqt,btkh->bqkgh", w, v.astype(jnp.float32),
                    mode=mode, policy=policy, site="attn_pv")
    out = out.reshape(B, 1, H, hd).astype(dt)
    return _proj_out(p["wo"], out, mode, x.dtype,
                     tp_reduce=cfg.tp_bf16_reduce, policy=policy), new_cache


def paged_slots(tables, positions, block_size: int):
    """Physical pool slot of each (sequence, position) pair.

    ``tables``: (B, nb) int32 block table (block ids into the shared pool;
    block 0 is the reserved NULL block).  ``positions``: (B, S) absolute
    token positions, ``-1`` for padding.  Returns (B, S) flat indices into
    a (num_blocks * block_size, ...) pool; padded entries map to slot 0
    (inside the null block, never attended because its ``pos_pool`` entry
    stays :data:`EMPTY_POS`).
    """
    pos_r = jnp.maximum(positions, 0)
    blk = jnp.take_along_axis(tables, pos_r // block_size, axis=1)
    phys = blk * block_size + pos_r % block_size
    return jnp.where(positions >= 0, phys, 0).astype(jnp.int32)


def paged_gather_indices(tables, block_size: int):
    """(B, nb * block_size) flat pool indices covering each sequence's
    logical cache window, in position order (the gather-based attention
    read: ``pool[idx]`` materializes a (B, T, KV, hd) view)."""
    B, nb = tables.shape
    offs = jnp.arange(block_size, dtype=tables.dtype)
    return (tables[:, :, None] * block_size
            + offs[None, None, :]).reshape(B, nb * block_size)


def _attn_paged_step(p, x, cache, pos, *, cfg, window, mode, policy, paged):
    """Multi-token attention step against the paged KV pool.

    One code path serves both the engine's chunked prefill (S = chunk) and
    batched decode (S = 1): new K/V are scattered to their physical slots,
    then every query attends over its own block table's logical window
    with an absolute-position causal mask -- prior chunks and intra-chunk
    causality fall out of the same ``kv_pos <= q_pos`` rule.

    Two read routes, resolved by :mod:`repro.kernels.routing`
    (``paged_attn: kernel|gather``) when the ``attn_paged`` site resolves
    to ``square_pallas``:

    - ``kernel`` -- the fused block-streaming Pallas kernel
      (:func:`repro.kernels.sq_paged_attn.sq_paged_attn`): block tables
      are indexed inside the grid, only each sequence's live columns are
      read, straight from the stored pools, and the gathered window is
      never materialized.  Guarded like every square-routed contraction: a
      non-finite output (eager only) trips the ``attn_paged`` route-health
      breaker and recomputes via the gather path.
    - ``gather`` -- ``paged_gather_indices`` + ``jnp.take`` materializes
      the dense (B, T, KV, hd) window, then the usual einsum pair.

    Both are token-identical; sliding windows mask by position distance
    instead of ring-indexing on either route.

    ``paged``: dict(tables (B, nb), pos_pool (P,) -- already holding this
    chunk's positions (the LM scatters once per step, shared across
    layers), phys (B, S) precomputed by :func:`paged_slots`, block_size).
    """
    B, S, D = x.shape
    hd = cfg.resolved_head_dim
    H, KV = cfg.n_heads, cfg.n_kv_heads
    G = H // KV
    dt = jnp.dtype(cfg.dtype)
    pos_r = jnp.maximum(pos, 0)

    q = _proj_in(p["wq"], x, H, hd, mode, policy).astype(dt)
    k1 = _proj_in(p["wk"], x, KV, hd, mode, policy).astype(dt)
    v1 = _proj_in(p["wv"], x, KV, hd, mode, policy).astype(dt)
    qr = basic.rope(q, pos_r, cfg.rope_theta)
    k1 = basic.rope(k1, pos_r, cfg.rope_theta)

    phys = paged["phys"].reshape(B * S)
    k_pool = cache["k"].at[phys].set(k1.reshape(B * S, KV, hd)
                                     .astype(cache["k"].dtype))
    v_pool = cache["v"].at[phys].set(v1.reshape(B * S, KV, hd)
                                     .astype(cache["v"].dtype))

    qf = qr.reshape(B, S, KV, G, hd).astype(jnp.float32) * hd ** -0.5

    def gather_attend():
        idx = paged_gather_indices(paged["tables"], paged["block_size"])
        k = jnp.take(k_pool, idx, axis=0)                  # (B, T, KV, hd)
        v = jnp.take(v_pool, idx, axis=0)
        kv_pos = jnp.take(paged["pos_pool"], idx, axis=0)  # (B, T)
        valid = (kv_pos[:, None, :] <= pos[:, :, None]) \
            & (kv_pos[:, None, :] < ATTEND_POS_LIMIT)      # (B, S, T)
        if window is not None:
            valid &= (pos[:, :, None] - kv_pos[:, None, :]) < window
        s = fs_einsum("bqkgh,btkh->bkgqt", qf, k.astype(jnp.float32),
                      mode=mode, policy=policy, site="attn_scores")
        s = _softcap(s, cfg.attn_logit_softcap)
        s = jnp.where(valid[:, None, None], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        return fs_einsum("bkgqt,btkh->bqkgh", w, v.astype(jnp.float32),
                         mode=mode, policy=policy, site="attn_pv")

    out = paged_read(qf, k_pool, v_pool, pos, paged, dt=dt, window=window,
                     softcap=cfg.attn_logit_softcap, mode=mode,
                     policy=policy, gather_attend=gather_attend)
    out = out.reshape(B, S, H, hd).astype(dt)
    return _proj_out(p["wo"], out, mode, x.dtype,
                     tp_reduce=cfg.tp_bf16_reduce, policy=policy), \
        {"k": k_pool, "v": v_pool}


def paged_read(qf, k_pool, v_pool, pos, paged, *, dt, window, softcap,
               mode, policy, gather_attend, dv=None):
    """Attention of pre-scaled queries ``qf`` (B, S, KV, G, hd) over the
    paged pools, by the route :mod:`repro.kernels.routing` picks when the
    ``attn_paged`` site resolves to ``square_pallas``: the fused kernel
    (``v_pool`` None: latent mode, V the first ``dv`` columns of K), or
    ``gather_attend()``, the caller's dense read.  Returns (B, S, KV, G,
    Dv) f32.  Guarded like every square-routed contraction: a non-finite
    kernel output (eager only) trips the ``attn_paged`` route-health
    breaker and recomputes on the gather route."""
    B, S, KV, G, hd = qf.shape
    T = paged["tables"].shape[1] * paged["block_size"]
    from repro.core.einsum import resolve_mode     # lazy: import cycle
    use_kernel = False
    if resolve_mode(mode, policy, "attn_paged") == "square_pallas" \
            and jnp.issubdtype(dt, jnp.floating):
        from repro.kernels import routing
        route = routing.select_paged_attn_route(
            S, T, batch=B, kv_heads=KV, group=G, hd=hd, dtype=dt)
        hkey = routing.health_key("attn_paged", (B, S, KV, G, hd, T), dt)
        use_kernel = (route.name == "kernel"
                      and not routing.route_health().is_demoted(hkey))
    if not use_kernel:
        return gather_attend()

    from repro.core import guards
    from repro.kernels import tuning
    from repro.kernels.ops import default_interpret
    from repro.kernels.sq_paged_attn import sq_paged_attn, tile_blocks
    interp = default_interpret()
    bs = paged["block_size"]
    plan = tuning.plan_paged_attn(
        S * G, hd, tile_blocks(bs, paged["tables"].shape[1]) * bs,
        k_pool.dtype, kv_heads=KV, dv=dv,
        pm_layout="mnk" if interp else "mkn")
    out = sq_paged_attn(
        qf, k_pool, v_pool, paged["tables"], paged["pos_pool"], pos,
        block_size=bs, window=window, softcap=softcap,
        attend_limit=ATTEND_POS_LIMIT, kc_qk=plan.kc_qk, kc_pv=plan.kc_pv,
        pm_layout=plan.pm_layout, interpret=interp, dv=dv)
    gp = guards.guard_policy()
    if gp.enabled and guards.check_finite(out) is False:
        # eager-only (check_finite is None under a jit trace): trip the
        # breaker and recompute on the gather route, whose fs_einsums do
        # their own counting
        routing.route_health().record_trip(hkey, limit=gp.trip_limit)
        return gather_attend()
    # the kernel subsumes both softmax-path contractions; count them at
    # the sites the audit already knows
    for site, width in (("attn_scores", hd), ("attn_pv", out.shape[-1])):
        counting.note_contraction(
            site=site, spec="paged_attn_kernel", mode="square_pallas",
            mults=B * KV * G * S * T * width)
    return out


def init_paged_kv_cache(cfg, pool_slots: int):
    """Empty paged KV pool: ``pool_slots`` = num_blocks * block_size
    physical token slots shared by every sequence (block tables map logical
    positions to slots).  Position bookkeeping lives in the engine's single
    shared ``pos_pool`` -- the layout is identical across layers, so it is
    not replicated per layer like the dense cache's ``pos``."""
    hd = cfg.resolved_head_dim
    dt = jnp.dtype(cfg.dtype)
    return {
        "k": jnp.zeros((pool_slots, cfg.n_kv_heads, hd), dt),
        "v": jnp.zeros((pool_slots, cfg.n_kv_heads, hd), dt),
    }


def init_kv_cache(cfg, batch: int, max_len: int, window: Optional[int] = None):
    """Empty KV cache.  SWA archs allocate only the window (ring buffer)."""
    T = min(max_len, window) if window is not None else max_len
    hd = cfg.resolved_head_dim
    dt = jnp.dtype(cfg.dtype)
    return {
        "k": jnp.zeros((batch, T, cfg.n_kv_heads, hd), dt),
        "v": jnp.zeros((batch, T, cfg.n_kv_heads, hd), dt),
        "pos": jnp.full((batch, T), EMPTY_POS, jnp.int32),
    }
