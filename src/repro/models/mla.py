"""Multi-head latent attention (MLA, DeepSeek-V2/V3), no query LoRA.

Widths: ``H`` heads, latent ``r = kv_lora_rank``, per head ``dn =
qk_nope_head_dim`` + ``dr = qk_rope_head_dim`` query/key columns and ``dv
= v_head_dim`` value columns.  For tokens ``x`` (S, d):

    q_h      = x W_q                     -> [q_nope_h (dn), q_pe_h (dr)]
    [c, p]   = x W_kv_a                  -> c (r), p (dr)
    c        = RMSNorm(c; kv_norm)       the latent, one row a token
    k_pe     = RoPE(p)                   one rotary key a token, every head
    q_pe_h   = RoPE(q_pe_h)
    k_nope_h = c W_UK_h,   v_h = c W_UV_h          (W_kv_b = [W_UK, W_UV])
    s_h(t)   = (q_nope_h . k_nope_h(t) + q_pe_h . k_pe(t)) / sqrt(dn + dr)
    o_h      = softmax_t(s_h) v_h,       out = [o_1 .. o_H] W_o

:func:`mla_forward` (train / full prefill) computes it as written: it
decompresses every key and value.  The cached steps use the **absorbed**
form, which never decompresses the cache.  Since ``q_nope_h . (c W_UK_h)
= (q_nope_h W_UK_h^T) . c`` and ``o_h = (sum_t p_t c_t) W_UV_h``:

    q_lat_h  = q_nope_h W_UK_h^T         (r)           site mla_absorb
    s_h(t)   = [q_lat_h, q_pe_h] . [c(t), k_pe(t)] / sqrt(dn + dr)
    o_lat_h  = sum_t softmax_t(s_h) c(t) (r)
    o_h      = o_lat_h W_UV_h            (dv)          site mla_unabsorb

so each token keeps one ``r + dr`` row, ``[c, k_pe]`` (576 values for
Moonlight), read as the key of all H heads (q.k over ``r + dr``) and, in
its first ``r`` columns, as their value (p.v over ``r``).  The paged step
(the serving engine: chunked prefill and decode alike) scatters that row
once per token into the layer's latent pool ``{"kv": (P, 1, r + dr)}``:
one whole-minor-dim pool, whose ``(block, 1, r + dr)`` blocks Mosaic takes
as they are, so no split into a 512 and a 64 pool is needed.  Attention
over the pool takes :func:`repro.kernels.routing.select_paged_attn_route`'s
route: the fused kernel in latent mode
(:func:`repro.kernels.sq_paged_attn.sq_paged_attn` with ``v_pool=None``:
the H query rows of a token over one latent head, V the first ``r``
columns of the K tile), or the gather of the table's latent rows.

Weights: ``wq`` (d, H, dn + dr), ``wkv_a`` (d, r + dr), ``kv_norm`` (r),
``wk_b`` (H, dn, r) = W_UK^T, ``wv_b`` (H, r, dv) = W_UV, ``wo`` (H, dv,
d); ``wk_b``/``wv_b`` are laid out head-major so that both absorbed
contractions are batched GEMMs over heads in their stored layout.  RoPE
is rotate-half over the ``dr`` columns; scaled by ``(dn + dr) ** -0.5``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.einsum import fs_einsum
from repro.layers import basic
from repro.layers.param import ParamSpec
from repro.models import attention as attn

__all__ = ["mla_spec", "mla_forward", "mla_decode", "init_paged_latent_cache",
           "init_latent_cache", "latent_width"]


def latent_width(cfg) -> int:
    """Values one token keeps per layer: the latent and its rotary key."""
    return cfg.kv_lora_rank + cfg.qk_rope_head_dim


def mla_spec(cfg, stack: int = 0):
    d, H = cfg.d_model, cfg.n_heads
    r, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    dt = jnp.dtype(cfg.dtype)

    def w(shape, axes, fan_in):
        if stack:
            shape, axes = (stack,) + shape, ("layers",) + axes
        return {"w": ParamSpec(shape, axes, dtype=dt, fan_in=fan_in)}

    return {
        "wq": w((d, H, dn + dr), ("embed", "heads", "head_dim"), d),
        "wkv_a": w((d, r + dr), ("embed", None), d),
        "kv_norm": basic.rmsnorm_spec(r, stack),
        "wk_b": w((H, dn, r), ("heads", "head_dim", None), r),
        "wv_b": w((H, r, dv), ("heads", None, "head_dim"), r),
        "wo": w((H, dv, d), ("heads", "head_dim", "embed"), H * dv),
    }


def _project(p, x, positions, cfg, mode, policy):
    """Queries (B, S, H, dn + dr) with RoPE on their last ``dr`` columns,
    and each token's cache row (B, S, r + dr): [normed latent, roped
    k_pe]."""
    H = cfg.n_heads
    r, dn, dr = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    dt = jnp.dtype(cfg.dtype)
    q = attn._proj_in(p["wq"], x, H, dn + dr, mode, policy).astype(dt)
    q = jnp.concatenate([q[..., :dn],
                         basic.rope(q[..., dn:], positions, cfg.rope_theta)],
                        axis=-1)
    ckv = basic.dense_apply(p["wkv_a"], x, mode=mode, policy=policy,
                            site="attn_qkv")
    c = basic.rmsnorm_apply(p["kv_norm"], ckv[..., :r], eps=cfg.norm_eps)
    k_pe = basic.rope(ckv[..., None, r:], positions, cfg.rope_theta)[..., 0, :]
    row = jnp.concatenate([c.astype(jnp.float32),
                           k_pe.astype(jnp.float32)], axis=-1).astype(dt)
    return q, row


def mla_forward(p, x, *, cfg, positions, causal: bool = True,
                window: Optional[int] = None, mode: Optional[str] = None,
                policy=None):
    """Full-sequence MLA, textbook form (decompressed K and V).  Returns
    (out, {"kv": (B, S, 1, r + dr)}), the cache rows of every token."""
    B, S, _ = x.shape
    H = cfg.n_heads
    r, dn, dr = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    dt = jnp.dtype(cfg.dtype)
    with jax.named_scope("mla"):
        q, row = _project(p, x, positions, cfg, mode, policy)
        c = row[..., :r]
        k_nope = fs_einsum("bsc,hnc->bshn", c, p["wk_b"]["w"], mode=mode,
                           policy=policy, site="mla_absorb").astype(dt)
        v = fs_einsum("bsc,hcv->bshv", c, p["wv_b"]["w"], mode=mode,
                      policy=policy, site="mla_unabsorb").astype(dt)
        k_pe = jnp.broadcast_to(row[:, :, None, r:], (B, S, H, dr))
        k = jnp.concatenate([k_nope, k_pe], axis=-1)
        with jax.named_scope("mla.latent_attn"):
            out = attn.chunked_attention(
                q.reshape(B, S, H, 1, dn + dr), k, v, positions, positions,
                causal=causal, window=window, chunk_q=cfg.attn_chunk_q,
                chunk_kv=cfg.attn_chunk_kv, softcap=cfg.attn_logit_softcap,
                block_skip=cfg.attn_block_skip, p_bf16=cfg.attn_p_bf16,
                mode=mode, policy=policy)
        out = attn._proj_out(p["wo"], out.reshape(B, S, H, -1), mode,
                             x.dtype, policy=policy)
    return out, {"kv": row[:, :, None, :]}


def _gather_attend(qf, kv, valid, r, softcap, mode, policy):
    """Absorbed attention over gathered latent rows.  qf (B, S, 1, H,
    r + dr) pre-scaled; kv (B, T, 1, r + dr); valid (B, S, T).  Returns
    the latent outputs (B, S, 1, H, r) f32."""
    kvf = kv.astype(jnp.float32)
    s = fs_einsum("bqkgh,btkh->bkgqt", qf, kvf, mode=mode, policy=policy,
                  site="attn_scores")
    s = attn._softcap(s, softcap)
    s = jnp.where(valid[:, None, None], s, attn.NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return fs_einsum("bkgqt,btkh->bqkgh", w, kvf[..., :r], mode=mode,
                     policy=policy, site="attn_pv")


def _absorbed_queries(p, q, cfg, mode, policy):
    """[q_nope W_UK^T, q_pe] scaled: (B, S, 1, H, r + dr) f32."""
    B, S, H, _ = q.shape
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q_lat = fs_einsum("bshn,hnc->bshc", q[..., :dn], p["wk_b"]["w"],
                      mode=mode, policy=policy, site="mla_absorb")
    qf = jnp.concatenate([q_lat.astype(jnp.float32),
                          q[..., dn:].astype(jnp.float32)], axis=-1)
    return (qf * (dn + dr) ** -0.5).reshape(B, S, 1, H, -1)


def _unabsorb(p, o_lat, x, cfg, mode, policy):
    """Latent outputs (B, S, 1, H, r) through W_UV and W_o."""
    B, S = o_lat.shape[:2]
    dt = jnp.dtype(cfg.dtype)
    o = fs_einsum("bshc,hcv->bshv",
                  o_lat.reshape(B, S, cfg.n_heads, -1).astype(dt),
                  p["wv_b"]["w"], mode=mode, policy=policy,
                  site="mla_unabsorb").astype(dt)
    return attn._proj_out(p["wo"], o, mode, x.dtype, policy=policy)


def mla_decode(p, x, cache, pos, *, cfg, window: Optional[int] = None,
               mode: Optional[str] = None, policy=None, paged=None):
    """Cached MLA step, absorbed form.  ``paged`` selects the serving
    engine's latent pool (``cache`` = ``{"kv": (P, 1, r + dr)}``, ``x``
    (B, S, D), ``pos`` (B, S) with -1 padding; see
    ``attention._attn_paged_step`` for ``paged``); without it ``cache`` is
    the dense per-slot ``{"kv": (B, T, 1, r + dr), "pos": (B, T)}`` and x
    (B, 1, D) one token per row at ``pos`` (scalar or (B,))."""
    with jax.named_scope("mla"):
        if paged is not None:
            return _paged_step(p, x, cache, pos, cfg=cfg, window=window,
                               mode=mode, policy=policy, paged=paged)
        B = x.shape[0]
        r = cfg.kv_lora_rank
        pos_b = jnp.broadcast_to(pos, (B,)) if jnp.ndim(pos) == 0 else pos
        q, row = _project(p, x, pos_b[:, None], cfg, mode, policy)
        T = cache["kv"].shape[1]
        slot = jnp.minimum(pos_b, T - 1)
        bidx = jnp.arange(B)
        kv = cache["kv"].at[bidx, slot].set(
            row[:, 0, None].astype(cache["kv"].dtype))
        kv_pos = cache["pos"].at[bidx, slot].set(pos_b.astype(jnp.int32))
        valid = (kv_pos <= pos_b[:, None]) \
            & (kv_pos < attn.ATTEND_POS_LIMIT)
        if window is not None:
            valid &= (pos_b[:, None] - kv_pos) < window
        with jax.named_scope("mla.latent_attn"):
            qf = _absorbed_queries(p, q, cfg, mode, policy)
            o_lat = _gather_attend(qf, kv, valid[:, None, :], r,
                                   cfg.attn_logit_softcap, mode, policy)
        out = _unabsorb(p, o_lat, x, cfg, mode, policy)
    return out, {"kv": kv, "pos": kv_pos}


def _paged_step(p, x, cache, pos, *, cfg, window, mode, policy, paged):
    B, S, _ = x.shape
    r = cfg.kv_lora_rank
    Dk = latent_width(cfg)
    pos_r = jnp.maximum(pos, 0)
    q, row = _project(p, x, pos_r, cfg, mode, policy)
    pool = cache["kv"].at[paged["phys"].reshape(B * S)].set(
        row.reshape(B * S, 1, Dk).astype(cache["kv"].dtype))
    dt = jnp.dtype(cfg.dtype)

    with jax.named_scope("mla.latent_attn"):
        qf = _absorbed_queries(p, q, cfg, mode, policy)

        def gather_attend():
            idx = attn.paged_gather_indices(paged["tables"],
                                            paged["block_size"])
            kv = jnp.take(pool, idx, axis=0)                 # (B, T, 1, Dk)
            kv_pos = jnp.take(paged["pos_pool"], idx, axis=0)
            valid = (kv_pos[:, None, :] <= pos[:, :, None]) \
                & (kv_pos[:, None, :] < attn.ATTEND_POS_LIMIT)
            if window is not None:
                valid &= (pos[:, :, None] - kv_pos[:, None, :]) < window
            return _gather_attend(qf, kv, valid, r, cfg.attn_logit_softcap,
                                  mode, policy)

        o_lat = attn.paged_read(
            qf, pool, None, pos, paged, dt=dt, window=window,
            softcap=cfg.attn_logit_softcap, mode=mode, policy=policy,
            gather_attend=gather_attend, dv=r)
    return _unabsorb(p, o_lat, x, cfg, mode, policy), {"kv": pool}


def init_paged_latent_cache(cfg, pool_slots: int):
    """Empty latent pool: one ``r + dr`` row per physical slot, read as
    both K and V (see the module docstring)."""
    return {"kv": jnp.zeros((pool_slots, 1, latent_width(cfg)),
                            jnp.dtype(cfg.dtype))}


def init_latent_cache(cfg, batch: int, max_len: int):
    """Empty dense per-slot latent cache (the reference ``Server``)."""
    return {"kv": jnp.zeros((batch, max_len, 1, latent_width(cfg)),
                            jnp.dtype(cfg.dtype)),
            "pos": jnp.full((batch, max_len), attn.EMPTY_POS, jnp.int32)}
