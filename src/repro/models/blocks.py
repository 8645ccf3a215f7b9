"""Uniform block interface over all temporal-mix kinds.

Every layer is ``kind`` in {attn, moe, mlstm, slstm, rglru, lattn, xdec}:
  - spec(kind)         -> param spec subtree (optionally stacked for scan)
  - forward(kind)      -> full-sequence pass, returns (x, cache_seed, aux)
  - decode(kind)       -> single-token pass against a cache
  - init_cache(kind)   -> empty decode cache

``xdec`` is the whisper-style decoder block (self-attn + cross-attn + ffn).
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.layers import basic
from repro.models import attention as attn
from repro.models import ffn as ffn_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import xlstm as xlstm_mod

__all__ = ["block_spec", "block_forward", "block_decode", "block_init_cache"]


def _norm_spec(cfg, stack):
    if cfg.norm == "layernorm":
        return basic.layernorm_spec(cfg.d_model, stack)
    return basic.rmsnorm_spec(cfg.d_model, stack)


def _norm_apply(cfg, p, x):
    if cfg.norm == "layernorm":
        return basic.layernorm_apply(p, x)
    return basic.rmsnorm_apply(p, x, eps=cfg.norm_eps)


def block_spec(kind: str, cfg, stack: int = 0) -> Dict[str, Any]:
    s: Dict[str, Any] = {"ln1": _norm_spec(cfg, stack)}
    if kind in ("attn", "moe", "lattn", "xdec"):
        s["attn"] = (mla_mod.mla_spec(cfg, stack) if cfg.is_mla
                     else attn.attn_spec(cfg, stack))
        if kind == "xdec":
            s["lnx"] = _norm_spec(cfg, stack)
            s["xattn"] = attn.attn_spec(cfg, stack)
        if cfg.d_ff:
            s["ln2"] = _norm_spec(cfg, stack)
            s["ffn"] = (moe_mod.moe_spec(cfg, stack) if kind == "moe"
                        else ffn_mod.ffn_spec(cfg, stack))
    elif kind == "mlstm":
        s["mix"] = xlstm_mod.mlstm_spec(cfg, stack)
    elif kind == "slstm":
        s["mix"] = xlstm_mod.slstm_spec(cfg, stack)
    elif kind == "rglru":
        s["mix"] = rglru_mod.rglru_spec(cfg, stack)
        if cfg.d_ff:
            s["ln2"] = _norm_spec(cfg, stack)
            s["ffn"] = ffn_mod.ffn_spec(cfg, stack)
    else:
        raise ValueError(f"unknown block kind {kind!r}")
    return s


def _window_for(kind, cfg):
    if kind == "lattn":
        return cfg.local_window
    return cfg.window


def _apply_moe(p, x, cfg, mode, policy=None, valid=None):
    """Dispatch MoE locally or through shard_map under a mesh (see moe.py).

    A model that names ``experts_held`` runs the dropless held-expert
    layer (``moe.moe_apply_held``; ``valid`` (B, S) masks padding tokens
    out of its picks) on one device.  Returns (out, aux, stats), stats the
    held layer's (``moe.N_STATS``,) counts, zeros otherwise."""
    from repro.distributed import context as dctx
    B, S, D = x.shape
    xt = x.reshape(B * S, D)
    mesh = dctx.current_mesh()
    stats = jnp.zeros((moe_mod.N_STATS,), jnp.int32)
    if cfg.experts_held:
        if mesh is not None:
            raise NotImplementedError(
                "the held-expert MoE layer runs on one device: it holds "
                "its share of the experts and routes its own tokens")
        out, aux, stats = moe_mod.moe_apply_held(
            p, xt, cfg=cfg, mode=mode, policy=policy,
            valid=None if valid is None else valid.reshape(B * S))
        return out.reshape(B, S, D), aux, stats
    if mesh is None:
        out, aux = moe_mod.moe_apply_local(p, xt, cfg=cfg, mode=mode,
                                           policy=policy)
    else:
        import numpy as np
        from jax.sharding import PartitionSpec as P
        data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        dsize = int(np.prod([mesh.shape[a] for a in data_axes])) if data_axes else 1
        if (B * S) % max(1, dsize) != 0:
            data_axes = ()          # tiny decode batches: replicate tokens
        model_ax = "model" if "model" in mesh.axis_names else None

        def body(pp, xx):
            out, aux = moe_mod.moe_apply_local(
                pp, xx, cfg=cfg, mode=mode, policy=policy,
                psum_axes=(model_ax,) if model_ax else None)
            if data_axes:
                aux = jax.lax.pmean(aux, data_axes)
            return out, aux

        pspec = {
            "router": {"w": P(None, None)},
            "w_gate": {"w": P(None, None, model_ax)},
            "w_up": {"w": P(None, None, model_ax)},
            "w_down": {"w": P(None, model_ax, None)},
        }
        tok_spec = P(data_axes, None) if data_axes else P(None, None)
        out, aux = jax.shard_map(
            body, mesh=mesh,
            in_specs=(pspec, tok_spec),
            out_specs=(tok_spec, P()),
            check_vma=False)(p, xt)
    return out.reshape(B, S, D), aux, stats


def block_forward(kind: str, p, x, ctx) -> Tuple[jnp.ndarray, Any, jnp.ndarray]:
    """Full-sequence block pass.

    ctx: dict(positions, mode, policy, cross_x, cross_positions, cfg, causal).
    Returns (x_out, cache_seed, aux_loss).
    """
    cfg = ctx["cfg"]
    mode = ctx["mode"]
    policy = ctx.get("policy")
    aux = jnp.zeros((), jnp.float32)
    h = _norm_apply(cfg, p["ln1"], x)
    if kind in ("attn", "moe", "lattn", "xdec"):
        if cfg.is_mla:
            out, cache = mla_mod.mla_forward(
                p["attn"], h, cfg=cfg, positions=ctx["positions"],
                causal=ctx.get("causal", True),
                window=_window_for(kind, cfg), mode=mode, policy=policy)
        else:
            out, kv = attn.attn_forward(
                p["attn"], h, cfg=cfg, positions=ctx["positions"],
                causal=ctx.get("causal", True),
                window=_window_for(kind, cfg), mode=mode, policy=policy)
            cache = {"k": kv[0], "v": kv[1]}
        x = x + out
        if kind == "xdec":
            hx = _norm_apply(cfg, p["lnx"], x)
            outx, xkv = attn.attn_forward(
                p["xattn"], hx, cfg=cfg, positions=ctx["positions"],
                cross_x=ctx["cross_x"], cross_positions=ctx["cross_positions"],
                mode=mode, policy=policy)
            x = x + outx
            cache["xk"], cache["xv"] = xkv
        if cfg.d_ff:
            h2 = _norm_apply(cfg, p["ln2"], x)
            if kind == "moe":
                out2, aux, _ = _apply_moe(p["ffn"], h2, cfg, mode, policy)
            else:
                out2 = ffn_mod.ffn_apply(p["ffn"], h2, cfg=cfg, mode=mode,
                                         policy=policy)
            x = x + out2
        return x, cache, aux
    if kind == "mlstm":
        out, state = xlstm_mod.mlstm_forward(p["mix"], h, cfg=cfg, mode=mode,
                                             policy=policy)
        return x + out, state, aux
    if kind == "slstm":
        out, state = xlstm_mod.slstm_forward(p["mix"], h, cfg=cfg, mode=mode,
                                             policy=policy)
        return x + out, state, aux
    if kind == "rglru":
        out, state = rglru_mod.rglru_forward(p["mix"], h, cfg=cfg, mode=mode,
                                             policy=policy)
        x = x + out
        if cfg.d_ff:
            h2 = _norm_apply(cfg, p["ln2"], x)
            x = x + ffn_mod.ffn_apply(p["ffn"], h2, cfg=cfg, mode=mode,
                                      policy=policy)
        return x, state, aux
    raise ValueError(kind)


def block_decode(kind: str, p, x, cache, ctx
                 ) -> Tuple[jnp.ndarray, Any, jnp.ndarray]:
    """Single-token decode step.  x: (B, 1, D).

    When ``ctx["paged"]`` is set (the serving engine), the attention cache
    is the shared paged pool, ``x`` may be a multi-token chunk (B, S, D)
    and ``ctx["pos"]`` is (B, S) -- see ``attention._attn_paged_step``.
    Returns (x, new_cache, stats): ``stats`` are a held-expert MoE
    layer's (``moe.N_STATS``,) counts, zeros for every other layer.
    """
    cfg = ctx["cfg"]
    mode = ctx["mode"]
    policy = ctx.get("policy")
    pos = ctx["pos"]                       # (B,) absolute position
    paged = ctx.get("paged")
    stats = jnp.zeros((moe_mod.N_STATS,), jnp.int32)
    h = _norm_apply(cfg, p["ln1"], x)
    if kind in ("attn", "moe", "lattn", "xdec"):
        # the layer's own cache: every entry but the cross-attention ones
        own = {k: v for k, v in cache.items() if k not in ("xk", "xv")}
        decode = mla_mod.mla_decode if cfg.is_mla else attn.attn_decode
        out, new_kv = decode(
            p["attn"], h, own, pos, cfg=cfg, window=_window_for(kind, cfg),
            mode=mode, policy=policy, paged=paged)
        x = x + out
        new_cache = dict(cache)
        new_cache.update(new_kv)
        if kind == "xdec":
            hx = _norm_apply(cfg, p["lnx"], x)
            outx, _ = attn.attn_decode(
                p["xattn"], hx, None, pos, cfg=cfg,
                cross_cache={"k": cache["xk"], "v": cache["xv"]}, mode=mode,
                policy=policy)
            x = x + outx
        if cfg.d_ff:
            h2 = _norm_apply(cfg, p["ln2"], x)
            if kind == "moe":
                out2, _, stats = _apply_moe(
                    p["ffn"], h2, cfg, mode, policy,
                    valid=None if paged is None else pos >= 0)
            else:
                out2 = ffn_mod.ffn_apply(p["ffn"], h2, cfg=cfg, mode=mode,
                                         policy=policy)
            x = x + out2
        return x, new_cache, stats
    if kind == "mlstm":
        out, state = xlstm_mod.mlstm_decode(p["mix"], h, cache, cfg=cfg,
                                            mode=mode, policy=policy)
        return x + out, state, stats
    if kind == "slstm":
        out, state = xlstm_mod.slstm_decode(p["mix"], h, cache, cfg=cfg,
                                            mode=mode, policy=policy)
        return x + out, state, stats
    if kind == "rglru":
        out, state = rglru_mod.rglru_decode(p["mix"], h, cache, cfg=cfg,
                                            mode=mode, policy=policy)
        x = x + out
        if cfg.d_ff:
            h2 = _norm_apply(cfg, p["ln2"], x)
            x = x + ffn_mod.ffn_apply(p["ffn"], h2, cfg=cfg, mode=mode,
                                      policy=policy)
        return x, state, stats
    raise ValueError(kind)


#: Block kinds whose decode cache is a KV dict -- the kinds the paged
#: serving engine supports (recurrent state and cross-attention caches are
#: per-slot, not positional, so paging does not apply to them).
PAGEABLE_KINDS = ("attn", "moe", "lattn")


def block_init_paged_cache(kind: str, cfg, pool_slots: int):
    """Empty paged pool for one layer: separate K and V pools
    (``attn.init_paged_kv_cache``), or with latent attention one latent
    pool read as both (``mla.init_paged_latent_cache``)."""
    if kind not in PAGEABLE_KINDS:
        raise ValueError(
            f"block kind {kind!r} has no paged decode cache; the paged "
            f"serving engine supports {PAGEABLE_KINDS} (use the dense "
            f"reference Server for recurrent / encoder-decoder archs)")
    if cfg.is_mla:
        return mla_mod.init_paged_latent_cache(cfg, pool_slots)
    return attn.init_paged_kv_cache(cfg, pool_slots)


def block_init_cache(kind: str, cfg, batch: int, cache_len: int,
                     enc_len: int = 0):
    if kind in ("attn", "moe", "lattn") and cfg.is_mla:
        return mla_mod.init_latent_cache(cfg, batch, cache_len)
    if kind in ("attn", "moe", "lattn", "xdec"):
        c = attn.init_kv_cache(cfg, batch, cache_len, _window_for(kind, cfg))
        if kind == "xdec":
            hd = cfg.resolved_head_dim
            dt = jnp.dtype(cfg.dtype)
            c["xk"] = jnp.zeros((batch, enc_len, cfg.n_kv_heads, hd), dt)
            c["xv"] = jnp.zeros((batch, enc_len, cfg.n_kv_heads, hd), dt)
        return c
    if kind == "mlstm":
        return xlstm_mod.mlstm_init_state(cfg, batch)
    if kind == "slstm":
        return xlstm_mod.slstm_init_state(cfg, batch)
    if kind == "rglru":
        return rglru_mod.rglru_init_state(cfg, batch)
    raise ValueError(kind)
