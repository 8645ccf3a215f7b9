"""Model assembly: decoder LM, enc-dec (whisper), VLM-prefixed LM.

Layer stacks are grouped into *periods* (one cycle of ``cfg.block_pattern``)
and scanned with ``jax.lax.scan`` over stacked params -- HLO size and compile
time stay O(period) instead of O(layers), the standard MaxText approach.
Pattern tails that don't fill a period are unrolled, and so are the
``cfg.first_k_dense`` leading dense layers that run before the scan
(``params["lead"]["layer<i>"]``, DeepSeek-V3's dense layer 0): plain
``attn`` blocks whose SwiGLU has width ``cfg.dense_d_ff``.

The same period/scan machinery drives decode: caches are stacked trees with
a leading period axis and are threaded through the scan.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core import counting
from repro.core.einsum import fs_einsum
from repro.layers import basic
from repro.layers.param import init_tree, abstract_tree, count_params
from repro.models import blocks as blk

__all__ = ["LM", "build_model"]


def _period_split(cfg) -> Tuple[int, Tuple[str, ...], Tuple[str, ...]]:
    """n_scan periods of the full pattern + unrolled tail kinds, over the
    layers after the leading dense ones (:func:`_lead_cfg`)."""
    kinds = cfg.layer_kinds[cfg.first_k_dense:]
    plen = len(cfg.block_pattern)
    if not cfg.scan_layers:
        return 0, (), kinds
    n_scan = len(kinds) // plen
    tail = kinds[n_scan * plen:]
    return n_scan, cfg.block_pattern, tail


def _lead_cfg(cfg):
    """The config the leading dense layers run with: their SwiGLU width."""
    return dataclasses.replace(cfg, d_ff=cfg.dense_d_ff or cfg.d_ff)


def _final_norm(cfg, params, x):
    if cfg.norm == "layernorm":
        return basic.layernorm_apply(params["final_norm"], x)
    return basic.rmsnorm_apply(params["final_norm"], x, eps=cfg.norm_eps)


def _head_table(params):
    """The output table: the untied head's, else the tied embedding."""
    return params["head"]["table"] if "head" in params \
        else params["embed"]["table"]


@dataclasses.dataclass
class LM:
    cfg: Any

    # ------------------------------------------------------------- spec
    def spec(self):
        cfg = self.cfg
        n_scan, period, tail = _period_split(cfg)
        s: Dict[str, Any] = {
            "embed": basic.embed_spec(cfg.padded_vocab, cfg.d_model,
                                      jnp.dtype(cfg.dtype)),
            "final_norm": (basic.layernorm_spec(cfg.d_model)
                           if cfg.norm == "layernorm"
                           else basic.rmsnorm_spec(cfg.d_model)),
        }
        dec_kind = {"attn": "xdec"} if cfg.encoder_layers else {}
        if not cfg.tie_embeddings:
            s["head"] = basic.embed_spec(cfg.padded_vocab, cfg.d_model,
                                         jnp.dtype(cfg.dtype))
        if cfg.first_k_dense:
            s["lead"] = {f"layer{i}": blk.block_spec("attn", _lead_cfg(cfg))
                         for i in range(cfg.first_k_dense)}
        if n_scan:
            s["scan"] = {f"pos{i}": blk.block_spec(dec_kind.get(k, k), cfg, n_scan)
                         for i, k in enumerate(period)}
        if tail:
            s["tail"] = {f"layer{i}": blk.block_spec(dec_kind.get(k, k), cfg)
                         for i, k in enumerate(tail)}
        if cfg.encoder_layers:
            s["encoder"] = {
                "blocks": {"pos0": blk.block_spec("attn", cfg, cfg.encoder_layers)},
                "norm": (basic.layernorm_spec(cfg.d_model)
                         if cfg.norm == "layernorm"
                         else basic.rmsnorm_spec(cfg.d_model)),
            }
        return s

    def init(self, key):
        return init_tree(self.spec(), key)

    def abstract_params(self):
        return abstract_tree(self.spec())

    def n_params(self) -> int:
        return count_params(self.spec())

    def n_active_params(self) -> int:
        """Active params per token (MoE discounts inactive experts)."""
        cfg = self.cfg
        total = self.n_params()
        if not cfg.n_experts:
            return total
        # gate+up+down per expert; a held share keeps experts_held of them
        # and its tokens use, in expectation, topk * held / n_experts
        expert_p = 3 * cfg.d_model * cfg.d_ff
        held = cfg.experts_held or cfg.n_experts
        per_layer_inactive = (held - cfg.topk * held / cfg.n_experts) \
            * expert_p
        n_moe_layers = sum(1 for k in cfg.layer_kinds if k == "moe")
        return int(round(total - n_moe_layers * per_layer_inactive))

    # ------------------------------------------------------- embedding
    def _embed_in(self, params, batch):
        cfg = self.cfg
        x = self._embed_tokens(params, batch["tokens"])
        if cfg.prefix_tokens:
            x = jnp.concatenate([batch["patches"].astype(x.dtype), x], axis=1)
        return x

    def _embed_tokens(self, params, tokens):
        cfg = self.cfg
        x = basic.embed_apply(params["embed"], tokens)
        if cfg.embed_scale:
            x = x * (cfg.d_model ** 0.5)
        return x.astype(jnp.dtype(cfg.dtype))

    def _encode(self, params, batch, mode):
        """Whisper-style encoder over precomputed frame embeddings (stub
        frontend per spec): non-causal attention stack."""
        cfg = self.cfg
        x = batch["frames"].astype(jnp.dtype(cfg.dtype))
        S = x.shape[1]
        ctx = {"cfg": cfg, "mode": mode, "policy": cfg.contraction_policy,
               "positions": jnp.arange(S), "causal": False}

        def body(x, p):
            x, _, _ = blk.block_forward("attn", p, x, ctx)
            return x, None

        if cfg.remat != "none":
            body = jax.checkpoint(body)
        with counting.count_scale(cfg.encoder_layers):
            x, _ = jax.lax.scan(body, x, params["encoder"]["blocks"]["pos0"])
        if cfg.norm == "layernorm":
            x = basic.layernorm_apply(params["encoder"]["norm"], x)
        else:
            x = basic.rmsnorm_apply(params["encoder"]["norm"], x)
        return x

    # ----------------------------------------------------- full forward
    def forward(self, params, batch, *, collect_cache: bool = False):
        """Teacher-forced full-sequence pass -> (hidden, aux_loss, cache).

        ``collect_cache=True`` (prefill) also returns per-layer cache seeds.
        """
        cfg = self.cfg
        mode = cfg.matmul_mode
        n_scan, period, tail = _period_split(cfg)
        x = self._embed_in(params, batch)
        S = x.shape[1]
        positions = jnp.arange(S)
        ctx = {"cfg": cfg, "mode": mode, "policy": cfg.contraction_policy,
               "positions": positions, "causal": True}
        if cfg.encoder_layers:
            enc = self._encode(params, batch, mode)
            ctx["cross_x"] = enc
            ctx["cross_positions"] = jnp.arange(enc.shape[1])
        dec_kind = {"attn": "xdec"} if cfg.encoder_layers else {}
        aux_total = jnp.zeros((), jnp.float32)
        caches = {}

        lead_ctx = dict(ctx, cfg=_lead_cfg(cfg))
        for i in range(cfg.first_k_dense):
            x, c, _ = blk.block_forward("attn", params["lead"][f"layer{i}"],
                                        x, lead_ctx)
            if collect_cache:
                caches.setdefault("lead", {})[f"layer{i}"] = c

        if n_scan:
            def body(x, pslice):
                aux_p = jnp.zeros((), jnp.float32)
                cache_p = {}
                for i, k in enumerate(period):
                    kk = dec_kind.get(k, k)
                    x, c, aux = blk.block_forward(kk, pslice[f"pos{i}"], x, ctx)
                    aux_p = aux_p + aux
                    if collect_cache:
                        cache_p[f"pos{i}"] = c
                return x, (aux_p, cache_p)

            if cfg.remat == "dots":
                # save GEMM outputs, recompute elementwise: trades activation
                # memory for removing the full-forward recompute
                body = jax.checkpoint(
                    body, prevent_cse=False,
                    policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
            elif cfg.remat != "none":
                body = jax.checkpoint(body, prevent_cse=False)
            with counting.count_scale(n_scan):
                x, (auxs, cache_scan) = jax.lax.scan(
                    body, x, {k: params["scan"][k] for k in params["scan"]})
            aux_total = aux_total + jnp.sum(auxs)
            if collect_cache:
                caches["scan"] = cache_scan
        for i, k in enumerate(tail):
            kk = dec_kind.get(k, k)
            x, c, aux = blk.block_forward(kk, params["tail"][f"layer{i}"], x, ctx)
            aux_total = aux_total + aux
            if collect_cache:
                caches.setdefault("tail", {})[f"layer{i}"] = c

        x = _final_norm(cfg, params, x)
        if cfg.encoder_layers and collect_cache:
            caches["enc_out"] = ctx["cross_x"]
        return x, aux_total, caches

    # ------------------------------------------------------------ logits
    def logits(self, params, hidden):
        """Full logits (small models / tests only -- training uses the
        chunked fused loss in repro.train.loss).  A ``logits_prep`` entry
        (set by :meth:`prepare_params`) supplies the prepared vocab table
        -- the weight-stationary inference pattern."""
        cfg = self.cfg
        table = params.get("logits_prep")
        if table is None:
            table = _head_table(params).astype(jnp.float32)
        return fs_einsum("bsd,vd->bsv", hidden.astype(jnp.float32),
                         table, mode=cfg.matmul_mode,
                         policy=cfg.contraction_policy, site="logits")

    # --------------------------------------------- prepared weights (infer)
    def prepare_params(self, params, *, prepare_grads: bool = False):
        """Weight-stationary inference params (paper §4-§5).

        Returns a params tree where every dense/projection/expert weight
        is wrapped in a :class:`repro.core.prepared.PreparedOperand`
        (prepared ONCE: widened, corrections precomputed, tile-padded) and
        a ``logits_prep`` entry carries the transposed vocab table, so
        repeated forwards/decodes amortize the constant-operand work --
        measurable under eager/interpret execution, free under jit
        caching.  INFERENCE pattern: the prepared leaves are derived
        values, not trainable params.

        Layers under the ``lax.scan`` stack keep raw weights (scan slices
        its operands along the period axis, which the prepared padded
        layout does not support) -- use ``scan_layers=False`` configs to
        prepare the whole stack.  Recurrent-mix weights also stay raw
        (their specs transpose per step).

        ``prepare_grads``: also carry each 2D prep's opposite-layout form
        (``PreparedOperand.grad``), which the fs_einsum custom VJP
        consumes for dL/dx -- for fine-tune-style loops that differentiate
        through prepared (frozen) weights without re-preparing per trace.
        """
        from repro.core.prepared import prepare_operand
        cfg = self.cfg
        hd = cfg.resolved_head_dim
        H, KV = cfg.n_heads, cfg.n_kv_heads
        pg = prepare_grads

        def prep_dense(p, site):
            w = p["w"]
            if w.ndim != 2:
                return p                      # stacked (scan) leaf: keep raw
            q = dict(p)
            q["w"] = prepare_operand(w, site=site, prepare_grads=pg)
            return q

        def prep_mla(p):
            if p["wq"]["w"].ndim != 3:
                return p                      # stacked: keep the block raw
            dq = p["wq"]["w"].shape[-1]
            q = dict(p)
            sites = {"wq": "attn_qkv", "wkv_a": "attn_qkv",
                     "wk_b": "mla_absorb", "wv_b": "mla_unabsorb",
                     "wo": "attn_out"}
            for nm, site in sites.items():
                w = p[nm]["w"]
                if nm == "wq":
                    w = w.reshape(w.shape[0], H * dq)
                elif nm == "wo":
                    w = w.reshape(-1, w.shape[-1])
                q[nm] = dict(p[nm], w=prepare_operand(
                    w, site=site, prepare_grads=pg and w.ndim == 2))
            return q

        def prep_attn(p):
            if "wkv_a" in p:
                return prep_mla(p)
            q = dict(p)
            for nm, nh in (("wq", H), ("wk", KV), ("wv", KV)):
                w = q[nm]["w"]
                if w.ndim != 3:
                    return p                  # stacked: keep the block raw
                sub = dict(q[nm])
                sub["w"] = prepare_operand(w.reshape(w.shape[0], nh * hd),
                                           site="attn_qkv", prepare_grads=pg)
                q[nm] = sub
            wo = q["wo"]["w"]
            sub = dict(q["wo"])
            sub["w"] = prepare_operand(wo.reshape(H * hd, wo.shape[-1]),
                                       site="attn_out", prepare_grads=pg)
            q["wo"] = sub
            return q

        def prep_moe(p):
            q = dict(p)
            q["router"] = prep_dense(p["router"], "moe_router")
            for nm in ("w_gate", "w_up", "w_down"):
                w = p[nm]["w"]
                if w.ndim != 3:
                    return p
                sub = dict(p[nm])
                sub["w"] = prepare_operand(w, site="moe_expert")
                q[nm] = sub
            return q

        def prep_block(p):
            q = dict(p)
            for key in ("attn", "xattn"):
                if key in q:
                    q[key] = prep_attn(q[key])
            if "ffn" in q:
                if "router" in q["ffn"]:
                    q["ffn"] = prep_moe(q["ffn"])
                else:
                    q["ffn"] = {k: (prep_dense(v, "ffn") if k.startswith("w")
                                    else v) for k, v in q["ffn"].items()}
            return q

        new = dict(params)
        for stack in ("lead", "tail"):
            if stack in new:
                new[stack] = {k: prep_block(v)
                              for k, v in new[stack].items()}
        table = _head_table(params)
        new["logits_prep"] = prepare_operand(table.astype(jnp.float32),
                                             transpose=True, site="logits",
                                             prepare_grads=pg)
        return new

    # ------------------------------------------------------------- cache
    def init_cache(self, batch_size: int, cache_len: int):
        cfg = self.cfg
        n_scan, period, tail = _period_split(cfg)
        dec_kind = {"attn": "xdec"} if cfg.encoder_layers else {}
        enc_len = cfg.encoder_seq
        cache: Dict[str, Any] = {}
        if cfg.first_k_dense:
            cache["lead"] = {f"layer{i}": blk.block_init_cache(
                "attn", cfg, batch_size, cache_len, enc_len)
                for i in range(cfg.first_k_dense)}
        if n_scan:
            def stack(kind):
                one = blk.block_init_cache(kind, cfg, batch_size, cache_len, enc_len)
                return jax.tree.map(
                    lambda a: jnp.broadcast_to(a, (n_scan,) + a.shape).copy(), one)
            cache["scan"] = {f"pos{i}": stack(dec_kind.get(k, k))
                             for i, k in enumerate(period)}
        if tail:
            cache["tail"] = {f"layer{i}": blk.block_init_cache(
                dec_kind.get(k, k), cfg, batch_size, cache_len, enc_len)
                for i, k in enumerate(tail)}
        return cache

    # ------------------------------------------------------- paged decode
    def init_paged_cache(self, pool_slots: int):
        """Per-layer paged pools (the serving engine's cache): every
        attention layer gets a ``(pool_slots, KV, hd)`` k/v pool, or with
        latent attention one ``(pool_slots, 1, kv_lora_rank +
        qk_rope_head_dim)`` latent pool, shared by all sequences; block
        tables (held by the engine) map each sequence's logical positions
        onto pool slots.  A model with held experts also carries
        ``moe_counters``: the (``moe.N_STATS``,) int32 running sums of its
        layers' pick statistics, accumulated by every step program on the
        device (:meth:`decode_paged`).  Raises for archs with non-KV
        decode state (recurrent / encoder-decoder) -- those serve through
        the dense reference ``Server``."""
        cfg = self.cfg
        if cfg.encoder_layers or cfg.prefix_tokens:
            raise ValueError(
                "paged serving supports plain decoder LMs; encoder-decoder "
                "and prefix-token archs use the dense reference Server")
        n_scan, period, tail = _period_split(cfg)
        cache: Dict[str, Any] = {}
        if cfg.first_k_dense:
            cache["lead"] = {
                f"layer{i}": blk.block_init_paged_cache("attn", cfg,
                                                        pool_slots)
                for i in range(cfg.first_k_dense)}
        if n_scan:
            def stack(kind):
                one = blk.block_init_paged_cache(kind, cfg, pool_slots)
                return jax.tree.map(
                    lambda a: jnp.broadcast_to(a, (n_scan,) + a.shape).copy(),
                    one)
            cache["scan"] = {f"pos{i}": stack(k)
                             for i, k in enumerate(period)}
        if tail:
            cache["tail"] = {
                f"layer{i}": blk.block_init_paged_cache(k, cfg, pool_slots)
                for i, k in enumerate(tail)}
        if cfg.experts_held:
            from repro.models import moe as moe_mod
            cache["moe_counters"] = jnp.zeros((moe_mod.N_STATS,), jnp.int32)
        return cache

    def _decode_layers(self, params, cache, x, ctx, dec_kind):
        """Run every layer's cached step: the leading dense layers, the
        scanned periods, the tail.  Returns (x, new cache, the layers'
        summed MoE statistics)."""
        cfg = self.cfg
        n_scan, period, tail = _period_split(cfg)
        cache = dict(cache)
        stats = []
        lead_ctx = dict(ctx, cfg=_lead_cfg(cfg))
        for i in range(cfg.first_k_dense):
            x, nc, st = blk.block_decode("attn", params["lead"][f"layer{i}"],
                                         x, cache["lead"][f"layer{i}"],
                                         lead_ctx)
            cache["lead"] = dict(cache["lead"], **{f"layer{i}": nc})
            stats.append(st)
        if n_scan:
            def body(x, sl):
                pslice, cslice = sl
                new_c, st = {}, 0
                for i, k in enumerate(period):
                    kk = dec_kind.get(k, k)
                    x, nc, s = blk.block_decode(kk, pslice[f"pos{i}"], x,
                                                cslice[f"pos{i}"], ctx)
                    new_c[f"pos{i}"] = nc
                    st = st + s
                return x, (new_c, st)

            with counting.count_scale(n_scan):
                x, (new_scan, st) = jax.lax.scan(
                    body, x, (params["scan"], cache["scan"]))
            cache["scan"] = new_scan
            stats.append(jnp.sum(st, axis=0))
        for i, k in enumerate(tail):
            kk = dec_kind.get(k, k)
            x, nc, st = blk.block_decode(kk, params["tail"][f"layer{i}"], x,
                                         cache["tail"][f"layer{i}"], ctx)
            cache["tail"] = dict(cache["tail"], **{f"layer{i}": nc})
            stats.append(st)
        return x, cache, sum(stats)

    def decode_paged(self, params, cache, tokens, positions, tables,
                     pos_pool, *, block_size: int):
        """One multi-token step against the paged cache.

        ``tokens``/``positions``: (B, S) int32, ``positions`` absolute with
        ``-1`` marking padding (padded tokens write to the null block and
        never attend).  S = 1 is batched continuous decode; S > 1 is a
        chunked-prefill chunk.  ``tables``: (B, nb) block tables;
        ``pos_pool``: (P,) shared physical-slot position ledger, scattered
        ONCE here (not per layer -- the position layout is identical across
        layers).  Returns (hidden (B, S, D), new_cache, new_pos_pool);
        logits are the caller's call (decode wants every step, chunked
        prefill only the last chunk).  A cache with ``moe_counters`` gets
        this step's MoE statistics added to them, on the device.
        """
        from repro.models import attention as attn_mod
        cfg = self.cfg
        mode = cfg.matmul_mode
        phys = attn_mod.paged_slots(tables, positions, block_size)
        pos_pool = pos_pool.at[phys.reshape(-1)].set(
            jnp.where(positions >= 0, positions,
                      attn_mod.EMPTY_POS).reshape(-1).astype(pos_pool.dtype))
        x = self._embed_tokens(params, jnp.maximum(tokens, 0))
        ctx = {"cfg": cfg, "mode": mode, "policy": cfg.contraction_policy,
               "pos": positions,
               "paged": {"tables": tables, "pos_pool": pos_pool,
                         "phys": phys, "block_size": block_size}}
        layers = {k: v for k, v in cache.items() if k != "moe_counters"}
        x, new, stats = self._decode_layers(params, layers, x, ctx, {})
        if "moe_counters" in cache:
            new["moe_counters"] = cache["moe_counters"] + stats
        return _final_norm(cfg, params, x), new, pos_pool

    # ------------------------------------------------------------ decode
    def decode_step(self, params, cache, tokens, pos):
        """One decode step.  tokens: (B, 1) int32; pos: (B,) absolute.
        Returns (logits (B, V), new_cache)."""
        cfg = self.cfg
        mode = cfg.matmul_mode
        dec_kind = {"attn": "xdec"} if cfg.encoder_layers else {}
        x = self._embed_tokens(params, tokens)
        ctx = {"cfg": cfg, "mode": mode, "policy": cfg.contraction_policy,
               "pos": pos}
        x, cache, _ = self._decode_layers(params, cache, x, ctx, dec_kind)
        x = _final_norm(cfg, params, x)
        logits = self.logits(params, x)[:, 0]
        return logits, cache

    # ----------------------------------------------------------- prefill
    def prefill(self, params, batch, cache_len: int):
        """Process a prompt, return (last_hidden, decode-ready cache)."""
        hidden, _, seeds = self.forward(params, batch, collect_cache=True)
        B = hidden.shape[0]
        cache = self.init_cache(B, cache_len)

        def fill(dst, seed):
            if not (isinstance(seed, dict) and "pos" in dst):
                return seed                                 # recurrent state
            # attention seed: every positional entry (k/v, or the latent
            # rows) goes in at its token's slot
            keys = [k for k in seed if k in dst and k not in ("xk", "xv")]
            S = seed[keys[0]].shape[1]
            T = dst[keys[0]].shape[1]
            out = dict(dst)
            if S >= T:
                # ring roll-in: keep the last T entries at slot pos % T
                ps = jnp.arange(S - T, S)
                idx = ps % T
                for k in keys:
                    out[k] = dst[k].at[:, idx].set(seed[k][:, -T:])
                out["pos"] = dst["pos"].at[:, idx].set(ps[None, :])
            else:
                for k in keys:
                    out[k] = dst[k].at[:, :S].set(seed[k])
                out["pos"] = dst["pos"].at[:, :S].set(jnp.arange(S)[None, :])
            if "xk" in dst:
                out["xk"], out["xv"] = seed["xk"], seed["xv"]
            return out

        new_cache: Dict[str, Any] = {}
        if "scan" in cache:
            new_cache["scan"] = {}
            for key in cache["scan"]:
                dst = cache["scan"][key]
                seed = seeds["scan"][key]
                if isinstance(seed, dict) and "pos" in dst:
                    # both stacked on leading period axis
                    new_cache["scan"][key] = jax.vmap(fill)(dst, seed)
                else:
                    new_cache["scan"][key] = seed
        for stack in ("lead", "tail"):
            if stack in cache:
                new_cache[stack] = {
                    key: fill(cache[stack][key], seeds[stack][key])
                    for key in cache[stack]}
        return hidden, new_cache


def build_model(cfg) -> LM:
    return LM(cfg)
