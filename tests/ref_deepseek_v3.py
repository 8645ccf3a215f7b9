"""Plain float32 reference of the DeepSeek-V3 layout (Moonlight-16B-A3B).

Straight ``jax.numpy`` from the published description, every matrix
product at ``jax.default_matmul_precision("highest")``: no kernels, no
cache, no batching, one sequence at a time.  It reads the program's
parameter tree (``repro.models.lm.LM.init`` / ``bench.weights.init``
layout) and its ``ModelConfig``:

- input embeddings not scaled; RMSNorm with weights stored as ``w - 1``;
- latent attention without query LoRA, textbook form: ``q = x W_q`` split
  per head into nope and rope columns; ``[c, p] = x W_kv_a``; ``c`` normed;
  ``k_nope_h = c W_UK_h``, ``v_h = c W_UV_h``; RoPE (rotate-half) on
  ``q_pe`` and on one ``k_pe = RoPE(p)`` shared by all heads; scores
  scaled by ``(nope + rope) ** -0.5``, causal softmax, ``W_o``;
- the leading ``first_k_dense`` layers: SwiGLU of ``dense_d_ff``;
- MoE layers: sigmoid router over all ``n_experts`` with the choice-only
  bias, top-k, normalised, scaled by ``routed_scale``; the experts
  ``[held_lo, held_lo + experts_held)`` computed for the tokens that picked
  them; plus the shared SwiGLU;
- an untied head.

Departure noted: RoPE pairs dims rotate-half style (first half with
second half); DeepSeek's published code interleaves pairs.  With random
weights the two are the same model up to a fixed permutation of the rope
columns of ``W_q`` and ``W_kv_a``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["forward", "moe_layer", "logits"]


def _f32(t):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), t)


def _norm(x, scale, eps):
    var = jnp.mean(x * x, -1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + scale)


def _rope(x, pos, theta):
    """x (S, n, d): rotate-half, inv_freq = theta ** (-2i / d)."""
    half = x.shape[-1] // 2
    inv = theta ** (-(np.arange(half) * 2.0 / x.shape[-1]))
    ang = pos[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(p, h):
    return (jax.nn.silu(h @ p["w_gate"]["w"]) * (h @ p["w_up"]["w"])) \
        @ p["w_down"]["w"]


def _attention(cfg, p, h):
    S = h.shape[0]
    r, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    pos = jnp.arange(S, dtype=jnp.float32)
    q = jnp.einsum("sd,dhe->she", h, p["wq"]["w"])
    q_nope, q_pe = q[..., :dn], _rope(q[..., dn:], pos, cfg.rope_theta)
    ckv = h @ p["wkv_a"]["w"]
    c = _norm(ckv[:, :r], p["kv_norm"]["scale"], cfg.norm_eps)
    k_pe = _rope(ckv[:, None, r:], pos, cfg.rope_theta)[:, 0]
    k_nope = jnp.einsum("sc,hnc->shn", c, p["wk_b"]["w"])
    v = jnp.einsum("sc,hcv->shv", c, p["wv_b"]["w"])
    s = jnp.einsum("qhn,khn->hqk", q_nope, k_nope) \
        + jnp.einsum("qhr,kr->hqk", q_pe, k_pe)
    s = s * (dn + cfg.qk_rope_head_dim) ** -0.5
    causal = np.tril(np.ones((S, S), bool))
    w = jax.nn.softmax(jnp.where(causal[None], s, -1e30), axis=-1)
    o = jnp.einsum("hqk,khv->qhv", w, v)
    return jnp.einsum("qhv,hvd->qd", o, p["wo"]["w"])


def moe_layer(cfg, p, h, *, held_lo: int = 0, shared: bool = True):
    """A MoE layer's output for tokens h (S, d), holding experts
    ``[held_lo, held_lo + H)`` (``H`` = the leading dim of the expert
    weights): picks on other experts add nothing; the shared experts are
    added when ``shared``."""
    with jax.default_matmul_precision("highest"):
        p, h = _f32(p), jnp.asarray(h, jnp.float32)
        scores = jax.nn.sigmoid(h @ p["router"]["w"])
        _, idx = jax.lax.top_k(scores + p["router"]["bias"], cfg.topk)
        gate = jnp.take_along_axis(scores, idx, axis=-1)
        if cfg.norm_topk:
            gate = gate / (gate.sum(-1, keepdims=True) + 1e-20)
        gate = gate * cfg.routed_scale
        out = jnp.zeros_like(h)
        for e in range(p["w_gate"]["w"].shape[0]):
            wt = jnp.sum(jnp.where(idx == held_lo + e, gate, 0.0), axis=-1)
            y = (jax.nn.silu(h @ p["w_gate"]["w"][e])
                 * (h @ p["w_up"]["w"][e])) @ p["w_down"]["w"][e]
            out = out + wt[:, None] * y
        if shared and "shared" in p:
            out = out + _swiglu(p["shared"], h)
        return out


def _layer(cfg, p, x, moe: bool):
    x = x + _attention(cfg, p["attn"], _norm(x, p["ln1"]["scale"],
                                             cfg.norm_eps))
    h = _norm(x, p["ln2"]["scale"], cfg.norm_eps)
    return x + (moe_layer(cfg, p["ffn"], h) if moe else _swiglu(p["ffn"], h))


def forward(cfg, params, tokens):
    """Final hidden states (S, d) of one sequence of ids (S,)."""
    with jax.default_matmul_precision("highest"):
        params = _f32(params)
        x = params["embed"]["table"][jnp.asarray(tokens)]
        if cfg.embed_scale:
            x = x * cfg.d_model ** 0.5
        for i in range(cfg.first_k_dense):
            x = _layer(cfg, params["lead"][f"layer{i}"], x, moe=False)
        stack = params["scan"]["pos0"]
        n = jax.tree.leaves(stack)[0].shape[0]
        for l in range(n):
            x = _layer(cfg, jax.tree.map(lambda a: a[l], stack), x, moe=True)
        return _norm(x, params["final_norm"]["scale"], cfg.norm_eps)


def logits(cfg, params, tokens):
    """Logits (S, vocab) of one sequence."""
    with jax.default_matmul_precision("highest"):
        h = forward(cfg, params, tokens)
        table = params["head"]["table"] if "head" in params \
            else params["embed"]["table"]
        return (h @ jnp.asarray(table, jnp.float32).T)[:, :cfg.vocab]
