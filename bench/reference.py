"""Plain float32 reference of the dense decoder LMs the benchmark runs.

Straight ``jax.numpy`` from the published descriptions, with no kernel,
cache, batching or square arithmetic, and every matrix product at
``Precision.HIGHEST``:

- h2o-danube3 (Llama layout): RMSNorm, rotary attention with grouped K/V
  heads, SwiGLU feed-forward, no biases;
- StarCoder2: LayerNorm with bias, rotary attention with grouped K/V heads
  and biases, tanh-GELU feed-forward with biases, sliding window.

Both use rotate-half RoPE and scale scores by ``1/sqrt(head_dim)``.  The
input embedding is ``sqrt(d_model)`` times the output table (the program
ties the two and scales its input; the reference keeps them as two
matrices, which the published untied layout allows).  Norm epsilons are
the published 1e-5.

Weights come from :mod:`bench.weights`, never from the program.  An
:class:`Arith` says how the reference computes: ``mm`` is the one matrix
product every contraction goes through and ``store`` rounds what is kept
between operations (weights, the residual stream after each layer).
:data:`F32` is the reference; :data:`FP8` is its low-precision control,
float8 (e4m3, one scale per tensor) wherever the program keeps bf16.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict

import jax
import jax.numpy as jnp

__all__ = ["Arith", "F32", "FP8", "mm_f32", "mm_fp8", "stored", "embed",
           "block", "head", "forward"]

EPS = 1e-5
NEG = -1e30


def mm_f32(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _fp8(x):
    """Round to float8_e4m3fn with one scale per tensor (absmax -> 448)."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm_fp8(spec: str, a, b):
    """Both operands rounded to fp8 before an otherwise exact product."""
    return mm_f32(spec, _fp8(a), _fp8(b))


@dataclasses.dataclass(frozen=True)
class Arith:
    mm: Callable
    store: Callable


F32 = Arith(mm_f32, lambda x: x)
FP8 = Arith(mm_fp8, _fp8)

_NORMS = ("ln1", "ln2", "final_norm")


def stored(ar: Arith, leaves: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """Weights as the arithmetic keeps them: every leaf the program keeps
    in bf16 goes through ``store``; norm parameters stay float32, as the
    program keeps them."""
    return {k: v if k.split("/")[0] in _NORMS else ar.store(v)
            for k, v in leaves.items()}


def _norm(cfg, x, p, name):
    if cfg["norm"] == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + EPS) * p[f"{name}/scale"] \
            + p[f"{name}/bias"]
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x / jnp.sqrt(var + EPS) * (1.0 + p[f"{name}/scale"])


def _rope(x, pos, theta):
    """x (S, n, hd); rotate-half with inv_freq = theta^(-2i/hd)."""
    half = x.shape[-1] // 2
    inv = theta ** (-(jnp.arange(half, dtype=jnp.float32) * 2.0
                      / x.shape[-1]))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def embed(cfg, top: Dict[str, jax.Array], tokens):
    """(S,) ids -> (S, d) input embeddings."""
    return top["embed/table"][tokens] * math.sqrt(cfg["d_model"])


def block(cfg, p: Dict[str, jax.Array], x, ar: Arith = F32):
    """One decoder layer over a whole causal sequence x (S, d)."""
    mm = ar.mm
    p = stored(ar, p)
    S = x.shape[0]
    H, KV, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    pos = jnp.arange(S)
    h = _norm(cfg, x, p, "ln1")

    def proj(name):
        y = mm("sd,dnh->snh", h, p[f"attn/{name}/w"])
        if f"attn/{name}/b" in p:
            y = y + p[f"attn/{name}/b"]
        return y

    q = _rope(proj("wq"), pos, cfg["rope_theta"])
    k = _rope(proj("wk"), pos, cfg["rope_theta"])
    v = proj("wv")
    k = jnp.repeat(k, H // KV, axis=1)          # head h reads K/V h // G
    v = jnp.repeat(v, H // KV, axis=1)
    s = mm("qnh,knh->nqk", q, k) / math.sqrt(hd)
    allowed = pos[None, :] <= pos[:, None]
    if cfg.get("window"):
        allowed &= (pos[:, None] - pos[None, :]) < cfg["window"]
    s = jnp.where(allowed[None], s, NEG)
    w = jax.nn.softmax(s, axis=-1)
    o = mm("nqk,knh->qnh", w, v)
    o = mm("qnh,nhd->qd", o, p["attn/wo/w"])
    if "attn/wo/b" in p:
        o = o + p["attn/wo/b"]
    x = x + o

    h = _norm(cfg, x, p, "ln2")

    def dense(name, y):
        y = mm("sd,df->sf", y, p[f"ffn/{name}/w"])
        if f"ffn/{name}/b" in p:
            y = y + p[f"ffn/{name}/b"]
        return y

    if cfg["activation"] == "swiglu":
        u = jax.nn.silu(dense("w_gate", h)) * dense("w_up", h)
    elif cfg["activation"] == "gelu":
        u = _gelu_tanh(dense("w_up", h))
    else:
        raise ValueError(f"no reference for activation {cfg['activation']!r}")
    return ar.store(x + dense("w_down", u))


def head(cfg, top: Dict[str, jax.Array], x, ar: Arith = F32):
    """Final norm and the output table: (S, d) -> (S, V) logits."""
    top = stored(ar, top)
    h = _norm(cfg, x, top, "final_norm")
    return ar.mm("sd,vd->sv", h, top["embed/table"][:cfg["vocab"]])


def forward(cfg, top, layers, tokens, ar: Arith = F32):
    """Whole-model logits, layers given as a list of per-layer dicts."""
    x = ar.store(embed(cfg, stored(ar, top), tokens))
    for p in layers:
        x = block(cfg, p, x, ar)
    return head(cfg, top, x, ar)

