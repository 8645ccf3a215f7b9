"""Dense decoder LMs: one kind of block, stacked, with grouped K/V heads.

The program's ``family="dense"`` (``repro.models.lm``).  Its reference,
straight ``jax.numpy`` from the published descriptions, with every matrix
product through ``ar.mm`` (``Precision.HIGHEST`` in float32):

- h2o-danube3 (Llama layout): RMSNorm, rotary attention with grouped K/V
  heads, SwiGLU feed-forward, no biases;
- StarCoder2: LayerNorm with bias, rotary attention with grouped K/V heads
  and biases, tanh-GELU feed-forward with biases, sliding window.

Both use rotate-half RoPE and scale scores by ``1/sqrt(head_dim)``.  The
input embedding is ``sqrt(d_model)`` times the output table (the program
ties the two and scales its input; the reference keeps them as two
matrices, which the published untied layout allows).

Weights.  Matrices are normal with variance 1/fan-in.  The program ties
its input and output embeddings and scales the input by sqrt(d), so a
table at 1/sqrt(d) would put each input token's own embedding a unit-RMS
share of the residual stream and make that token the top logit by many
standard deviations: greedy decoding would copy its input.  The table is
drawn at sqrt(L)/d instead, which leaves the input embedding about
1/sqrt(d) of a stream that L unit-RMS layers build, and spreads the logits
like the ones a model ranks (their standard deviation is sqrt(L/d)).
Every weight stays small enough that a bf16 leaf registers an AdamW step
of 1e-4.

The interface is :mod:`bench.arch`'s.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from bench import reference as ref

#: Prefix of the leaves stacked over layers (one period of one block kind).
STACK = "scan/pos0/"

MODEL_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "d_ff", "vocab", "activation", "norm", "rope_theta", "window",
              "attn_bias", "ffn_bias", "tie_embeddings", "dtype")

WIDTHS = ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff")

KEPT = ("ln1", "ln2", "final_norm")

TINY = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
            d_ff=128, vocab=512, window=64, dtype="float32")


def model_kwargs(cfg: dict) -> dict:
    return {"family": "dense", **{k: cfg[k] for k in MODEL_KEYS}}


# ------------------------------------------------------------------ weights

def layout(cfg: dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """Stacked leaves carry the layer axis first."""
    d, H, KV, hd = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"], \
        cfg["head_dim"]
    f, L, V = cfg["d_ff"], cfg["n_layers"], cfg["vocab"]
    dt = cfg["dtype"]
    V = V + (-V) % 256                   # the program pads its vocab table
    norm = ("scale", "bias") if cfg["norm"] == "layernorm" else ("scale",)
    out = {"embed/table": ((V, d), dt)}
    for n in norm:
        out[f"final_norm/{n}"] = ((d,), "float32")
        out[f"{STACK}ln1/{n}"] = ((L, d), "float32")
        out[f"{STACK}ln2/{n}"] = ((L, d), "float32")
    heads = {"wq": H, "wk": KV, "wv": KV}
    for nm, nh in heads.items():
        out[f"{STACK}attn/{nm}/w"] = ((L, d, nh, hd), dt)
        if cfg["attn_bias"]:
            out[f"{STACK}attn/{nm}/b"] = ((L, nh, hd), dt)
    out[f"{STACK}attn/wo/w"] = ((L, H, hd, d), dt)
    if cfg["attn_bias"]:
        out[f"{STACK}attn/wo/b"] = ((L, d), dt)
    mats = {"w_up": (d, f), "w_down": (f, d)}
    if cfg["activation"] in ("swiglu", "geglu"):
        mats["w_gate"] = (d, f)
    for nm, (a, b) in mats.items():
        out[f"{STACK}ffn/{nm}/w"] = ((L, a, b), dt)
        if cfg["ffn_bias"]:
            out[f"{STACK}ffn/{nm}/b"] = ((L, b), dt)
    return out


def layers(cfg: dict):
    return [(STACK, l) for l in range(cfg["n_layers"])]


def draw(cfg: dict, path: str, z):
    name = path.rsplit("/", 2)
    if path.endswith("/scale"):          # norm weight is 1 + scale (rms)
        return z * 0.1 + (1.0 if cfg["norm"] == "layernorm" else 0.0)
    if path.endswith("/bias") or path.endswith("/b"):
        return z * 0.02
    if path == "embed/table":
        return z * math.sqrt(cfg["n_layers"]) / cfg["d_model"]
    if name[-2] == "wo":
        return z / math.sqrt(cfg["n_heads"] * cfg["head_dim"])
    if name[-2] == "w_down":
        return z / math.sqrt(cfg["d_ff"])
    if path.endswith("/w"):
        return z / math.sqrt(cfg["d_model"])
    raise KeyError(f"no draw rule for leaf {path!r}")


# ---------------------------------------------------------------- reference

def _norm(cfg, x, p, name):
    if cfg["norm"] == "layernorm":
        return ref.layer_norm(x, p[f"{name}/scale"], p[f"{name}/bias"])
    return ref.rms_norm(x, 1.0 + p[f"{name}/scale"])   # stored as w - 1


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def embed(cfg, top: Dict[str, jax.Array], tokens):
    """(S,) ids -> (S, d) input embeddings."""
    return top["embed/table"][tokens] * math.sqrt(cfg["d_model"])


def decoder_layer(cfg, p: Dict[str, jax.Array], x, ar: ref.Arith = ref.F32):
    """One decoder layer over a whole causal sequence x (S, d)."""
    mm = ar.mm
    p = ref.stored(ar, p, KEPT)
    S = x.shape[0]
    H, KV, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    pos = jnp.arange(S)
    h = _norm(cfg, x, p, "ln1")

    def proj(name):
        y = mm("sd,dnh->snh", h, p[f"attn/{name}/w"])
        if f"attn/{name}/b" in p:
            y = y + p[f"attn/{name}/b"]
        return y

    q = ref.rope(proj("wq"), pos, cfg["rope_theta"])
    k = ref.rope(proj("wk"), pos, cfg["rope_theta"])
    v = proj("wv")
    k = jnp.repeat(k, H // KV, axis=1)          # head h reads K/V h // G
    v = jnp.repeat(v, H // KV, axis=1)
    s = mm("qnh,knh->nqk", q, k) / math.sqrt(hd)
    allowed = pos[None, :] <= pos[:, None]
    if cfg.get("window"):
        allowed &= (pos[:, None] - pos[None, :]) < cfg["window"]
    s = jnp.where(allowed[None], s, ref.NEG)
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


def block(cfg, l: int):
    return decoder_layer


def head(cfg, top: Dict[str, jax.Array], x, ar: ref.Arith = ref.F32):
    """Final norm and the output table: (S, d) -> (S, V) logits."""
    top = ref.stored(ar, top, KEPT)
    h = _norm(cfg, x, top, "final_norm")
    return ar.mm("sd,vd->sv", h, top["embed/table"][:cfg["vocab"]])


# -------------------------------------------------------------- operations

def matmul_params(cfg: dict) -> int:
    """Weights each token multiplies through in the layer stack."""
    d, H, KV, hd, f = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                       cfg["head_dim"], cfg["d_ff"])
    ffn = 3 if cfg["activation"] in ("swiglu", "geglu") else 2
    per_layer = d * (H + 2 * KV) * hd + H * hd * d + ffn * d * f
    return per_layer * cfg["n_layers"]


def attention_flops(cfg: dict, ctx: int) -> float:
    """q.k and p.v of one query over ``ctx`` keys, every layer."""
    if cfg.get("window"):
        ctx = min(ctx, cfg["window"])
    return 4.0 * cfg["n_layers"] * cfg["n_heads"] * cfg["head_dim"] * ctx


def logits_flops(cfg: dict) -> float:
    return 2.0 * cfg["d_model"] * cfg["vocab"]


def token_flops(cfg: dict, ctx: int, logits: bool) -> float:
    f = 2.0 * matmul_params(cfg) + attention_flops(cfg, ctx)
    return f + (logits_flops(cfg) if logits else 0.0)


def paged_attn_cost(cfg: dict, ctx: int, itemsize: int) -> Tuple[float, float]:
    """K and V are two pools of ``n_kv_heads * head_dim`` a token."""
    if cfg.get("window"):
        ctx = min(ctx, cfg["window"])
    kv, hd, L = cfg["n_kv_heads"], cfg["head_dim"], cfg["n_layers"]
    return attention_flops(cfg, ctx), 2.0 * L * ctx * kv * hd * itemsize
