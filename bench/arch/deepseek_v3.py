"""DeepSeek-V3 layout MoE LMs (Moonlight-16B-A3B): latent attention, leading
dense layers, sigmoid-routed experts with shared experts.

The program's ``family="moe"`` with ``kv_lora_rank > 0`` and
``experts_held`` (``repro.models.mla``, ``repro.models.moe``).  Keys are
the published ``config.json``'s (``model_type`` ``deepseek_v3``), plus:

- ``experts_held``: the routed experts of each MoE layer this chip holds,
  experts ``[0, experts_held)`` of ``n_routed_experts``;
- ``vocab``: the vocabulary rows held here (the first ``vocab`` of
  ``vocab_size``); the traffic draws its ids from them and the logits are
  over them.

Its reference, straight ``jax.numpy`` from the published description with
every matrix product through ``ar.mm``:

- embeddings not scaled; RMSNorm (epsilon ``rms_norm_eps``) with weights
  stored as ``w - 1``;
- latent attention without query LoRA, in the textbook form:
  ``q = x W_q`` split into ``qk_nope_head_dim`` and ``qk_rope_head_dim``
  columns per head; ``[c, p] = x W_kv_a``, ``c`` normed by ``kv_norm``;
  ``k_nope_h = c W_UK_h``, ``v_h = c W_UV_h``; rotary ``q_pe`` and one
  rotary ``k_pe = RoPE(p)`` shared by every head (rotate-half, see
  ``assumed`` in the configuration); scores scaled by ``(nope + rope) **
  -0.5``; causal softmax; ``W_o``;
- layers ``< first_k_dense_replace``: a SwiGLU of ``intermediate_size``;
- the others: a router over all ``n_routed_experts`` in float32, scores
  ``sigmoid(x W_r)``, the top ``num_experts_per_tok`` of ``scores +
  bias`` chosen (``noaux_tc`` with one group), their scores normalised
  (``norm_topk_prob``) and scaled by ``routed_scaling_factor``; the held
  experts' SwiGLUs of ``moe_intermediate_size`` weighted by those picks
  (the experts other chips hold add nothing here, in the program and the
  reference alike); plus the shared experts, one SwiGLU of
  ``n_shared_experts * moe_intermediate_size``;
- an untied head over the held vocabulary.

Weights.  Matrices are normal with variance 1/fan-in; the input embedding
has unit variance (it is not scaled, and each layer's norm reads it); the
router's correction bias is small, ``N(0, 0.01^2)``: against sigmoid scores
that spread by about 0.2 it moves about 5% of the top-6 picks (near-ties,
on about 30% of tokens) without deciding them, so every expert keeps about
its 6/64 of the picks, as the trained bias keeps a deployment's experts
evenly loaded.  At ``N(0, 0.1^2)`` it moved about 40% of the picks, and
the held experts' share of them, and with it the time of a decode step,
changed with the seed.

The interface is :mod:`bench.arch`'s.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from bench import reference as ref

#: Prefix of the stacked MoE layers (one period of one block kind).
STACK = "scan/pos0/"

MODEL_KEYS = ("num_hidden_layers", "hidden_size", "num_attention_heads",
              "num_key_value_heads", "kv_lora_rank", "q_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "intermediate_size", "moe_intermediate_size",
              "first_k_dense_replace", "moe_layer_freq", "n_routed_experts",
              "experts_held", "num_experts_per_tok", "n_shared_experts",
              "routed_scaling_factor", "norm_topk_prob", "scoring_func",
              "topk_method", "n_group", "topk_group", "rope_theta",
              "rms_norm_eps", "tie_word_embeddings", "attention_bias",
              "max_position_embeddings", "vocab", "dtype")

WIDTHS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
          "intermediate_size", "moe_intermediate_size",
          "num_experts_per_tok")

KEPT = ("ln1", "ln2", "final_norm")

TINY = dict(num_hidden_layers=5, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=16, v_head_dim=16, intermediate_size=128,
            moe_intermediate_size=32, n_routed_experts=8, experts_held=4,
            num_experts_per_tok=3, n_shared_experts=2, vocab=512,
            dtype="float32")

#: Leaves the program keeps in float32 inside a layer (besides norms).
_F32 = ("ffn/router/", "attn/kv_norm/")


def _check(cfg: dict) -> None:
    """What the program implements of the published family."""
    want = dict(q_lora_rank=None, scoring_func="sigmoid",
                topk_method="noaux_tc", n_group=1, topk_group=1,
                moe_layer_freq=1, attention_bias=False,
                tie_word_embeddings=False)
    bad = {k: cfg[k] for k, v in want.items() if cfg[k] != v}
    if bad:
        raise ValueError(f"deepseek_v3: the program has no path for {bad}")


def model_kwargs(cfg: dict) -> dict:
    _check(cfg)
    return dict(
        family="moe", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["moe_intermediate_size"], vocab=cfg["vocab"],
        activation="swiglu", norm="rmsnorm", rope_theta=cfg["rope_theta"],
        tie_embeddings=False, embed_scale=False,
        norm_eps=cfg["rms_norm_eps"], max_seq=cfg["max_position_embeddings"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        first_k_dense=cfg["first_k_dense_replace"],
        dense_d_ff=cfg["intermediate_size"],
        n_experts=cfg["n_routed_experts"], experts_held=cfg["experts_held"],
        topk=cfg["num_experts_per_tok"], router_scoring="sigmoid",
        norm_topk=cfg["norm_topk_prob"],
        routed_scale=cfg["routed_scaling_factor"],
        n_shared_experts=cfg["n_shared_experts"], block_pattern=("moe",),
        dtype=cfg["dtype"])


# ------------------------------------------------------------------ weights

def _lead(cfg: dict):
    return [f"lead/layer{i}/" for i in range(cfg["first_k_dense_replace"])]


def _attn_layout(cfg, pre, L, dt):
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    r, dn, dr, dv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    return {f"{pre}attn/wq/w": (L + (d, H, dn + dr), dt),
            f"{pre}attn/wkv_a/w": (L + (d, r + dr), dt),
            f"{pre}attn/kv_norm/scale": (L + (r,), "float32"),
            f"{pre}attn/wk_b/w": (L + (H, dn, r), dt),
            f"{pre}attn/wv_b/w": (L + (H, r, dv), dt),
            f"{pre}attn/wo/w": (L + (H, dv, d), dt),
            f"{pre}ln1/scale": (L + (d,), "float32"),
            f"{pre}ln2/scale": (L + (d,), "float32")}


def _swiglu_layout(pre, L, d, f, dt):
    return {f"{pre}w_gate/w": (L + (d, f), dt),
            f"{pre}w_up/w": (L + (d, f), dt),
            f"{pre}w_down/w": (L + (f, d), dt)}


def layout(cfg: dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """Stacked leaves carry the layer axis first."""
    d, V, dt = cfg["hidden_size"], cfg["vocab"], cfg["dtype"]
    V = V + (-V) % 256                   # the program pads its vocab tables
    f, E, Eh = (cfg["moe_intermediate_size"], cfg["n_routed_experts"],
                cfg["experts_held"])
    out = {"embed/table": ((V, d), dt), "head/table": ((V, d), dt),
           "final_norm/scale": ((d,), "float32")}
    for pre in _lead(cfg):
        out.update(_attn_layout(cfg, pre, (), dt))
        out.update(_swiglu_layout(pre + "ffn/", (), d,
                                  cfg["intermediate_size"], dt))
    L = (cfg["num_hidden_layers"] - cfg["first_k_dense_replace"],)
    out.update(_attn_layout(cfg, STACK, L, dt))
    out[f"{STACK}ffn/router/w"] = (L + (d, E), "float32")
    out[f"{STACK}ffn/router/bias"] = (L + (E,), "float32")
    out[f"{STACK}ffn/w_gate/w"] = (L + (Eh, d, f), dt)
    out[f"{STACK}ffn/w_up/w"] = (L + (Eh, d, f), dt)
    out[f"{STACK}ffn/w_down/w"] = (L + (Eh, f, d), dt)
    out.update(_swiglu_layout(f"{STACK}ffn/shared/", L, d,
                              cfg["n_shared_experts"] * f, dt))
    return out


def layers(cfg: dict):
    n_moe = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    return [(p, None) for p in _lead(cfg)] + [(STACK, l) for l in range(n_moe)]


def draw(cfg: dict, path: str, z):
    name = path.rsplit("/", 2)
    if path.endswith("/scale"):          # norm weight is 1 + scale (rms)
        return z * 0.1
    if path.endswith("router/bias"):
        return z * 0.01
    if path == "embed/table":
        return z
    if path == "head/table":
        return z / math.sqrt(cfg["hidden_size"])
    if name[-2] == "wo":
        return z / math.sqrt(cfg["num_attention_heads"] * cfg["v_head_dim"])
    if name[-2] in ("wk_b", "wv_b"):
        return z / math.sqrt(cfg["kv_lora_rank"])
    if name[-2] == "w_down":
        return z / math.sqrt(path_fan_in(cfg, path))
    if path.endswith("/w"):
        return z / math.sqrt(cfg["hidden_size"])
    raise KeyError(f"no draw rule for leaf {path!r}")


def path_fan_in(cfg: dict, path: str) -> int:
    """The input width of a ``w_down``: the SwiGLU it closes."""
    f = cfg["moe_intermediate_size"]
    if "/shared/" in path:
        return cfg["n_shared_experts"] * f
    if path.startswith("lead/"):
        return cfg["intermediate_size"]
    return f


# ---------------------------------------------------------------- reference

def _stored(ar, p):
    """Weights as the arithmetic keeps them: norms, the router and the
    latent norm stay float32, as the program keeps them."""
    kept = ref.stored(ar, p, KEPT)
    return {k: p[k] if k.startswith(_F32) else v for k, v in kept.items()}


def _norm(cfg, x, w):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x / jnp.sqrt(var + cfg["rms_norm_eps"]) * (1.0 + w)


def _swiglu(mm, h, p, pre):
    u = jax.nn.silu(mm("sd,df->sf", h, p[f"{pre}w_gate/w"])) \
        * mm("sd,df->sf", h, p[f"{pre}w_up/w"])
    return mm("sf,fd->sd", u, p[f"{pre}w_down/w"])


def attention(cfg, p, h, mm):
    """Latent attention over a causal sequence h (S, d), textbook form."""
    S = h.shape[0]
    H = cfg["num_attention_heads"]
    r, dn, dr = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], \
        cfg["qk_rope_head_dim"]
    pos = jnp.arange(S)
    q = mm("sd,dnh->snh", h, p["attn/wq/w"])
    q_nope, q_pe = q[..., :dn], ref.rope(q[..., dn:], pos, cfg["rope_theta"])
    ckv = mm("sd,dc->sc", h, p["attn/wkv_a/w"])
    c = _norm(cfg, ckv[:, :r], p["attn/kv_norm/scale"])
    k_pe = ref.rope(ckv[:, None, r:], pos, cfg["rope_theta"])   # (S, 1, dr)
    k_nope = mm("sc,hnc->shn", c, p["attn/wk_b/w"])
    v = mm("sc,hcv->shv", c, p["attn/wv_b/w"])
    s = (mm("qhn,khn->hqk", q_nope, k_nope)
         + mm("qhr,kr->hqk", q_pe, k_pe[:, 0])) / math.sqrt(dn + dr)
    s = jnp.where((pos[None, :] <= pos[:, None])[None], s, ref.NEG)
    w = jax.nn.softmax(s, axis=-1)
    o = mm("hqk,khv->qhv", w, v)
    return mm("qhv,hvd->qd", o, p["attn/wo/w"])


def experts(cfg, p, h, mm, held_lo: int = 0):
    """The routed experts' part of a MoE layer that holds experts
    ``[held_lo, held_lo + experts_held)``: the router over all experts,
    only the held ones computed."""
    K = cfg["num_experts_per_tok"]
    logits = mm("sd,de->se", h, p["ffn/router/w"])
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + p["ffn/router/bias"], K)
    gate = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        gate = gate / (jnp.sum(gate, -1, keepdims=True) + 1e-20)
    gate = gate * cfg["routed_scaling_factor"]
    held = held_lo + jnp.arange(p["ffn/w_gate/w"].shape[0])
    # weight of each held expert for each token: its pick's gate, or 0
    wt = jnp.sum(jnp.where(idx[:, :, None] == held[None, None, :],
                           gate[:, :, None], 0.0), axis=1)        # (S, Eh)
    u = jax.nn.silu(mm("sd,edf->esf", h, p["ffn/w_gate/w"])) \
        * mm("sd,edf->esf", h, p["ffn/w_up/w"])
    y = mm("esf,efd->esd", u, p["ffn/w_down/w"])
    return jnp.einsum("se,esd->sd", wt, y,
                      precision=jax.lax.Precision.HIGHEST)


def layer(cfg, p, x, ar: ref.Arith = ref.F32, *, moe: bool,
          held_lo: int = 0):
    """One decoder layer over a causal sequence x (S, d)."""
    mm = ar.mm
    p = _stored(ar, p)
    x = x + attention(cfg, p, _norm(cfg, x, p["ln1/scale"]), mm)
    h = _norm(cfg, x, p["ln2/scale"])
    if moe:
        out = experts(cfg, p, h, mm, held_lo) + _swiglu(mm, h, p,
                                                        "ffn/shared/")
    else:
        out = _swiglu(mm, h, p, "ffn/")
    return ar.store(x + out)


def dense_layer(cfg, p, x, ar: ref.Arith = ref.F32):
    return layer(cfg, p, x, ar, moe=False)


def moe_layer(cfg, p, x, ar: ref.Arith = ref.F32):
    return layer(cfg, p, x, ar, moe=True)


def embed(cfg, top: Dict[str, jax.Array], tokens):
    """(S,) ids -> (S, d) input embeddings (not scaled)."""
    return top["embed/table"][tokens]


def block(cfg, l: int):
    return dense_layer if l < cfg["first_k_dense_replace"] else moe_layer


def head(cfg, top: Dict[str, jax.Array], x, ar: ref.Arith = ref.F32):
    """Final norm and the untied head over the held vocabulary."""
    top = ref.stored(ar, top, KEPT)
    h = _norm(cfg, x, top["final_norm/scale"])
    return ar.mm("sd,vd->sv", h, top["head/table"][:cfg["vocab"]])


# -------------------------------------------------------------- operations

def _attn_macs(cfg: dict) -> int:
    """Per token and layer, the projections of the absorbed form that
    serving runs: q, the latent, q_nope into the latent, the latent output
    out through W_UV, and W_o."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    r, dn, dr, dv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    return (d * H * (dn + dr) + d * (r + dr) + H * dn * r + H * r * dv
            + H * dv * d)


def held_picks(cfg: dict) -> float:
    """Routed experts a token runs on this chip, in expectation."""
    return cfg["num_experts_per_tok"] * cfg["experts_held"] \
        / cfg["n_routed_experts"]


def matmul_params(cfg: dict) -> float:
    """Weights each token multiplies through in the layer stack."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    k = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - k
    dense = _attn_macs(cfg) + 3 * d * cfg["intermediate_size"]
    moe = (_attn_macs(cfg) + d * cfg["n_routed_experts"]
           + held_picks(cfg) * 3 * d * f
           + 3 * d * cfg["n_shared_experts"] * f)
    return k * dense + n_moe * moe


def attention_flops(cfg: dict, ctx: int) -> float:
    """One query over ``ctx`` latent rows, every head and layer: q.k over
    the latent and rotary width, p.v over the latent."""
    Dk = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return 2.0 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] \
        * ctx * (Dk + cfg["kv_lora_rank"])


def logits_flops(cfg: dict) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab"]


def token_flops(cfg: dict, ctx: int, logits: bool) -> float:
    f = 2.0 * matmul_params(cfg) + attention_flops(cfg, ctx)
    return f + (logits_flops(cfg) if logits else 0.0)


def paged_attn_cost(cfg: dict, ctx: int, itemsize: int) -> Tuple[float, float]:
    """One latent pool: one ``kv_lora_rank + qk_rope_head_dim`` row a token
    and layer, read once as both K and V."""
    Dk = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return (attention_flops(cfg, ctx),
            float(cfg["num_hidden_layers"] * ctx * Dk * itemsize))
