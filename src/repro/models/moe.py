"""Mixture-of-Experts block: top-k routing, capacity-bounded sort-based
dispatch, batched-expert GEMMs (GShard/Switch style, dropless up to the
capacity factor) -- :func:`moe_apply_local` -- and the dropless held-expert
layer of :func:`moe_apply_held` (below).

Dispatch is plain gather/scatter over a *local* token set, so under the
production mesh the block runs inside ``shard_map`` (tokens sharded over
(pod, data); expert weights tensor-parallel over 'model' on the hidden
axis with a single psum after the down-projection — the same collective
pattern as a dense FFN, so MoE inherits the dense comm roofline).  The
expert GEMMs are batched einsums over the expert axis: FLOPs are exactly
``topk * tokens * capacity_factor`` worth of expert compute — no E/topk
dense-compute inflation.

Router aux (load-balance) loss follows Switch: E * sum_e f_e * P_e.

The router weight and the batched (E, d, f) expert weights may arrive as
:class:`repro.core.prepared.PreparedOperand` leaves (weight-stationary
inference, see :meth:`repro.models.lm.LM.prepare_params`): ``fs_einsum``
then reuses the prepared column slabs -- the batched expert GEMMs are
exactly the constant-operand case the paper's §4 amortization targets.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import counting
from repro.core.einsum import fs_einsum, resolve_mode
from repro.core.prepared import unwrap
from repro.kernels.sq_grouped_matmul import group_rows, max_tiles
from repro.layers import basic
from repro.layers.param import ParamSpec

__all__ = ["moe_spec", "moe_apply_local", "moe_capacity", "moe_apply_held",
           "route", "GROUP_ROWS", "N_STATS"]

#: Row tile of a held expert's group in the grouped GEMM (one f32 sublane
#: granule: a decode step's few rows per expert fill one tile).
GROUP_ROWS = 8
#: Entries of the per-layer statistics :func:`moe_apply_held` returns:
#: top-k picks of valid tokens, those on held experts, rows computed.
N_STATS = 3


def moe_spec(cfg, stack: int = 0):
    d, f = cfg.d_model, cfg.d_ff
    e = cfg.experts_held or cfg.n_experts
    dt = jnp.dtype(cfg.dtype)
    shape = (e, d, f)
    axes = ("expert", "embed", "mlp")
    dshape = (e, f, d)
    daxes = ("expert", "mlp", "embed")
    if stack:
        shape = (stack,) + shape
        axes = ("layers",) + axes
        dshape = (stack,) + dshape
        daxes = ("layers",) + daxes
    E = cfg.n_experts                    # the router's published width
    rshape = (stack, d, E) if stack else (d, E)
    raxes = ("layers", "embed", None) if stack else ("embed", None)
    spec = {
        "router": {"w": ParamSpec(rshape, raxes, dtype=jnp.float32, fan_in=d)},
        "w_gate": {"w": ParamSpec(shape, axes, dtype=dt, fan_in=d)},
        "w_up": {"w": ParamSpec(shape, axes, dtype=dt, fan_in=d)},
        "w_down": {"w": ParamSpec(dshape, daxes, dtype=dt, fan_in=f)},
    }
    if cfg.router_scoring == "sigmoid":
        bshape = (stack, E) if stack else (E,)
        baxes = ("layers", None) if stack else (None,)
        spec["router"]["bias"] = ParamSpec(bshape, baxes, dtype=jnp.float32,
                                           init="zeros")
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * f
        spec["shared"] = {
            "w_gate": basic.dense_spec(d, fs, ("embed", "mlp"), dt, False,
                                       stack),
            "w_up": basic.dense_spec(d, fs, ("embed", "mlp"), dt, False,
                                     stack),
            "w_down": basic.dense_spec(fs, d, ("mlp", "embed"), dt, False,
                                       stack),
        }
    return spec


def moe_capacity(n_tokens: int, cfg) -> int:
    cap = int(n_tokens * cfg.topk * cfg.capacity_factor / cfg.n_experts) + 1
    return max(4, cap + (-cap) % 4)


def moe_apply_local(p, x, *, cfg, mode: Optional[str] = None,
                    psum_axes=None, policy=None):
    """MoE over a local token block.  x: (T, D) (callers flatten B*S).

    ``psum_axes``: mesh axis names to psum the down-projection over when the
    expert hidden axis is tensor-sharded inside shard_map; None outside.
    Returns (out (T, D), aux_loss scalar).
    """
    T, D = x.shape
    E, K = cfg.n_experts, cfg.topk
    C = moe_capacity(T, cfg)

    logits = fs_einsum("td,de->te", x.astype(jnp.float32), p["router"]["w"],
                       mode=mode, policy=policy, site="moe_router")
    probs = jax.nn.softmax(logits, axis=-1)                      # (T, E)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)              # (T, K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)        # renorm

    # ---- flatten assignments and sort by expert ----
    flat_expert = expert_idx.reshape(-1)                         # (T*K,)
    flat_token = jnp.repeat(jnp.arange(T), K)
    flat_gate = gate_vals.reshape(-1)
    order = jnp.argsort(flat_expert, stable=True)
    se, st, sg = flat_expert[order], flat_token[order], flat_gate[order]
    counts = jnp.bincount(se, length=E)                          # (E,)
    offsets = jnp.cumsum(counts) - counts                        # exclusive
    rank = jnp.arange(T * K) - offsets[se]                       # slot in expert
    keep = rank < C
    dest = jnp.where(keep, se * C + rank, E * C)                 # drop -> sink

    # ---- dispatch: (E*C + 1 sink, D) buffer ----
    xt = x.astype(jnp.dtype(cfg.dtype))
    buf = jnp.zeros((E * C + 1, D), xt.dtype).at[dest].set(xt[st])
    eb = buf[: E * C].reshape(E, C, D)

    # ---- batched expert GEMMs (fair-square dispatch over the expert axis) ----
    gate_h = fs_einsum("ecd,edf->ecf", eb, p["w_gate"]["w"],
                       mode=mode, policy=policy, site="moe_expert")
    up_h = fs_einsum("ecd,edf->ecf", eb, p["w_up"]["w"],
                     mode=mode, policy=policy, site="moe_expert")
    h = (jax.nn.silu(gate_h.astype(jnp.float32)) * up_h.astype(jnp.float32))
    h = h.astype(xt.dtype)
    y = fs_einsum("ecf,efd->ecd", h, p["w_down"]["w"],
                  mode=mode, policy=policy,
                  site="moe_expert").astype(jnp.float32)
    if psum_axes:
        y = jax.lax.psum(y, psum_axes)                           # TP combine

    # ---- combine: gather back and weight by gates ----
    y_flat = jnp.concatenate([y.reshape(E * C, D),
                              jnp.zeros((1, D), y.dtype)], axis=0)
    contrib = y_flat[dest] * (sg * keep)[:, None]
    out = jnp.zeros((T, D), jnp.float32).at[st].add(contrib)

    # ---- Switch aux loss: E * sum_e fraction_e * router_prob_e ----
    frac = counts.astype(jnp.float32) / jnp.maximum(1, T * K)
    pmean = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac * pmean)
    return out.astype(x.dtype), aux


# --------------------------------------------------------------------------
# Held, dropless experts (a device's share of an expert-parallel layer)
# --------------------------------------------------------------------------

def route(p, x, *, cfg, mode=None, policy=None):
    """Top-k choice over all ``cfg.n_experts`` router outputs, in f32, by
    sigmoid scores (DeepSeek-V3's ``noaux_tc`` with one group): scores
    ``s = sigmoid(x W)``; the choice ranks ``s + bias`` but the weights
    are ``s`` of the chosen experts.  Then, with ``cfg.norm_topk``, the
    weights are divided by their sum, and all are multiplied by
    ``cfg.routed_scale``.  Returns (weights (T, k) f32, experts (T, k)
    int32).
    """
    if cfg.router_scoring != "sigmoid":
        raise ValueError(f"the held-expert layer routes by sigmoid scores, "
                         f"not {cfg.router_scoring!r}")
    logits = fs_einsum("td,de->te", x.astype(jnp.float32), p["w"],
                       mode=mode, policy=policy, site="moe_router")
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + p["bias"].astype(jnp.float32), cfg.topk)
    gate = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.norm_topk:
        gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
    return gate * cfg.routed_scale, idx.astype(jnp.int32)


def _grouped(rows, w, tile_expert, n_used, *, mode, policy):
    """rows (R, K) in GROUP_ROWS-row tiles times each tile's expert's
    weights w (E, K, N): the Pallas grouped kernel on the square path,
    else the tiles' gathered weights through one batched fs_einsum."""
    w = unwrap(w)
    R, K = rows.shape
    N = w.shape[-1]
    bm = GROUP_ROWS
    if resolve_mode(mode, policy, "moe_expert") == "square_pallas" \
            and jnp.issubdtype(w.dtype, jnp.floating):
        from repro.kernels import ops as kops
        out = kops.sq_grouped_matmul(rows, w, tile_expert, n_used, bm=bm)
        # the static row bound: what the kernel's operand holds
        counting.note_contraction(site="moe_expert", spec="grouped",
                                  mode="square_pallas", mults=R * K * N)
        return out
    wt = jnp.take(w, tile_expert, axis=0)                 # (tiles, K, N)
    out = fs_einsum("tmk,tkn->tmn", rows.reshape(R // bm, bm, K), wt,
                    mode=mode, policy=policy, site="moe_expert")
    return out.reshape(R, N).astype(jnp.float32)


def moe_apply_held(p, x, *, cfg, mode: Optional[str] = None, policy=None,
                   held_lo: int = 0, shared: bool = True, valid=None):
    """The MoE layer of a device holding experts ``[held_lo, held_lo + H)``
    of ``cfg.n_experts`` (``H`` = the leading dim of ``p["w_gate"]``),
    dropless.  x: (T, D) tokens, ``valid`` (T,) masks padding (no picks).

    The router (:func:`route`) chooses among all experts; only the picks
    that land on a held expert are computed -- what the other devices'
    experts would add is not this layer's (expert parallelism sums the
    shares).  Held picks are sorted by expert into GROUP_ROWS-row tiles
    (:func:`repro.kernels.sq_grouped_matmul.group_rows`), each expert's
    SwiGLU runs as three grouped GEMMs over the tiles, and each pick's
    output is weighted and added back to its token.  With ``shared``, the
    shared experts (one SwiGLU of ``n_shared_experts * d_ff``) add to every
    token.  Returns (out (T, D), aux_loss, stats): ``stats`` (N_STATS,)
    int32 = picks of valid tokens, those held, rows the grouped GEMMs
    computed (used tiles x GROUP_ROWS).
    """
    T, D = x.shape
    K = cfg.topk
    H = unwrap(p["w_gate"]["w"]).shape[0]
    dt = jnp.dtype(cfg.dtype)
    if valid is None:
        valid = jnp.ones((T,), bool)
    with jax.named_scope("moe.route"):
        gate, idx = route(p["router"], x, cfg=cfg, mode=mode,
                          policy=policy)
    local = idx - held_lo
    held = (local >= 0) & (local < H) & valid[:, None]           # (T, K)
    flat_e = jnp.where(held, local, H).reshape(-1)
    flat_held = held.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(T), K)
    with jax.named_scope("moe.experts"):
        dest, tile_expert, n_used = group_rows(flat_e, H, GROUP_ROWS)
        R = max_tiles(T * K, H, GROUP_ROWS) * GROUP_ROWS
        xt = x.astype(dt)
        rows = jnp.zeros((R, D), dt).at[jnp.where(flat_held, dest, R)].set(
            xt[flat_tok], mode="drop")
        kw = dict(mode=mode, policy=policy)
        g = _grouped(rows, p["w_gate"]["w"], tile_expert, n_used, **kw)
        u = _grouped(rows, p["w_up"]["w"], tile_expert, n_used, **kw)
        h = (jax.nn.silu(g) * u).astype(dt)
        y = _grouped(h, p["w_down"]["w"], tile_expert, n_used, **kw)
        # rows of unused tiles are undefined: select, never multiply
        contrib = jnp.where(flat_held[:, None], y[dest], 0.0) \
            * gate.reshape(-1)[:, None]
        out = jnp.zeros((T, D), jnp.float32).at[flat_tok].add(contrib)
    if shared and "shared" in p:
        with jax.named_scope("moe.shared"):
            sp = p["shared"]
            su = basic.dense_apply(sp["w_up"], x, mode=mode, policy=policy,
                                   site="moe_shared")
            sg = basic.dense_apply(sp["w_gate"], x, mode=mode, policy=policy,
                                   site="moe_shared")
            sh = basic.activation("swiglu", su, sg).astype(x.dtype)
            out = out + basic.dense_apply(sp["w_down"], sh, mode=mode,
                                          policy=policy,
                                          site="moe_shared").astype(
                                              jnp.float32)
    stats = jnp.stack([K * jnp.sum(valid), jnp.sum(flat_held),
                       n_used[0] * GROUP_ROWS]).astype(jnp.int32)
    # noaux_tc balances by the bias, with no auxiliary loss
    return out.astype(x.dtype), jnp.zeros((), jnp.float32), stats
