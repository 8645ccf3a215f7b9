"""Basic layers, all GEMMs routed through the fair-square einsum dispatch.

Every dense contraction in the framework goes through :func:`dense_apply`,
which routes ``repro.core.einsum.fs_einsum`` (site-labelled, policy-aware,
counted) -- so switching a whole model to the paper's square-form
arithmetic is a single config flag (``matmul_mode``), with optional
per-site overrides via ``cfg.contraction_policy``.  Model-internal
contractions that are not dense layers (attention scores, MoE expert
batches, recurrent state mixes, the vocab GEMM) go through ``fs_einsum``
directly at their own call sites, so the dispatch -- and the
multiplies-replaced-by-squares counter -- covers the whole model, not
just the dense layers.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import einsum as fse
from repro.core import prepared as fsp
from repro.core import squares as sq
from repro.layers.param import ParamSpec

__all__ = ["dense_spec", "dense_apply", "embed_spec", "embed_apply",
           "rmsnorm_spec", "rmsnorm_apply", "layernorm_spec",
           "layernorm_apply", "rope", "activation"]

# ---------------------------------------------------------------------- dense

def dense_spec(d_in: int, d_out: int, axes: Tuple[Optional[str], Optional[str]],
               dtype=jnp.bfloat16, bias: bool = False, stack: int = 0):
    shape = (d_in, d_out)
    ax = axes
    if stack:
        shape = (stack,) + shape
        ax = ("layers",) + axes
    spec = {"w": ParamSpec(shape, ax, dtype=dtype, fan_in=d_in)}
    if bias:
        bshape = (stack, d_out) if stack else (d_out,)
        bax = ("layers", axes[1]) if stack else (axes[1],)
        spec["b"] = ParamSpec(bshape, bax, dtype=dtype, init="zeros")
    return spec


def dense_tp_reduce(p, x, *, mode: Optional[str] = None, out_dtype=None,
                    axis: str = "model", reduce_dtype=jnp.bfloat16,
                    policy=None, site: str = "dense"):
    """Row-parallel dense (contraction dim sharded over ``axis``) with an
    EXPLICIT reduced-precision psum.

    GSPMD's automatic lowering all-reduces the f32 partials of TP-sharded
    contractions (measured 268 MB x 480 per train step on deepseek train_4k);
    casting each local partial to bf16 before the psum halves that traffic.
    The local contraction still goes through the fair-square dispatch, so the
    paper's correction terms are computed on the LOCAL K-shard and ride the
    same single collective (DESIGN.md §6).

    Falls back to ``dense_apply`` when there is no mesh, the contraction dim
    does not divide, or the input is not actually sharded on ``axis``.
    """
    from repro.distributed import context as dctx
    mesh = dctx.current_mesh()
    w = p["w"]
    K, N = w.shape[-2], w.shape[-1]
    if (mesh is None or axis not in mesh.axis_names
            or K % mesh.shape[axis] != 0):
        return dense_apply(p, x, mode=mode, out_dtype=out_dtype,
                           policy=policy, site=site)
    # TP sharding splits the contraction axis, so the global-K prepared
    # corrections do not apply per shard: the shard_map path always
    # contracts the raw weight (each shard computes its local corrections).
    w = fsp.unwrap(w)
    import numpy as np
    from jax.sharding import PartitionSpec as P
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dsize = int(np.prod([mesh.shape[a] for a in data_axes])) if data_axes else 1
    lead = x.shape[:-1]
    if not lead or lead[0] % max(1, dsize) != 0:
        data_axes = ()
    bspec = (data_axes,) if data_axes else (None,)
    in_x = P(*bspec, *([None] * (len(lead) - 1)), axis)
    out_s = P(*bspec, *([None] * (len(lead) - 1)), None)

    def body(wl, xl):
        part = fse.fs_einsum("tk,kn->tn", xl.reshape(-1, xl.shape[-1]), wl,
                             mode=mode, policy=policy, site=site,
                             preferred=sq.accum_dtype(xl.dtype))
        part = part.astype(reduce_dtype)
        part = jax.lax.psum(part, axis)
        return part.reshape(*xl.shape[:-1], wl.shape[-1])

    out = jax.shard_map(body, mesh=mesh, in_specs=(P(axis, None), in_x),
                        out_specs=out_s, check_vma=False)(w, x)
    if "b" in p:
        out = out + p["b"].astype(out.dtype)
    if out_dtype is not None:
        out = out.astype(out_dtype)
    return out


def dense_apply(p, x, *, mode: Optional[str] = None, out_dtype=None,
                policy=None, site: str = "dense"):
    """x[..., d_in] @ w[d_in, d_out] through the fair-square dispatch.

    ``p["w"]`` may be a :class:`repro.core.prepared.PreparedOperand`
    (weight-stationary inference: prepare once with
    :func:`repro.core.prepared.prepare_operand` or
    :meth:`repro.models.lm.LM.prepare_params`, reuse every call)."""
    w = p["w"]
    lead = x.shape[:-1]
    out = fse.fs_einsum("tk,kn->tn", x.reshape(-1, x.shape[-1]), w,
                        mode=mode, policy=policy, site=site,
                        preferred=sq.accum_dtype(x.dtype))
    out = out.reshape(*lead, w.shape[-1])
    if "b" in p:
        out = out + p["b"].astype(out.dtype)
    if out_dtype is not None:
        out = out.astype(out_dtype)
    return out


# ------------------------------------------------------------------ embedding

def embed_spec(vocab: int, d: int, dtype=jnp.bfloat16):
    return {"table": ParamSpec((vocab, d), ("vocab", "embed"), dtype=dtype,
                               init="embed", fan_in=d)}


def embed_apply(p, ids):
    return jnp.take(p["table"], ids, axis=0)


# ---------------------------------------------------------------------- norms

def rmsnorm_spec(d: int, stack: int = 0):
    shape = (stack, d) if stack else (d,)
    axes = ("layers", "embed") if stack else ("embed",)
    return {"scale": ParamSpec(shape, axes, dtype=jnp.float32, init="zeros")}


def rmsnorm_apply(p, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * (1.0 + p["scale"])
    return y.astype(x.dtype)


def layernorm_spec(d: int, stack: int = 0):
    shape = (stack, d) if stack else (d,)
    axes = ("layers", "embed") if stack else ("embed",)
    return {"scale": ParamSpec(shape, axes, dtype=jnp.float32, init="ones"),
            "bias": ParamSpec(shape, axes, dtype=jnp.float32, init="zeros")}


def layernorm_apply(p, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return y.astype(x.dtype)


# ----------------------------------------------------------------------- rope

def rope(x, positions, theta: float = 10000.0):
    """Rotary embedding.  x: (..., seq, heads, head_dim); positions: (..., seq)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.arange(half, dtype=jnp.float32) / half
    inv = theta ** (-freqs)                                  # (half,)
    ang = positions[..., :, None].astype(jnp.float32) * inv  # (..., seq, half)
    cos = jnp.cos(ang)[..., :, None, :]                      # (..., seq, 1, half)
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin,
                           xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- activations

def activation(name: str, x, gate=None):
    if name == "gelu":
        return jax.nn.gelu(x)
    if name == "silu":
        return jax.nn.silu(x)
    if name == "geglu":
        assert gate is not None
        return jax.nn.gelu(gate) * x
    if name == "swiglu":
        assert gate is not None
        return jax.nn.silu(gate) * x
    raise ValueError(f"unknown activation {name!r}")
