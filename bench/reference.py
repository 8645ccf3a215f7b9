"""What every plain float32 reference shares: arithmetic, norms and RoPE.

Straight ``jax.numpy``, with no kernel, cache, batching or square
arithmetic.  The architectures themselves (embedding, layers, head) are in
``bench/arch/<arch>.py``; their weights come from :mod:`bench.weights`,
never from the program.

An :class:`Arith` says how the reference computes: ``mm`` is the one
matrix product every contraction goes through and ``store`` rounds what
is kept between operations (weights, the residual stream after each
layer).  :data:`F32` is the reference, every product at
``Precision.HIGHEST``; :data:`FP8` is its low-precision control, float8
(e4m3, one scale per tensor) wherever the program keeps bf16.  Norm
epsilons are the published 1e-5.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Sequence

import jax
import jax.numpy as jnp

__all__ = ["Arith", "F32", "FP8", "mm_f32", "mm_fp8", "stored", "rms_norm",
           "layer_norm", "rope", "EPS", "NEG"]

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


def stored(ar: Arith, leaves: Dict[str, jax.Array],
           kept: Sequence[str]) -> Dict[str, jax.Array]:
    """Weights as the arithmetic keeps them: every leaf the program keeps
    in bf16 goes through ``store``; leaves whose path starts with a name
    in ``kept`` (norm parameters) stay float32, as the program keeps
    them."""
    return {k: v if k.split("/")[0] in kept else ar.store(v)
            for k, v in leaves.items()}


def rms_norm(x, w):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x / jnp.sqrt(var + EPS) * w


def layer_norm(x, scale, bias):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + EPS) * scale + bias


def rope(x, pos, theta):
    """x (S, n, hd); rotate-half with inv_freq = theta^(-2i/hd)."""
    half = x.shape[-1] // 2
    inv = theta ** (-(jnp.arange(half, dtype=jnp.float32) * 2.0
                      / x.shape[-1]))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
