"""Seeded weights of a dense decoder LM, made by the benchmark.

Matrices are normal with variance 1/fan-in.  The program ties its input
and output embeddings and scales the input by sqrt(d), so a table at
1/sqrt(d) would put each input token's own embedding a unit-RMS share of
the residual stream and make that token the top logit by many standard
deviations: greedy decoding would copy its input.  The table is drawn at
sqrt(L)/d instead, which leaves the input embedding about 1/sqrt(d) of a
stream that L unit-RMS layers build, and spreads the logits like the ones
a model ranks (their standard deviation is sqrt(L/d)).  Every weight
stays small enough that a bf16 leaf registers an AdamW step of 1e-4.

Every leaf is drawn from its own key, ``fold_in(fold_in(key, crc(path)),
layer)``, so one layer's weights can be drawn again on their own: the
system under test gets the whole tree from one jitted call, in the dtype
it serves, and the plain reference draws the same values a layer at a
time.  Leaves are named as the program's parameter tree names them; the
harness checks the layout below against the program's abstract tree, so a
change of the program's layout fails loudly instead of feeding it other
numbers.
"""
from __future__ import annotations

import math
import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

__all__ = ["base_key", "model", "layout", "init", "layer", "top", "STACK",
           "MODEL_KEYS"]

#: Prefix of the leaves stacked over layers (one period of one block kind).
STACK = "scan/pos0/"

#: The keys of a configuration file that fix the model's arithmetic.
MODEL_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "d_ff", "vocab", "activation", "norm", "rope_theta", "window",
              "attn_bias", "ffn_bias", "tie_embeddings", "dtype")


def model(cfg: dict) -> dict:
    """The model keys of a configuration file, and nothing else."""
    return {k: cfg[k] for k in MODEL_KEYS}


def base_key(seed: int):
    """A PRNG key from any non-negative seed, including ones past 32 bits."""
    if seed < 0:
        raise ValueError(f"seed {seed} < 0")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def layout(cfg: dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """path -> (shape, dtype) of every leaf, stacked leaves with the layer
    axis first."""
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


def _draw(cfg: dict, key, path: str, layer_id, shape, dtype):
    """One leaf (or one layer's slice of one), rounded to its stored dtype."""
    k = jax.random.fold_in(jax.random.fold_in(
        key, zlib.crc32(path.encode()) & 0x7FFFFFFF), layer_id)
    z = jax.random.normal(k, shape, jnp.float32)
    name = path.rsplit("/", 2)
    if path.endswith("/scale"):          # norm weight is 1 + scale (rms)
        z = z * 0.1 + (1.0 if cfg["norm"] == "layernorm" else 0.0)
    elif path.endswith("/bias") or path.endswith("/b"):
        z = z * 0.02
    elif path == "embed/table":
        z = z * math.sqrt(cfg["n_layers"]) / cfg["d_model"]
    elif name[-2] == "wo":
        z = z / math.sqrt(cfg["n_heads"] * cfg["head_dim"])
    elif name[-2] == "w_down":
        z = z / math.sqrt(cfg["d_ff"])
    elif path.endswith("/w"):
        z = z / math.sqrt(cfg["d_model"])
    else:
        raise KeyError(f"no draw rule for leaf {path!r}")
    return z.astype(dtype)


def _nest(flat: Dict[str, jax.Array]) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return tree


def init(cfg: dict, key) -> dict:
    """The whole tree in the program's layout and stored dtypes.  Call
    under ``jax.jit`` (with ``cfg`` static) to make it on the device in
    one program; stacked leaves are drawn a layer at a time, so no leaf is
    ever whole in float32."""
    flat = {}
    for path, (shape, dt) in layout(cfg).items():
        if path.startswith(STACK):
            one = lambda l, p=path, s=shape[1:], d=dt: _draw(cfg, key, p, l, s, d)
            flat[path] = jax.lax.map(one, jnp.arange(shape[0]))
        else:
            flat[path] = _draw(cfg, key, path, 0, shape, dt)
    return _nest(flat)


def layer(cfg: dict, key, l) -> Dict[str, jax.Array]:
    """Layer ``l``'s leaves, float32, keyed by path without the stack
    prefix (``attn/wq/w`` ...)."""
    return {p[len(STACK):]: _draw(cfg, key, p, l, s[1:], dt).astype(jnp.float32)
            for p, (s, dt) in layout(cfg).items() if p.startswith(STACK)}


def top(cfg: dict, key) -> Dict[str, jax.Array]:
    """The leaves outside the layer stack, float32."""
    return {p: _draw(cfg, key, p, 0, s, dt).astype(jnp.float32)
            for p, (s, dt) in layout(cfg).items() if not p.startswith(STACK)}
