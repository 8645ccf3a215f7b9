"""Seeded weights, made by the benchmark, in the program's layout.

Every leaf is drawn from its own key, ``fold_in(fold_in(key, crc(path)),
layer)``, so one layer's weights can be drawn again on their own: the
system under test gets the whole tree from one jitted call, in the dtype
it serves, and the plain reference draws the same values a layer at a
time.  ``layer`` is the leaf's position along its stack's layer axis, 0
for a leaf that has none.  The layout, the layers and each leaf's
distribution are the architecture's (``bench/arch/<arch>.py``); leaves
are named as the program's parameter tree names them, and the harness
checks the layout against the program's abstract tree, so a change of the
program's layout fails loudly instead of feeding it other numbers.
"""
from __future__ import annotations

import zlib
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from bench import arch

__all__ = ["base_key", "model", "layout", "init", "layer", "top"]


def model(cfg: dict) -> dict:
    """The keys of a configuration file that fix the model's arithmetic,
    its architecture among them, and nothing else."""
    return {"arch": cfg["arch"], **{k: cfg[k] for k in arch.of(cfg).MODEL_KEYS}}


def base_key(seed: int):
    """A PRNG key from any non-negative seed, including ones past 32 bits."""
    if seed < 0:
        raise ValueError(f"seed {seed} < 0")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def layout(cfg: dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """path -> (shape, dtype) of every leaf, stacked leaves with the layer
    axis first."""
    return arch.of(cfg).layout(cfg)


def _draw(cfg: dict, key, path: str, layer_id, shape, dtype):
    """One leaf (or one layer's slice of one), rounded to its stored dtype."""
    k = jax.random.fold_in(jax.random.fold_in(
        key, zlib.crc32(path.encode()) & 0x7FFFFFFF), layer_id)
    z = jax.random.normal(k, shape, jnp.float32)
    return arch.of(cfg).draw(cfg, path, z).astype(dtype)


def _nest(flat: Dict[str, jax.Array]) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return tree


def _prefixes(cfg: dict):
    """(prefixes of stacked layers, prefixes of layers of their own)."""
    lay = arch.of(cfg).layers(cfg)
    return ({p for p, i in lay if i is not None},
            {p for p, i in lay if i is None})


def init(cfg: dict, key) -> dict:
    """The whole tree in the program's layout and stored dtypes.  Call
    under ``jax.jit`` (with ``cfg`` static) to make it on the device in
    one program; stacked leaves are drawn a layer at a time, so no leaf is
    ever whole in float32."""
    stacked, _ = _prefixes(cfg)
    flat = {}
    for path, (shape, dt) in layout(cfg).items():
        if path.startswith(tuple(stacked)):
            one = lambda l, p=path, s=shape[1:], d=dt: _draw(cfg, key, p, l, s, d)
            flat[path] = jax.lax.map(one, jnp.arange(shape[0]))
        else:
            flat[path] = _draw(cfg, key, path, 0, shape, dt)
    return _nest(flat)


def layer(cfg: dict, key, prefix: str, i: Optional[jax.Array] = None
          ) -> Dict[str, jax.Array]:
    """One layer's leaves, float32, keyed by path without ``prefix``
    (``attn/wq/w`` ...): position ``i`` of the stack under ``prefix``, or
    with ``i`` None the leaves of a layer of its own.  ``(prefix, i)`` is
    the layer's entry in the architecture's ``layers``."""
    return {p[len(prefix):]: _draw(cfg, key, p, 0 if i is None else i,
                                   s if i is None else s[1:], dt
                                   ).astype(jnp.float32)
            for p, (s, dt) in layout(cfg).items() if p.startswith(prefix)}


def top(cfg: dict, key) -> Dict[str, jax.Array]:
    """The leaves outside every layer, float32."""
    stacked, own = _prefixes(cfg)
    inside = tuple(stacked | own)
    return {p: _draw(cfg, key, p, 0, s, dt).astype(jnp.float32)
            for p, (s, dt) in layout(cfg).items() if not p.startswith(inside)}
