"""Architectures the benchmark runs, one module each: ``bench/arch/<arch>.py``.

A configuration file names its architecture under ``"arch"``.  The
yardstick's shared modules (:mod:`bench.weights`, :mod:`bench.reference`,
:mod:`bench.checks`, :mod:`bench.flops`, :mod:`bench.program`) ask that
module for everything that belongs to one architecture, so a new
architecture is a new file here and no existing file is edited.

Every configuration has ``"arch"`` and ``"vocab"``, the token ids the
model holds and the traffic draws from.  An architecture module defines:

``MODEL_KEYS``
    the configuration keys that fix the model's arithmetic; with
    ``"arch"`` they are what the shared modules see
    (:func:`bench.weights.model`).
``WIDTHS``
    keys that are widths (beside any key ending in ``_dim`` or
    ``_rank``): a configuration never lists them in ``reduced``.
``KEPT``
    first path components of the leaves the program keeps in float32
    (norm parameters); every other leaf is stored in the served dtype.
``TINY``
    the keys to replace for a model of the same architecture that the
    CPU runs in seconds (the tests' cells).
``model_kwargs(cfg)``
    keyword arguments of the program's ``ModelConfig``, its ``family``
    among them.
``layout(cfg)``
    ``{path: (shape, dtype)}`` of every parameter leaf, named as the
    program's parameter tree names it.
``layers(cfg)``
    one ``(prefix, index)`` per layer, in the order the layers run.  The
    layer's leaves are those whose path starts with ``prefix``; ``index``
    is its position along their leading layer axis, or None where the
    leaves carry no such axis (a layer of its own).  Leaves under no
    layer's prefix are the top: embeddings, final norm, head.
``draw(cfg, path, z)``
    a leaf's values from ``z``, standard normal draws of its shape.
``embed(cfg, top, tokens)``, ``block(cfg, l)``, ``head(cfg, top, x, ar)``
    the plain float32 reference: input embeddings of ``(S,)`` ids; the
    function ``fn(cfg, p, x, ar)`` that runs layer ``l`` over a causal
    sequence ``x (S, d)`` from its leaves ``p`` (keyed by path without
    the prefix); final norm and logits.  ``ar`` is a
    :class:`bench.reference.Arith`.
``token_flops(cfg, ctx, logits)``, ``logits_flops(cfg)``
    operations the model requires for one token at context ``ctx`` (the
    token included), with or without its logits, and for the logits.
``paged_attn_cost(cfg, ctx, itemsize)``
    ``(operations, bytes)`` of one query's paged attention at context
    ``ctx`` over every layer: its q.k and p.v, and the cached K and V it
    reads once at ``itemsize`` bytes a value.

How a mixture-of-experts model with latent attention (Moonlight-16B-A3B,
the ``deepseek_v3`` layout) says itself in this interface:

- A leading dense layer before a stacked MoE period: ``layers`` gives
  layer 0 the dense layer's own prefix (index None, or 0 of a stack of
  one) and layers 1.. the MoE stack's prefix with indices 0..; ``block``
  returns the dense SwiGLU block for ``l < first_k_dense_replace`` and
  the MoE block after it.  Layers are told apart by index.
- Held experts and a sliced vocabulary are configuration keys beside
  their published counts (say ``experts_held`` beside
  ``n_routed_experts``, ``vocab`` beside ``vocab_published``), both in
  ``MODEL_KEYS``: ``layout`` gives the expert leaves a leading axis of
  the experts held, the router its published width, and the tables
  ``vocab`` rows.
- A latent pool whose K and V share storage: ``paged_attn_cost`` counts
  q.k over the latent and rotary width (576) and p.v over the latent
  (512), and bytes for one 576-wide row a token and layer, read once.
- Per-token operations count the routed experts each token picks
  (``num_experts_per_tok``) that this chip holds, in expectation
  ``num_experts_per_tok * experts_held / n_routed_experts``, plus the
  shared experts, never every routed expert.
"""
from __future__ import annotations

import importlib.util
import os
from types import ModuleType
from typing import Dict

__all__ = ["DIR", "path", "load", "of"]

#: Where architecture modules are looked up.
DIR = os.path.dirname(os.path.abspath(__file__))

_loaded: Dict[str, ModuleType] = {}


def path(name: str) -> str:
    """The file an architecture named ``name`` lives in."""
    return os.path.join(DIR, name + ".py")


def load(name: str) -> ModuleType:
    """The module of the architecture ``name``, loaded once per file."""
    p = path(name)
    if p not in _loaded:
        if not os.path.exists(p):
            raise FileNotFoundError(f"no architecture {name!r}: expected "
                                    f"its module at {p}")
        spec = importlib.util.spec_from_file_location(f"bench_arch_{name}", p)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[p] = mod
    return _loaded[p]


def of(cfg: dict) -> ModuleType:
    """The architecture module a configuration names."""
    if "arch" not in cfg:
        raise FileNotFoundError(
            f"configuration {cfg.get('name', '?')!r} names no architecture: "
            f"give it \"arch\", the name of its module {path('<arch>')}")
    return load(cfg["arch"])

