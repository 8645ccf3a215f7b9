"""The comparisons that decide ``correct``, against the plain reference.

Serving: over each served request's prompt and served tokens, the gap by
which each served token's reference logit lies below the reference's best
at that position.  :func:`served_numbers` reduces the gaps to the numbers
compared.  Its control puts the reference in fp8 in the program's place
and reads the gaps of the tokens fp8 puts first.

All reference work runs layer by layer, after the program's state is
freed, so that it fits beside nothing.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import arch, weights
from bench import reference as ref

__all__ = ["served_gaps", "served_numbers", "reference_logits"]

BUCKET = 256


def _bucket(n: int) -> int:
    return -(-n // BUCKET) * BUCKET


def reference_logits(cfg: dict, key, seqs: Sequence[np.ndarray],
                     ar=ref.F32) -> List[jax.Array]:
    """Reference logits (S, V) of each token sequence, drawing each
    layer's weights once for all of them.  The layers run in the
    architecture's order, each with the block it names for it."""
    mod = arch.of(cfg)
    frozen = tuple(sorted(cfg.items(), key=lambda kv: kv[0]))
    layer_fn = jax.jit(functools.partial(_layer, frozen), static_argnums=1)
    top = jax.jit(functools.partial(_top, frozen))(key)
    kept = ref.stored(ar, top, mod.KEPT)
    xs = []
    for s in seqs:
        toks = np.zeros(_bucket(len(s)), np.int32)
        toks[:len(s)] = s
        xs.append(ar.store(mod.embed(cfg, kept, jnp.asarray(toks))))
    block_fns = {}
    for l, (prefix, i) in enumerate(mod.layers(cfg)):
        p = layer_fn(key, prefix, None if i is None else jnp.int32(i))
        fn = mod.block(cfg, l)
        if fn not in block_fns:
            block_fns[fn] = jax.jit(functools.partial(_block, frozen, ar, fn))
        xs = [block_fns[fn](p, x) for x in xs]
    head_fn = jax.jit(functools.partial(_head, frozen, ar))
    return [head_fn(top, x)[:len(s)] for x, s in zip(xs, seqs)]


def _layer(frozen, key, prefix, i):
    return weights.layer(dict(frozen), key, prefix, i)


def _top(frozen, key):
    return weights.top(dict(frozen), key)


def _block(frozen, ar, fn, p, x):
    return fn(dict(frozen), p, x, ar)


def _head(frozen, ar, top, x):
    cfg = dict(frozen)
    return arch.of(cfg).head(cfg, top, x, ar)


def served_gaps(cfg: dict, key,
                served: Sequence[Tuple[np.ndarray, np.ndarray]],
                control=None) -> np.ndarray:
    """Gap of each served token below the reference's best logit.

    ``served``: (prompt, served tokens) pairs.  With ``control`` (an
    :class:`bench.reference.Arith` such as ``FP8``), the tokens judged
    are those the control puts first at the same positions instead."""
    if not served:
        return np.asarray([np.inf])
    seqs = [np.concatenate([p, o[:-1]]) for p, o in served]
    logits = reference_logits(cfg, key, seqs)
    picks = [None] * len(served)
    if control is not None:
        ctl = reference_logits(cfg, key, seqs, ar=control)
        picks = [np.asarray(jnp.argmax(c[len(p) - 1:], axis=-1))
                 for c, (p, _) in zip(ctl, served)]
    gaps = []
    for lg, (p, o), pick in zip(logits, served, picks):
        rows = lg[len(p) - 1:]
        toks = jnp.asarray(o if pick is None else pick)
        best = jnp.max(rows, axis=-1)
        got = jnp.take_along_axis(rows, toks[:, None], axis=-1)[:, 0]
        gaps.append(np.asarray(best - got))
    return np.concatenate(gaps)


def served_numbers(gaps: np.ndarray) -> Dict[str, float]:
    """The numbers compared with their limits: the mean gap over every
    served token."""
    return {"logit_gap_mean": float(np.mean(np.asarray(gaps, np.float64)))}
