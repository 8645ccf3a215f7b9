"""The system under test, as the benchmark calls it.

The only module of the benchmark that imports the program (``src/repro``).
It turns a configuration file into the program's ``ModelConfig`` from the
keywords its architecture gives (``bench/arch/<arch>.py``), checks that
the benchmark's weight layout is the program's, and lists the compiled
programs a window drives.
"""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402

from bench import arch, weights  # noqa: E402


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import SQUARE_GEMMS_POLICY, ModelConfig
    policies = {"square_gemms": SQUARE_GEMMS_POLICY, None: None}
    if cfg["contraction_policy"] not in policies:
        raise KeyError(f"unknown contraction policy "
                       f"{cfg['contraction_policy']!r}")
    return ModelConfig(
        name=cfg["name"], matmul_mode=cfg["matmul_mode"],
        contraction_policy=policies[cfg["contraction_policy"]],
        **arch.of(cfg).model_kwargs(cfg))


def build(cfg: dict):
    """(model, ModelConfig) with the layout checked against the
    benchmark's weights."""
    from repro.models.lm import build_model
    mcfg = model_config(cfg)
    model = build_model(mcfg)
    want = weights.layout(cfg)
    got = {"/".join(str(getattr(k, "key", k)) for k in path):
           (tuple(a.shape), str(a.dtype))
           for path, a in jax.tree_util.tree_flatten_with_path(
               model.abstract_params())[0]}
    if got != want:
        diff = sorted(set(got.items()) ^ set(want.items()))
        raise RuntimeError(f"the program's parameter layout is not the "
                           f"benchmark's: {diff[:6]}")
    return model, mcfg


def engine(model, params, spec: dict, prepared: bool):
    from repro.serve.engine import Engine, EngineConfig
    return Engine(model, params, EngineConfig(prepared=prepared, **spec))


def warm_position_resets(eng, counts) -> None:
    """Compile the engine's position-ledger reset for each count of freed
    blocks in ``counts``, so that none compiles inside the window (the
    engine compiles one program per count).  Resetting the null block
    (id 0) is a no-op: its positions are always the empty sentinel."""
    for n in counts:
        eng._reset_pos([0] * n)
    jax.block_until_ready(eng.pos_pool)


def serve_programs(eng):
    """{name: compiled HLO text} of the engine's three step programs, for
    arguments of the shapes the window drives."""
    import jax.numpy as jnp
    c = eng.cfg
    B, nb, C = c.max_slots, c.blocks_per_seq, c.prefill_chunk
    i32 = jnp.int32
    S = jax.ShapeDtypeStruct
    d = eng.model.cfg.d_model
    dt = jnp.dtype(eng.model.cfg.dtype)
    base = (eng.params, eng.cache, eng.pos_pool)
    return {
        "decode": eng._decode.lower(*base, S((B, nb), i32), S((B, 1), i32),
                                    S((B, 1), i32)).compile().as_text(),
        "chunk": eng._chunk.lower(*base, S((1, nb), i32), S((1, C), i32),
                                  S((1, C), i32)).compile().as_text(),
        "logits_at": eng._logits_at.lower(eng.params, S((1, C, d), dt),
                                          S((), i32)).compile().as_text(),
    }

