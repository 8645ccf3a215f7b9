"""The plain float32 reference of the dense architecture
(``bench/arch/dense.py``) against the program's own forward pass, for both
models it describes, on the CPU at the registry's reduced size."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import arch, checks, program, reference, weights
from repro.configs import get_config

ARCHS = ["h2o-danube-3-4b", "starcoder2-3b"]
DENSE = arch.load("dense")


def _cfg(name):
    reg = get_config(name).reduced()
    cfg = {k: getattr(reg, k) for k in DENSE.MODEL_KEYS}
    cfg["head_dim"] = reg.resolved_head_dim
    return dict(cfg, arch="dense", name=name, matmul_mode="standard",
                contraction_policy=None)


def _tokens(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(0, cfg["vocab"], n,
                                                dtype=np.int32)


def test_layer_draws_match_the_whole_tree():
    cfg = weights.model(_cfg("starcoder2-3b"))
    key = weights.base_key(2 ** 34 + 9)
    tree = jax.jit(functools.partial(weights.init, cfg))(key)
    layer = jax.jit(functools.partial(weights.layer, cfg), static_argnums=1)
    for l, (prefix, i) in enumerate(DENSE.layers(cfg)):
        assert (prefix, i) == (DENSE.STACK, l)
        one = layer(key, prefix, i)
        assert np.array_equal(one["attn/wq/w"],
                              tree["scan"]["pos0"]["attn"]["wq"]["w"][l])
        assert np.array_equal(one["ln2/bias"],
                              tree["scan"]["pos0"]["ln2"]["bias"][l])
    top = jax.jit(functools.partial(weights.top, cfg))(key)
    assert np.array_equal(top["embed/table"], tree["embed"]["table"])


@pytest.mark.parametrize("name", ARCHS)
def test_reference_logits_match_the_program(name, monkeypatch):
    cfg = _cfg(name)
    if cfg["norm"] == "rmsnorm":
        # the program's RMSNorm epsilon (1e-6) departs from the published
        # 1e-5 the reference uses; match it so the rest is compared tightly
        monkeypatch.setattr(reference, "EPS", 1e-6)
    model, _ = program.build(cfg)
    mcfg = weights.model(cfg)
    key = weights.base_key(123)
    params = jax.jit(functools.partial(weights.init, mcfg))(key)
    toks = _tokens(cfg, 40)
    hidden, _, _ = model.forward(params, {"tokens": jnp.asarray(toks)[None]})
    got = np.asarray(model.logits(params, hidden)[0])
    want = np.asarray(checks.reference_logits(mcfg, key, [toks])[0])
    assert got.shape == want.shape == (40, cfg["vocab"])
    scale = np.abs(want).max()
    assert np.abs(got - want).max() < 1e-4 * scale
    ctl = np.asarray(checks.reference_logits(mcfg, key, [toks],
                                             ar=reference.FP8)[0])
    assert np.abs(ctl - want).max() > 1e-3 * scale

