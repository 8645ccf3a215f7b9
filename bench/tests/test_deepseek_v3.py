"""The DeepSeek-V3 layout (``bench/arch/deepseek_v3.py``, Moonlight's cell)
at a size the CPU runs in seconds: its reference against the repository's
own plain reference and the program, and its tiny cell through the
harness as ``test_new_arch.py`` runs ``dense``'s."""
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import arch, checks, harness, program, weights
from bench.tests import tiny

CELL = "moonlight-16b-a3b-sq.reason32"
MOD = arch.load("deepseek_v3")


def _repo_reference():
    path = os.path.join(harness.ROOT, "tests", "ref_deepseek_v3.py")
    spec = importlib.util.spec_from_file_location("ref_deepseek_v3", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def serve_cell():
    entry, cfg, mix, limits = harness.load_cell(CELL)
    mix = dict(mix, clients=2, rounds=4,
               prompt=dict(mix["prompt"], median=12, lo=4, hi=24),
               output=dict(mix["output"], median=8, lo=4, hi=24),
               engine=dict(max_slots=2, block_size=8, prefill_chunk=8,
                           blocks_per_seq=8, num_blocks=17,
                           max_new_tokens=24))
    return entry, dict(cfg, **MOD.TINY), mix, limits


def _tiny():
    return dict(serve_cell()[1], matmul_mode="standard",
                contraction_policy=None)


def test_the_benchmark_reference_is_the_repository_reference():
    """bench/arch/deepseek_v3.py's embed/block/head, layer by layer on its
    own draws, against tests/ref_deepseek_v3.py on the whole tree: the two
    plain references cannot drift apart.  Both are float32 at HIGHEST
    precision, so they agree to f32 rounding of differently ordered sums
    (1e-4 of logits that spread over about 4)."""
    cfg = _tiny()
    mcfg = weights.model(cfg)
    key = weights.base_key(2 ** 33 + 11)
    toks = np.random.default_rng(1).integers(0, cfg["vocab"], 24,
                                             dtype=np.int32)
    got = np.asarray(checks.reference_logits(mcfg, key, [toks])[0])
    model, mc = program.build(cfg)
    params = jax.jit(functools.partial(weights.init, mcfg))(key)
    want = np.asarray(jax.jit(_repo_reference().logits, static_argnums=0)(
        mc, params, toks))
    assert got.shape == want.shape == (24, cfg["vocab"])
    np.testing.assert_allclose(got, want, atol=1e-4 * np.abs(want).max())


def test_layer_draws_match_the_whole_tree():
    cfg = weights.model(_tiny())
    key = weights.base_key(2 ** 34 + 3)
    tree = jax.jit(functools.partial(weights.init, cfg))(key)
    layer = jax.jit(functools.partial(weights.layer, cfg), static_argnums=1)
    lay = MOD.layers(cfg)
    assert lay[0] == ("lead/layer0/", None)
    assert [l for _, l in lay[1:]] == list(range(4))
    lead = layer(key, "lead/layer0/", None)
    assert np.array_equal(lead["attn/wk_b/w"],
                          tree["lead"]["layer0"]["attn"]["wk_b"]["w"])
    one = layer(key, MOD.STACK, jnp.int32(2))
    assert np.array_equal(one["ffn/router/bias"],
                          tree["scan"]["pos0"]["ffn"]["router"]["bias"][2])
    assert one["ffn/w_gate/w"].shape[0] == cfg["experts_held"]


def test_the_operations_count_held_picks_and_one_latent_row():
    cfg = harness.load_cell(CELL)[1]
    d, f, H = 2048, 1408, 16
    attn = d * H * 192 + d * 576 + H * 128 * 512 + H * 512 * 128 + H * 128 * d
    moe = attn + d * 64 + 6 * 8 / 64 * 3 * d * f + 3 * d * 2 * f
    assert MOD.matmul_params(cfg) == pytest.approx(
        attn + 3 * d * 11264 + 26 * moe)
    ops, moved = MOD.paged_attn_cost(cfg, 300, 2)
    assert ops == 2 * 27 * H * 300 * (576 + 512)
    assert moved == 27 * 300 * 576 * 2


def test_the_tiny_cell_runs_through_the_harness():
    res, rec = tiny.run(CELL, serve_cell())
    assert res["correct"], res["checks"]
    assert rec.model_flops > 0 and rec.notes["served"]
    assert res["checks"]["logit_gap_mean"]["value"] < 1e-3
