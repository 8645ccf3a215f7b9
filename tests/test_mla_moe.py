"""Latent attention (MLA) and held dropless experts, on the CPU at a tiny
DeepSeek-V3 shape: the program against the plain float32 reference
(``tests/ref_deepseek_v3.py``), the latent paged-attention kernel and the
grouped expert GEMM against ``jax.numpy``, the expert shares against the
uncut layer, and the engine's on-device MoE counters.

Tolerances, and why a bf16 reference would fail them: f32 computations
that differ only in summation order agree to ~1e-6 of their scale; the
square path's ``(a + b)^2 - a^2 - b^2`` loses a few more bits to
cancellation, ~1e-5 of the operands' squared scale.  bf16 keeps 8
mantissa bits: a bf16 reference departs by ~4e-3 of scale per rounding,
an order of magnitude past every tolerance below.
"""
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ContractionPolicy
from repro.kernels import ops, routing
from repro.kernels.sq_grouped_matmul import group_rows, max_tiles
from repro.kernels.sq_paged_attn import sq_paged_attn
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models.lm import build_model
from repro.serve.engine import Engine, EngineConfig
from repro.serve.server import Request

_spec = importlib.util.spec_from_file_location(
    "ref_deepseek_v3", os.path.join(os.path.dirname(__file__),
                                    "ref_deepseek_v3.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)
ref_logits = jax.jit(ref.logits, static_argnums=0)

# the grouped GEMM and the latent kernel on the square path; every other
# contraction on the multiplier baseline (keeps interpret-mode time down)
KERNELS = ContractionPolicy.of(attn_paged="square_pallas",
                               moe_expert="square_pallas")


@pytest.fixture(autouse=True)
def _no_autotune(monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    routing.reset_route_health()
    yield
    routing.reset_route_health()


def _cfg(**kw):
    base = dict(n_layers=5, d_model=64, n_heads=4, n_kv_heads=4,
                kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=16,
                v_head_dim=16, d_ff=32, dense_d_ff=128, n_experts=8,
                experts_held=4, topk=3, vocab=512, dtype="float32",
                max_seq=256, attn_chunk_q=32, attn_chunk_kv=32,
                loss_chunk=64)
    base.update(kw)
    return dataclasses.replace(get_config("moonlight-16b-a3b"), **base)


def _params(model, seed=0):
    """Initialised weights, with a router bias that moves some choices."""
    params = model.init(jax.random.PRNGKey(seed))
    router = params["scan"]["pos0"]["ffn"]["router"]
    router["bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(seed + 1),
                                             router["bias"].shape)
    return params


def _scale(x):
    return float(np.abs(np.asarray(x)).max())


# ------------------------------------------------------- model vs reference

def test_forward_matches_the_reference():
    cfg = _cfg()
    model = build_model(cfg)
    params = _params(model)
    toks = np.random.default_rng(0).integers(0, cfg.vocab, 20, np.int32)
    h, _, _ = jax.jit(model.forward)(params,
                                     {"tokens": jnp.asarray(toks)[None]})
    got = np.asarray(model.logits(params, h)[0, :, :cfg.vocab])
    want = np.asarray(ref_logits(cfg, params, toks))
    # f32 on both sides, sums in another order: 1e-5 of the logit scale
    np.testing.assert_allclose(got, want, atol=1e-5 * _scale(want))


@pytest.mark.parametrize("route", ["kernel", "gather"])
def test_engine_prefill_and_decode_match_the_reference(route, monkeypatch):
    """Chunked prefill then paged decode through the Engine, with the
    latent kernel and the grouped expert GEMM on the square path and the
    latent pool read by ``route``, against the reference's full forward
    at every served position."""
    monkeypatch.setenv("REPRO_ROUTE", f"paged_attn={route}")
    cfg = _cfg(contraction_policy=KERNELS)
    model = build_model(cfg)
    params = _params(model, seed=3)
    prompt = np.random.default_rng(4).integers(0, cfg.vocab, 13, np.int32)
    eng = Engine(model, params, EngineConfig(
        max_slots=2, block_size=8, num_blocks=12, blocks_per_seq=4,
        prefill_chunk=4, max_new_tokens=5, prepared=True))
    seen = []
    sample = eng._sample
    eng._sample = lambda lg: (seen.append(np.asarray(lg)[0]), sample(lg))[1]
    out = eng.run([Request(0, prompt.tolist())])[0].tokens
    assert len(out) == 5 and len(seen) == 5
    seq = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
    want = np.asarray(ref_logits(cfg, params, seq))[len(prompt) - 1:]
    got = np.stack(seen)[:, :cfg.vocab]
    # square-path partial products (grouped GEMM, latent kernel) lose a
    # few bits to cancellation: 1e-4 of the logit scale
    np.testing.assert_allclose(got, want, atol=1e-4 * _scale(want))


def test_the_shares_add_up_to_the_uncut_layer():
    """Two devices holding experts [0, 4) and [4, 8): their layer outputs,
    with the shared experts counted once, sum to the uncut reference
    layer; each share is the reference's share."""
    full = _cfg(experts_held=8)
    model = build_model(full)
    p = jax.tree.map(lambda a: a[1], _params(model)["scan"]["pos0"]["ffn"])
    x = jnp.asarray(np.random.default_rng(2).normal(size=(24, 64)),
                    jnp.float32)
    half = _cfg()
    total = 0.0
    for i in range(2):
        share = dict(p, **{k: {"w": p[k]["w"][4 * i:4 * i + 4]}
                           for k in ("w_gate", "w_up", "w_down")})
        out, _, stats = moe_mod.moe_apply_held(share, x, cfg=half,
                                               held_lo=4 * i, shared=i == 0)
        want = ref.moe_layer(half, share, x, held_lo=4 * i, shared=i == 0)
        np.testing.assert_allclose(out, want, atol=1e-5 * _scale(want))
        assert int(stats[0]) == 24 * half.topk
        total = total + out
    uncut = ref.moe_layer(full, p, x)
    np.testing.assert_allclose(total, uncut, atol=1e-5 * _scale(uncut))


# -------------------------------------------------------------- kernels

def _latent_setup(B=2, S=2, H=4, Dk=24, nb=6, bs=4, n=20, seed=0):
    rng = np.random.default_rng(seed)
    P = (1 + B * nb) * bs
    pool = rng.normal(size=(P, 1, Dk)).astype(np.float32)
    pos_pool = np.full(P, attn.EMPTY_POS, np.int32)
    tables = np.zeros((B, nb), np.int32)
    for b in range(B):
        blocks = 1 + b * nb + np.arange(-(-n // bs))
        tables[b, :len(blocks)] = blocks
        for c, blk in enumerate(blocks):
            for j in range(bs):
                if c * bs + j < n:
                    pos_pool[blk * bs + j] = c * bs + j
    q = rng.normal(size=(B, S, 1, H, Dk)).astype(np.float32)
    q_pos = np.tile(np.arange(n - S, n), (B, 1)).astype(np.int32)
    return q, pool, tables, pos_pool, q_pos


def _latent_reference(q, pool, tables, pos_pool, q_pos, *, bs, dv, window):
    idx = attn.paged_gather_indices(jnp.asarray(tables), bs)
    kv = np.asarray(pool)[np.asarray(idx)][:, :, 0]           # (B, T, Dk)
    kpos = np.asarray(pos_pool)[np.asarray(idx)]
    s = np.einsum("bshd,btd->bsht", q[:, :, 0], kv)
    ok = (kpos[:, None, :] <= q_pos[:, :, None]) \
        & (kpos[:, None, :] < attn.ATTEND_POS_LIMIT)
    if window is not None:
        ok &= (q_pos[:, :, None] - kpos[:, None, :]) < window
    s = np.where(ok[:, :, None], s, -1e30)
    w = np.exp(s - s.max(-1, keepdims=True))
    w = w / w.sum(-1, keepdims=True)
    return np.einsum("bsht,btd->bshd", w, kv[..., :dv])[:, :, None]


@pytest.mark.parametrize("case", ["live", "evicted", "padded"])
def test_latent_paged_attention_matches_jnp(case):
    """One latent pool read as K (all 24 columns) and V (the first 16),
    four query rows per token over one latent head: live columns only,
    leading columns dead by the window and evicted to the null block, and
    a padded query row."""
    bs, dv, window = 4, 16, None
    q, pool, tables, pos_pool, q_pos = _latent_setup(bs=bs)
    if case == "evicted":
        window = 7
        for b in range(tables.shape[0]):
            n_dead = (int(q_pos[b].min()) - window + 1) // bs
            for blk in tables[b, :n_dead]:
                pos_pool[blk * bs:(blk + 1) * bs] = attn.EMPTY_POS
            tables[b, :n_dead] = 0
    if case == "padded":
        q_pos[1, 0] = -1
    got = np.asarray(sq_paged_attn(
        jnp.asarray(q), jnp.asarray(pool), None, jnp.asarray(tables),
        jnp.asarray(pos_pool), jnp.asarray(q_pos), block_size=bs,
        window=window, dv=dv))
    want = _latent_reference(q, pool, tables, pos_pool, q_pos, bs=bs, dv=dv,
                             window=window)
    assert got.shape == (2, 2, 1, 4, dv)
    rows = q_pos >= 0
    # square path over q.k of 24 terms: 1e-5 of the operands' scale
    np.testing.assert_allclose(got[rows], want[rows], atol=1e-5)


@pytest.mark.parametrize("case", ["random", "empty", "one", "worst"])
def test_grouped_matmul_matches_einsum(case):
    """Picks sorted into 8-row tiles by expert: experts with no picks,
    every pick on one expert, and the static worst case (every group ends
    one row into a tile, so every tile of the bound is used)."""
    E, K, N, bm, P = 4, 32, 24, 8, 20
    rng = np.random.default_rng(7)
    ex = jnp.asarray({"random": rng.integers(0, E + 2, P),
                      "empty": [0] * 5 + [3] * 15,
                      "one": [2] * P,
                      "worst": [0] * 9 + [1] * 9 + [2] + [3]}[case],
                     jnp.int32)
    w = jnp.asarray(rng.normal(size=(E, K, N)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(P, K)), jnp.float32)
    dest, te, used = group_rows(ex, E, bm)
    R = max_tiles(P, E, bm) * bm
    held = np.asarray(ex < E)
    rows = jnp.zeros((R, K)).at[jnp.where(ex < E, dest, R)].set(
        x, mode="drop")
    got = np.asarray(ops.sq_grouped_matmul(rows, w, te, used, bm=bm))
    want = np.einsum("pk,pkn->pn", x, w[np.minimum(np.asarray(ex), E - 1)])
    counts = np.bincount(np.asarray(ex)[held], minlength=E)
    assert int(used[0]) == int(np.sum(-(-counts // bm)))
    if case == "worst":
        assert int(used[0]) == R // bm
    np.testing.assert_allclose(got[np.asarray(dest)][held], want[held],
                               atol=1e-4)


# ------------------------------------------------------------- counters

def test_moe_counters_stay_on_the_device_until_read():
    cfg = _cfg()
    model = build_model(cfg)
    eng = Engine(model, _params(model), EngineConfig(
        max_slots=2, block_size=8, num_blocks=12, blocks_per_seq=4,
        prefill_chunk=4, max_new_tokens=3))
    prompts = [[5, 6, 7, 8, 9], [1, 2, 3]]
    eng.submit([Request(i, p) for i, p in enumerate(prompts)])
    while eng.step():
        pass
    assert eng.metrics.moe_picks is None           # nothing read per tick
    s = eng.metrics.summary()
    n_moe = cfg.n_layers - cfg.first_k_dense
    # every prompt token once, every generated token but the last once
    tokens = sum(len(p) + 3 - 1 for p in prompts)
    assert s["moe_picks"] == tokens * cfg.topk * n_moe
    assert 0 < s["moe_picks_held"] < s["moe_picks"]
    assert s["moe_rows_padded"] % moe_mod.GROUP_ROWS == 0
    assert s["moe_rows_padded"] >= s["moe_picks_held"]
    c = eng.registry.snapshot()["counters"]
    assert c["engine_moe_picks_held_total"] == s["moe_picks_held"]
