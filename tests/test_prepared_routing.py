"""Prepared-operand + route-planner suite (core.prepared, kernels.routing).

Acceptance (ISSUE 4): PreparedOperand reuse is bit-identical to raw-array
dispatch across ALL five modes and dtypes (incl. int8); the cache key
invalidates on shape/dtype/site changes; and select_route's four
regime choices are pinned to the cost model (tiny-K conv -> im2col,
batch-4 conv -> fused, small-MN-large-B GEMM -> batch-fold, sub-floor ->
virtual), with the REPRO_ROUTE override honored.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import conv as cc
from repro.core import matmul as M
from repro.core.einsum import fs_einsum
from repro.core.matmul import MODES
from repro.core.prepared import PreparedOperand, prepare_operand, unwrap
from repro.kernels import ops, routing

RNG = np.random.default_rng(11)


# ---------------------------------------------------------------------------
# PreparedOperand bit-identity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
def test_prepared_matmul_bit_identical(mode, dtype):
    """fs_einsum(prepared) must be BIT-identical to fs_einsum(raw) in every
    mode -- the prepared form only amortizes work, never changes it."""
    if dtype == "int8":
        a = jnp.asarray(RNG.integers(-30, 30, (24, 40)), jnp.int8)
        w = jnp.asarray(RNG.integers(-30, 30, (40, 48)), jnp.int8)
    else:
        a = jnp.asarray(RNG.normal(size=(24, 40)), jnp.dtype(dtype))
        w = jnp.asarray(RNG.normal(size=(40, 48)), jnp.dtype(dtype))
    prep = prepare_operand(w, site="dense")
    r1 = np.asarray(fs_einsum("tk,kn->tn", a, w, mode=mode))
    r2 = np.asarray(fs_einsum("tk,kn->tn", a, prep, mode=mode))
    np.testing.assert_array_equal(r1, r2)


@pytest.mark.parametrize("mode", MODES)
def test_prepared_transposed_vocab_gemm(mode):
    """The tied-embedding pattern: table (V, D) contracted on its LAST
    axis, prepared with transpose=True (transpose materialized once)."""
    h = jnp.asarray(RNG.normal(size=(16, 40)).astype(np.float32))
    table = jnp.asarray(RNG.normal(size=(56, 40)).astype(np.float32))
    prep = prepare_operand(table, transpose=True, site="logits")
    r1 = np.asarray(fs_einsum("td,vd->tv", h, table, mode=mode))
    r2 = np.asarray(fs_einsum("td,vd->tv", h, prep, mode=mode))
    np.testing.assert_array_equal(r1, r2)


@pytest.mark.parametrize("mode", MODES)
def test_prepared_batched_expert_gemm(mode):
    """Batched (E, K, N) prepared weights (the MoE expert stack)."""
    x = jnp.asarray(RNG.normal(size=(3, 10, 24)).astype(np.float32))
    w = jnp.asarray(RNG.normal(size=(3, 24, 16)).astype(np.float32))
    prep = prepare_operand(w, site="moe_expert")
    r1 = np.asarray(fs_einsum("ecd,edf->ecf", x, w, mode=mode))
    r2 = np.asarray(fs_einsum("ecd,edf->ecf", x, prep, mode=mode))
    np.testing.assert_array_equal(r1, r2)


@pytest.mark.parametrize("mode", cc.CONV2D_MODES)
@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_prepared_conv2d_bit_identical(mode, dtype):
    if dtype == "int8":
        x = jnp.asarray(RNG.integers(-20, 20, (1, 4, 10, 10)), jnp.int8)
        w = jnp.asarray(RNG.integers(-20, 20, (3, 4, 3, 3)), jnp.int8)
    else:
        x = jnp.asarray(RNG.normal(size=(1, 4, 10, 10)).astype(np.float32))
        w = jnp.asarray(RNG.normal(size=(3, 4, 3, 3)).astype(np.float32))
    prep = prepare_operand(w, for_="conv2d")
    r1 = np.asarray(cc.conv2d(x, w, mode=mode, padding="SAME"))
    r2 = np.asarray(cc.conv2d(x, prep, mode=mode, padding="SAME"))
    np.testing.assert_array_equal(r1, r2)


def test_prepared_matmul_level_dispatch():
    """core.matmul.matmul accepts prepared operands in every mode."""
    a = jnp.asarray(RNG.normal(size=(3, 20, 40)).astype(np.float32))
    w = jnp.asarray(RNG.normal(size=(40, 32)).astype(np.float32))
    prep = prepare_operand(w)
    for mode in MODES:
        r1 = np.asarray(M.matmul(a, w, mode=mode))
        r2 = np.asarray(M.matmul(a, prep, mode=mode))
        np.testing.assert_array_equal(r1, r2)


def test_prepared_incompatible_spec_falls_back():
    """A spec whose y-side layout does not match how the operand was
    prepared must fall back to the raw source (correct, just unamortized):
    here y is contracted on its last axis but prepared UNtransposed."""
    h = jnp.asarray(RNG.normal(size=(16, 40)).astype(np.float32))
    table = jnp.asarray(RNG.normal(size=(56, 40)).astype(np.float32))
    prep = prepare_operand(table)                       # canonical (56, 40)
    ref = np.asarray(fs_einsum("td,vd->tv", h, table, mode="square_pallas"))
    out = np.asarray(fs_einsum("td,vd->tv", h, prep, mode="square_pallas"))
    np.testing.assert_array_equal(ref, out)


def test_prepared_rides_jit_boundaries():
    """PreparedOperand is a pytree: it crosses jit as a leaf bundle."""
    a = jnp.asarray(RNG.normal(size=(16, 40)).astype(np.float32))
    w = jnp.asarray(RNG.normal(size=(40, 48)).astype(np.float32))
    prep = prepare_operand(w)
    f = jax.jit(lambda a, p: fs_einsum("tk,kn->tn", a, p,
                                       mode="square_pallas"))
    out = np.asarray(f(a, prep))
    ref = np.asarray(fs_einsum("tk,kn->tn", a, w, mode="square_pallas"))
    np.testing.assert_array_equal(out, ref)
    leaves = jax.tree_util.tree_leaves(prep)
    assert len(leaves) >= 3                       # source + canon + corr


def test_prepare_is_idempotent_and_unwrap():
    w = jnp.asarray(RNG.normal(size=(8, 8)).astype(np.float32))
    prep = prepare_operand(w)
    assert prepare_operand(prep) is prep
    assert unwrap(prep) is w
    assert unwrap(w) is w


def test_cache_key_invalidation():
    """The cache key must change with shape, dtype, layout (transpose)
    and site -- anything that changes the prepared artifact."""
    w32 = jnp.zeros((16, 24), jnp.float32)
    base = prepare_operand(w32, site="dense")
    assert prepare_operand(jnp.zeros((16, 24), jnp.bfloat16),
                           site="dense").key != base.key
    assert prepare_operand(jnp.zeros((24, 16), jnp.float32),
                           site="dense").key != base.key
    assert prepare_operand(w32, site="ffn").key != base.key
    assert prepare_operand(w32, site="dense", transpose=True).key != base.key
    assert prepare_operand(w32, site="dense").key == base.key


def test_prepared_kind_mismatch_raises():
    w = jnp.zeros((4, 4), jnp.float32)
    conv_prep = prepare_operand(jnp.zeros((2, 2, 3, 3), jnp.float32),
                                for_="conv2d")
    with pytest.raises(ValueError, match="PreparedOperand"):
        ops.sq_matmul(w, conv_prep)
    with pytest.raises(ValueError, match="PreparedOperand"):
        ops.sq_conv2d(jnp.zeros((8, 8), jnp.float32), prepare_operand(w))


# ---------------------------------------------------------------------------
# Route planner: the four regime pins
# ---------------------------------------------------------------------------

def test_route_tiny_k_conv_selects_im2col():
    """The historical 64x64 k5x5 single-channel shape: 360 KB patch
    matrix, K volume 25 -- the measured im2col-wins regime."""
    route = routing.select_conv2d_route(60, 60, 5, 5, 1, 1)
    assert route.name == "im2col"


def test_route_batch4_conv_selects_fused():
    """b4 32x32x64->64 k3x3: ~8 MB patch matrix, K volume 576 -- the
    measured fused-wins regime (6x at batch 4 in BENCH_kernels.json)."""
    route = routing.select_conv2d_route(30, 30, 3, 3, 64, 64, batch=4)
    assert route.name == "fused"


def test_route_small_mn_large_b_folds():
    """Small (M, N) per element with large B: grid-step overhead dominates
    the one-element-per-step schedule -> batch-folded row tiles."""
    route = routing.select_matmul_route(8, 8, 64, batch=64)
    assert route.name == "fold"
    # large per-element tiles amortize their grid step natively
    assert routing.select_matmul_route(128, 128, 128,
                                       batch=4).name == "batched"


def test_route_sub_floor_selects_virtual():
    """Below the kernel-overhead floor the MXU-form virtual fallback is
    strictly faster than any pallas_call."""
    assert routing.select_matmul_route(8, 8, 8).name == "virtual"
    assert routing.select_matmul_route(256, 256, 256).name == "kernel"


def test_route_generic_entry_point():
    r = routing.select_route("matmul", {"m": 256, "n": 256, "k": 256})
    assert r.name == "kernel"
    r = routing.select_route("conv2d", {"oh": 60, "ow": 60, "kh": 5,
                                        "kw": 5, "ci": 1, "co": 1})
    assert r.name == "im2col"
    with pytest.raises(ValueError, match="route kind"):
        routing.select_route("conv3d", {})


def test_repro_route_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_ROUTE", "virtual")
    assert routing.select_matmul_route(256, 256, 256).name == "virtual"
    monkeypatch.setenv("REPRO_ROUTE", "matmul=kernel,conv2d=fused")
    assert routing.select_matmul_route(8, 8, 8).name == "kernel"
    assert routing.select_conv2d_route(60, 60, 5, 5, 1, 1).name == "fused"
    monkeypatch.setenv("REPRO_ROUTE", "auto")
    assert routing.select_matmul_route(8, 8, 8).name == "virtual"
    monkeypatch.setenv("REPRO_ROUTE", "bogus")
    with pytest.raises(ValueError, match="REPRO_ROUTE"):
        routing.select_matmul_route(8, 8, 8)
    with pytest.raises(ValueError, match="REPRO_ROUTE"):
        monkeypatch.setenv("REPRO_ROUTE", "matmul=fused")   # wrong kind,
        routing.select_matmul_route(8, 8, 8)                # scoped: strict


def test_repro_route_bare_name_scopes_to_its_kind(monkeypatch):
    """A bare route name pins only the kind it is valid for: pinning the
    conv route must not crash every matmul dispatch (and vice versa)."""
    monkeypatch.setenv("REPRO_ROUTE", "fused")
    assert routing.select_conv2d_route(30, 30, 3, 3, 64, 64).name == "fused"
    assert routing.select_matmul_route(256, 256, 256).name == "kernel"
    monkeypatch.setenv("REPRO_ROUTE", "kernel")
    assert routing.select_matmul_route(8, 8, 8).name == "kernel"
    assert routing.select_conv2d_route(60, 60, 5, 5, 1, 1).name == "im2col"


def test_einsum_pallas_routes_through_planner(monkeypatch):
    """square_pallas einsum dispatch honors the forced route end-to-end
    (numerics stay correct on every route)."""
    x = jnp.asarray(RNG.normal(size=(16, 8, 48)).astype(np.float32))
    y = jnp.asarray(RNG.normal(size=(16, 48, 8)).astype(np.float32))
    ref = np.einsum("bmk,bkn->bmn", np.asarray(x), np.asarray(y))
    for forced in ("batched", "fold", "virtual"):
        monkeypatch.setenv("REPRO_ROUTE", f"matmul={forced}")
        out = np.asarray(fs_einsum("bmk,bkn->bmn", x, y,
                                   mode="square_pallas"))
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# Batch-folded kernel
# ---------------------------------------------------------------------------

def test_folded_kernel_matches_batched():
    """fold=True is the same arithmetic as the one-element-per-step
    batched kernel."""
    a = jnp.asarray(RNG.normal(size=(10, 6, 40)).astype(np.float32))
    b = jnp.asarray(RNG.normal(size=(10, 40, 12)).astype(np.float32))
    r1 = np.asarray(ops.sq_matmul(a, b))
    r2 = np.asarray(ops.sq_matmul(a, b, fold=True))
    np.testing.assert_allclose(r1, r2, rtol=1e-6, atol=1e-5)
    np.testing.assert_allclose(r2, np.asarray(a) @ np.asarray(b),
                               rtol=1e-5, atol=1e-4)


def test_folded_kernel_int8_exact():
    a = jnp.asarray(RNG.integers(-25, 25, (7, 5, 32)), jnp.int8)
    b = jnp.asarray(RNG.integers(-25, 25, (7, 32, 9)), jnp.int8)
    ref = np.asarray(a, np.int32) @ np.asarray(b, np.int32)
    out = np.asarray(ops.sq_matmul(a, b, fold=True))
    np.testing.assert_array_equal(out, ref)


def test_folded_prepared_batched():
    a = jnp.asarray(RNG.normal(size=(12, 4, 32)).astype(np.float32))
    b = jnp.asarray(RNG.normal(size=(12, 32, 8)).astype(np.float32))
    prep = prepare_operand(b)
    r1 = np.asarray(ops.sq_matmul(a, b, fold=True))
    r2 = np.asarray(ops.sq_matmul(a, prep, fold=True))
    np.testing.assert_array_equal(r1, r2)


# ---------------------------------------------------------------------------
# Model-level prepared weights
# ---------------------------------------------------------------------------

def _tiny_cfg(**kw):
    from repro.configs.base import ContractionPolicy, ModelConfig
    pol = ContractionPolicy.of(default="square_pallas",
                               attn_scores="standard", attn_pv="standard")
    base = dict(name="tiny", family="dense", n_layers=2, d_model=32,
                n_heads=2, n_kv_heads=2, d_ff=64, vocab=128, head_dim=16,
                dtype="float32", scan_layers=False, remat="none",
                attn_chunk_q=16, attn_chunk_kv=16, loss_chunk=16,
                max_seq=64, matmul_mode="square_pallas",
                contraction_policy=pol)
    base.update(kw)
    return ModelConfig(**base)


def test_lm_prepare_params_bit_identical():
    """LM.prepare_params: forward + logits identical to raw params."""
    from repro.models.lm import build_model
    cfg = _tiny_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jnp.asarray(RNG.integers(0, cfg.vocab, (2, 16)), jnp.int32)
    h1, _, _ = model.forward(params, {"tokens": tokens})
    l1 = model.logits(params, h1)
    pp = model.prepare_params(params)
    assert isinstance(pp["logits_prep"], PreparedOperand)
    h2, _, _ = model.forward(pp, {"tokens": tokens})
    l2 = model.logits(pp, h2)
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


def test_lm_prepare_params_moe():
    from repro.models.lm import build_model
    cfg = _tiny_cfg(name="tinymoe", family="moe", n_experts=4, topk=2,
                    block_pattern=("moe",))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    tokens = jnp.asarray(RNG.integers(0, cfg.vocab, (2, 16)), jnp.int32)
    h1, _, _ = model.forward(params, {"tokens": tokens})
    pp = model.prepare_params(params)
    h2, _, _ = model.forward(pp, {"tokens": tokens})
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))
