"""Equivalence + planner tests for the vectorized (chunked) Pallas kernels.

The chunked kernels must compute exactly what the pre-refactor rank-1
kernels computed: the streamed square-form contraction of
``core.matmul.pm_matmul_scan``.  Integer paths bit-match; float paths match
to reassociation tolerance (chunking changes the add order, nothing else).
"""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.matmul import pm_matmul_scan
from repro.kernels import ops, tuning

RNG = np.random.default_rng(11)

RAGGED_SHAPES = [(1, 1, 1), (7, 13, 9), (100, 60, 130), (64, 128, 32),
                 (130, 257, 140)]

# (bm, bn, bk, kc) plans: degenerate 1-chunk (kc == bk), rank-1 (kc == 1),
# and mid chunkings, across both PM-block layouts.
PLANS = [
    dict(bm=32, bn=128, bk=32, kc=32, pm_layout="mnk"),    # 1-chunk
    dict(bm=32, bn=128, bk=32, kc=32, pm_layout="mkn"),    # 1-chunk, TPU lay
    dict(bm=64, bn=128, bk=128, kc=1, pm_layout="mkn"),    # rank-1 (seed)
    dict(bm=64, bn=128, bk=128, kc=32, pm_layout="mnk"),
    dict(bm=8, bn=128, bk=64, kc=16, pm_layout="mkn"),
    # "kmn" row walk: one unrolled group; two lane groups over a K of 200
    # padded to 256; bm 32 with a bn of 512 (clamped to these shapes)
    dict(bm=8, bn=128, bk=64, kc=64, pm_layout="kmn"),
    dict(bm=8, bn=128, bk=256, kc=128, pm_layout="kmn"),
    dict(bm=32, bn=512, bk=256, kc=128, pm_layout="kmn"),
]


@pytest.mark.parametrize("shape", RAGGED_SHAPES)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
def test_chunked_matches_pm_matmul_scan(shape, dtype):
    m, k, n = shape
    if dtype == "int8":
        a = jnp.asarray(RNG.integers(-128, 128, (m, k)).astype(np.int8))
        b = jnp.asarray(RNG.integers(-128, 128, (k, n)).astype(np.int8))
    else:
        a = jnp.asarray(RNG.normal(size=(m, k)), jnp.dtype(dtype))
        b = jnp.asarray(RNG.normal(size=(k, n)), jnp.dtype(dtype))
    out = np.asarray(ops.sq_matmul(a, b))
    ref = np.asarray(pm_matmul_scan(a, b))
    if dtype == "int8":
        np.testing.assert_array_equal(out, ref)       # bit-exact
    else:
        np.testing.assert_allclose(out, ref, rtol=5e-3, atol=5e-3 * k)


@pytest.mark.parametrize("plan", PLANS)
def test_plans_agree_f32(plan):
    a = jnp.asarray(RNG.normal(size=(100, 200)).astype(np.float32))
    b = jnp.asarray(RNG.normal(size=(200, 60)).astype(np.float32))
    out = np.asarray(ops.sq_matmul(a, b, **plan))
    ref = np.asarray(pm_matmul_scan(a, b))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-2)


@pytest.mark.parametrize("plan", PLANS)
def test_plans_agree_int8_bitexact(plan):
    a = jnp.asarray(RNG.integers(-128, 128, (50, 70)).astype(np.int8))
    b = jnp.asarray(RNG.integers(-128, 128, (70, 40)).astype(np.int8))
    out = np.asarray(ops.sq_matmul(a, b, **plan))
    np.testing.assert_array_equal(out, np.asarray(pm_matmul_scan(a, b)))


@pytest.mark.parametrize("kind", ["cpm3_matmul", "cpm4_matmul"])
@pytest.mark.parametrize("kc,pm_layout", [(1, "mkn"), (64, "mkn"),
                                          (16, "mnk"), (64, "mnk")])
def test_cpm_chunked_layouts_agree(kind, kc, pm_layout):
    m, k, n = 40, 64, 24
    x = jnp.asarray((RNG.normal(size=(m, k))
                     + 1j * RNG.normal(size=(m, k))).astype(np.complex64))
    y = jnp.asarray((RNG.normal(size=(k, n))
                     + 1j * RNG.normal(size=(k, n))).astype(np.complex64))
    op = getattr(ops, kind)
    re, im = op(x, y, bk=64, kc=kc, pm_layout=pm_layout)
    z = np.asarray(x) @ np.asarray(y)
    np.testing.assert_allclose(np.asarray(re), z.real, rtol=1e-3, atol=1e-3 * k)
    np.testing.assert_allclose(np.asarray(im), z.imag, rtol=1e-3, atol=1e-3 * k)


@pytest.mark.parametrize("tb", [1, 4, 16])
def test_conv_tap_blocks_agree(tb):
    x = jnp.asarray(RNG.normal(size=(500,)).astype(np.float32))
    w = jnp.asarray(RNG.normal(size=(11,)).astype(np.float32))   # ragged vs tb
    out = np.asarray(ops.sq_conv(x, w, tb=tb))
    ref = np.correlate(np.asarray(x), np.asarray(w), mode="valid")
    np.testing.assert_allclose(out, ref, rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("shape", [(16, 16, 3, 3), (32, 24, 5, 3),
                                   (64, 64, 7, 7)])
def test_sq_conv2d_matches_lax_conv(shape):
    import jax.lax as lax
    H, W, kh, kw = shape
    x = jnp.asarray(RNG.normal(size=(H, W)).astype(np.float32))
    w = jnp.asarray(RNG.normal(size=(kh, kw)).astype(np.float32))
    out = np.asarray(ops.sq_conv2d(x, w))
    ref = lax.conv_general_dilated(
        x[None, None], w[None, None], (1, 1), "VALID",
        dimension_numbers=("NCHW", "OIHW", "NCHW"))[0, 0]
    np.testing.assert_allclose(out, np.asarray(ref), rtol=2e-3,
                               atol=2e-3 * kh * kw)


def test_sq_conv2d_filter_bank():
    import jax.lax as lax
    x = jnp.asarray(RNG.normal(size=(20, 20)).astype(np.float32))
    w = jnp.asarray(RNG.normal(size=(4, 3, 3)).astype(np.float32))
    out = np.asarray(ops.sq_conv2d(x, w))
    ref = lax.conv_general_dilated(
        x[None, None], w[:, None], (1, 1), "VALID",
        dimension_numbers=("NCHW", "OIHW", "NCHW"))[0]
    assert out.shape == (4, 18, 18)
    np.testing.assert_allclose(out, np.asarray(ref), rtol=2e-3, atol=2e-2)


# ---------------------------------------------------------------------------
# Planner unit tests
# ---------------------------------------------------------------------------

def test_planner_sublane_alignment():
    """Odd M must not yield a sublane-misaligned bm (the seed bug: M=100
    -> bm=100)."""
    plan = tuning.plan_matmul(100, 256, 256)
    assert plan.bm % tuning.SUBLANE == 0
    plan = tuning.plan_matmul(100, 256, 256, bm=100)    # explicit odd bm
    assert plan.bm % tuning.SUBLANE == 0


def test_planner_small_operands_exact():
    plan = tuning.plan_matmul(3, 5, 2)
    assert plan.bm <= 3 and plan.bn <= 5 and plan.bk <= 2
    assert plan.bk % plan.kc == 0


def test_planner_kc_divides_bk():
    for (m, n, k) in [(128, 128, 128), (1000, 333, 77), (8, 8, 8)]:
        for layout in ("mkn", "mnk"):
            plan = tuning.plan_matmul(m, n, k, pm_layout=layout)
            assert plan.bk % plan.kc == 0, plan


def test_planner_explicit_tiles_respected():
    plan = tuning.plan_matmul(512, 512, 512, bm=64, bn=128, bk=128, kc=16)
    assert (plan.bm, plan.bn, plan.bk, plan.kc) == (64, 128, 128, 16)


def test_planner_mnk_cache_budget():
    """mnk plans cap the reduce depth and keep the hot (bn, kc) panel
    cache-resident (the chunk-wide bound was retired: large-bm single-step
    plans are the measured winners on tall-skinny im2col shapes)."""
    for plan in tuning.candidate_plans(1024, 1024, 1024, pm_layout="mnk"):
        if plan.kc > 1:
            assert plan.kc <= tuning.KC_MNK_MAX
            assert (plan.bn + tuning.SUBLANE) * plan.kc * 4 \
                <= tuning.CACHE_BUDGET


def test_planner_vmem_budget():
    from repro.core import cost_model as cm
    for plan in tuning.candidate_plans(2048, 2048, 2048):
        cost = cm.pm_grid_cost(2048, 2048, 2048, *plan.astuple())
        assert cost.vmem_bytes <= tuning.VMEM_BUDGET


def test_autotune_cache_roundtrip(tmp_path, monkeypatch):
    """plan_matmul must serve plans straight from a JSON cache file."""
    path = tmp_path / "tuning_cache.json"
    monkeypatch.setenv("REPRO_TUNING_CACHE", str(path))
    tuning.clear_cache()
    entry = {"bm": 16, "bn": 128, "bk": 64, "kc": 16, "pm_layout": "mnk",
             "us_per_call": 1.0}
    path.write_text(json.dumps({"sq_matmul:64x64x64:float32": entry}))
    plan = tuning.plan_matmul(64, 64, 64, jnp.float32, pm_layout="mnk")
    assert plan == tuning.TilePlan(16, 128, 64, 16, "mnk")
    # a layout mismatch must NOT serve the cached plan (CPU-tuned "mnk"
    # entries never leak into TPU "mkn" plans)
    plan = tuning.plan_matmul(64, 64, 64, jnp.float32, pm_layout="mkn")
    assert plan.pm_layout == "mkn" and plan.bm != 16
    # explicit user tiles bypass the cache
    plan = tuning.plan_matmul(64, 64, 64, jnp.float32, bm=32,
                              pm_layout="mnk")
    assert plan.bm == 32
    tuning.clear_cache()


def test_autotune_sweep_smoke(tmp_path, monkeypatch):
    """End-to-end: autotune a tiny shape, then plan from the cache."""
    path = tmp_path / "cache.json"
    monkeypatch.setenv("REPRO_TUNING_CACHE", str(path))
    tuning.clear_cache()
    cache = tuning.autotune_matmul([(32, 32, 32)], jnp.float32,
                                   max_candidates=2, reps=1)
    key = "sq_matmul:32x32x32:float32"
    assert key in cache and cache[key]["us_per_call"] > 0
    plan = tuning.plan_matmul(32, 32, 32, jnp.float32,
                              pm_layout=cache[key]["pm_layout"])
    assert plan.bm == cache[key]["bm"] and plan.kc == cache[key]["kc"]
    tuning.clear_cache()


def test_autotune_miss_warns_once(tmp_path, monkeypatch):
    """On a cache miss the planner warns ONCE per key and falls back to the
    cost-model plan (no silent per-call sweeping)."""
    import warnings

    monkeypatch.setenv("REPRO_TUNING_CACHE", str(tmp_path / "none.json"))
    tuning.clear_cache()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        p1 = tuning.plan_matmul(37, 41, 43, jnp.float32, pm_layout="mnk")
        assert len(w) == 1 and "autotune cache miss" in str(w[0].message)
        p2 = tuning.plan_matmul(37, 41, 43, jnp.float32, pm_layout="mnk")
        assert len(w) == 1                       # warned once, not twice
    assert p1 == p2                              # deterministic model plan
    # explicit tiles never consult the cache, so they never warn
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        tuning.plan_matmul(37, 41, 43, jnp.float32, bm=8, bn=128, bk=128)
        assert len(w) == 0
    tuning.clear_cache()


def test_autotune_escape_hatch(tmp_path, monkeypatch):
    """REPRO_AUTOTUNE=0 disables cache lookups AND the miss warning."""
    import warnings

    path = tmp_path / "cache.json"
    entry = {"bm": 16, "bn": 128, "bk": 64, "kc": 16, "pm_layout": "mnk",
             "us_per_call": 1.0}
    path.write_text(json.dumps({"sq_matmul:64x64x64:float32": entry}))
    monkeypatch.setenv("REPRO_TUNING_CACHE", str(path))
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    tuning.clear_cache()
    assert not tuning.autotune_enabled()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        plan = tuning.plan_matmul(64, 64, 64, jnp.float32, pm_layout="mnk")
        assert len(w) == 0                       # no miss warning
    assert plan != tuning.TilePlan(16, 128, 64, 16, "mnk")   # cache ignored
    tuning.clear_cache()


# The training bench's GEMM population (benchmarks/training.py model at
# B=2, S=64): forward ffn pair at M = B*S*2 rows after attn concat, the
# TRANSPOSED backward pair the custom VJP emits for dL/dx / dL/dW, and
# the chunked vocab-grad trio (loss_chunk rows t=64 against v=4096).
TRAINING_GEMMS = [           # (m, n, k), matching plan_matmul's order
    (256, 256, 128),      # qkv/out fwd + bwd_x (square d_model block)
    (256, 1024, 128),     # ffn up bwd pair
    (1024, 256, 128),     # ffn down bwd pair (transposed partner)
    (64, 4096, 256),      # chunked logits fwd (t x d @ d x v)
    (64, 256, 4096),      # logits bwd_x (t x v @ v x d)
    (4096, 256, 64),      # logits bwd_w (v x t @ t x d, transposed)
]


def test_training_shapes_served_from_committed_cache(monkeypatch):
    """Every training-bench GEMM (forward, transposed-backward pair, and
    vocab-grad trio) must hit the COMMITTED package cache: plan_matmul
    serves the tuned plan with zero miss warnings, so a square_pallas
    train step traces warning-free out of the box."""
    import warnings

    monkeypatch.delenv("REPRO_TUNING_CACHE", raising=False)
    tuning.clear_cache()
    cache = tuning.load_cache()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        for (m, n, k) in TRAINING_GEMMS:
            key = f"sq_matmul:{m}x{n}x{k}:float32"
            assert key in cache, f"committed cache missing {key}"
            entry = cache[key]
            plan = tuning.plan_matmul(m, n, k, jnp.float32,
                                      pm_layout=entry["pm_layout"])
            assert plan == tuning.TilePlan(
                entry["bm"], entry["bn"], entry["bk"], entry["kc"],
                entry["pm_layout"]), key
    misses = [str(x.message) for x in w if "cache miss" in str(x.message)]
    assert not misses, misses
    tuning.clear_cache()


@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_batched_kernel_matches_unbatched(dtype):
    """The leading batch grid axis computes exactly the per-element 2D
    kernel result (same plan family, same arithmetic)."""
    nb, m, k, n = 3, 33, 40, 17
    if dtype == "int8":
        a = jnp.asarray(RNG.integers(-128, 128, (nb, m, k)).astype(np.int8))
        b = jnp.asarray(RNG.integers(-128, 128, (nb, k, n)).astype(np.int8))
    else:
        a = jnp.asarray(RNG.normal(size=(nb, m, k)).astype(np.float32))
        b = jnp.asarray(RNG.normal(size=(nb, k, n)).astype(np.float32))
    out = np.asarray(ops.sq_matmul(a, b))
    assert out.shape == (nb, m, n)
    for i in range(nb):
        ref = np.asarray(ops.sq_matmul(a[i], b[i]))
        if dtype == "int8":
            np.testing.assert_array_equal(out[i], ref)
        else:
            np.testing.assert_allclose(out[i], ref, rtol=1e-5, atol=1e-4)


def test_autotune_batched_writes_batch_key(tmp_path, monkeypatch):
    """autotune_matmul(batch=N) writes the batch-keyed entry that
    plan_matmul(batch=N) looks up (closing the miss-warning loop)."""
    path = tmp_path / "cache.json"
    monkeypatch.setenv("REPRO_TUNING_CACHE", str(path))
    tuning.clear_cache()
    cache = tuning.autotune_matmul([(16, 16, 16)], jnp.float32,
                                   max_candidates=1, reps=1, batch=3)
    key = "sq_matmul:3b:16x16x16:float32"
    assert key in cache
    plan = tuning.plan_matmul(16, 16, 16, jnp.float32, batch=3,
                              pm_layout=cache[key]["pm_layout"])
    assert plan.bm == cache[key]["bm"] and plan.kc == cache[key]["kc"]
    tuning.clear_cache()


# ---------------------------------------------------------------------------
# The "kmn" row walk: wide tiles, the batched/fold path, TPU planning
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "int8"])
@pytest.mark.parametrize("bm,bn", [(8, 512), (32, 512), (32, 128)])
def test_kmn_wide_tiles_agree(dtype, bm, bn):
    """Tiles as wide as the chip's plans: bn 512 over N = 600 (padded to
    1024), a ragged K = 300 walked in two 128-wide groups of a padded
    256-wide slab."""
    m, k, n = 40, 300, 600
    if dtype == "int8":
        a = jnp.asarray(RNG.integers(-128, 128, (m, k)).astype(np.int8))
        b = jnp.asarray(RNG.integers(-128, 128, (k, n)).astype(np.int8))
    else:
        a = jnp.asarray(RNG.normal(size=(m, k)).astype(np.float32))
        b = jnp.asarray(RNG.normal(size=(k, n)).astype(np.float32))
    out = np.asarray(ops.sq_matmul(a, b, bm=bm, bn=bn, bk=256, kc=128,
                                   pm_layout="kmn"))
    ref = np.asarray(pm_matmul_scan(a, b))
    if dtype == "int8":
        np.testing.assert_array_equal(out, ref)
    else:
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-2)


@pytest.mark.parametrize("dtype", ["float32", "int8"])
@pytest.mark.parametrize("fold", [False, True])
def test_kmn_batched_agrees(dtype, fold):
    """The batched and batch-folded kernels under "kmn" compute what the
    chunked layout does (int8 bit-exact)."""
    nb, m, k, n = 5, 16, 128, 256
    if dtype == "int8":
        a = jnp.asarray(RNG.integers(-128, 128, (nb, m, k)).astype(np.int8))
        b = jnp.asarray(RNG.integers(-128, 128, (nb, k, n)).astype(np.int8))
    else:
        a = jnp.asarray(RNG.normal(size=(nb, m, k)).astype(np.float32))
        b = jnp.asarray(RNG.normal(size=(nb, k, n)).astype(np.float32))
    out = np.asarray(ops.sq_matmul(a, b, pm_layout="kmn", fold=fold))
    ref = np.asarray(ops.sq_matmul(a, b, pm_layout="mnk"))
    if dtype == "int8":
        np.testing.assert_array_equal(out, ref)
    else:
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-3)


def test_kmn_fold_width_keeps_vreg_budget():
    from repro.core import cost_model as cm
    plan = tuning.TilePlan(8, 512, 128, 128, "kmn")
    fb = ops._pick_fb(plan, 64)
    assert fb * cm.kmn_acc_vregs(8, 512) <= cm.KMN_ACC_VREGS
    assert ops._pick_fb(tuning.TilePlan(8, 512, 128, 128, "mkn"), 64) == 32


@pytest.mark.parametrize("m", [8, 32])
def test_tpu_sq_matmul_plan_is_kmn(monkeypatch, m):
    """On the TPU, sq_matmul resolves the row walk at the decode shapes,
    with every row of the call in one tile (the weights stream once) and
    the tile within the vreg budget; the grouped kernel still resolves
    "mkn"."""
    from repro.core import cost_model as cm
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    plan = ops._resolve_plan(m, 10240, 3840, jnp.bfloat16, bm=None, bn=None,
                             bk=None, kc=None, pm_layout=None,
                             interpret=False, kind="sq_matmul")
    assert plan.pm_layout == "kmn"
    assert plan.bm == m and plan.bn >= 512 and plan.kc == 128
    assert cm.kmn_acc_vregs(plan.bm, plan.bn) <= cm.KMN_ACC_VREGS
    grouped = ops._resolve_plan(8, 1408, 2048, jnp.float32, bm=None,
                                bn=None, bk=None, kc=None, pm_layout=None,
                                interpret=False, kind="sq_grouped_matmul")
    assert grouped.pm_layout == "mkn"


def test_grouped_and_paged_kernels_stay_chunked(monkeypatch):
    """sq_grouped_matmul's plan resolves "mkn" on the TPU, and the paged
    kernel refuses the row walk."""
    from repro.kernels import sq_paged_attn as spa
    seen = []
    real = ops._resolve_plan

    def spy(*args, **kw):
        plan = real(*args, **kw)
        seen.append(plan.pm_layout)
        return plan

    monkeypatch.setattr(ops, "_resolve_plan", spy)
    monkeypatch.setattr(ops, "_sq_grouped_exec",
                        lambda *a, **k: None)
    x = jnp.zeros((16, 256), jnp.float32)
    w = jnp.zeros((2, 256, 128), jnp.float32)
    ops.sq_grouped_matmul(x, w, jnp.zeros((2,), jnp.int32),
                          jnp.ones((1,), jnp.int32), bm=8, interpret=False)
    assert seen == ["mkn"]
    with pytest.raises(ValueError, match="pm_layout"):
        spa.sq_paged_attn(jnp.zeros((1, 1, 1, 1, 8)), jnp.zeros((16, 1, 8)),
                          jnp.zeros((16, 1, 8)), jnp.zeros((1, 1), jnp.int32),
                          jnp.zeros((16,), jnp.int32),
                          jnp.zeros((1, 1), jnp.int32), block_size=16,
                          pm_layout="kmn")


def test_kmn_plan_counter(monkeypatch):
    """tuning_sq_matmul_kmn_plans_total counts sq_matmul plans resolved to
    "kmn", and nothing else."""
    from repro.obs import metrics as obs_metrics
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    ctr = obs_metrics.default_registry().counter(
        "tuning_sq_matmul_kmn_plans_total")
    before = ctr.value
    tuning.plan_matmul(8, 10240, 3840, pm_layout="kmn")
    tuning.plan_matmul(32, 10240, 3840, pm_layout="kmn")
    assert ctr.value == before + 2
    tuning.plan_matmul(8, 10240, 3840, pm_layout="mkn")
    tuning.plan_matmul(8, 1408, 2048, pm_layout="mkn",
                       kind="sq_grouped_matmul")
    tuning.plan_matmul(64, 64, 64, pm_layout="mnk")
    assert ctr.value == before + 2
    tuning.plan_matmul(64, 64, 64, bm=8, bn=128, bk=64, pm_layout="kmn")
    assert ctr.value == before + 3


@pytest.mark.parametrize("shape,want", [
    ((8, 1408, 2048), (8, 128, 2048, 2048)),
    ((8, 2048, 1408), (8, 128, 1408, 1408)),
    ((32, 10240, 3840), (8, 128, 3840, 1920)),
    ((256, 256, 256), (32, 256, 256, 256)),
])
def test_chunked_layouts_plan_as_before(monkeypatch, shape, want):
    """A single chunked layout is still ranked by the unchanged cost: the
    "mkn" picks of the grouped kernel are the ones it had before "kmn"
    existed (Moonlight's expert shapes among them)."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    m, n, k = shape
    plan = tuning.plan_matmul(m, n, k, pm_layout="mkn",
                              kind="sq_grouped_matmul")
    assert plan == tuning.TilePlan(*want, "mkn")


@pytest.mark.parametrize("m", [8, 32, 256])
def test_kmn_plans_pad_only_to_the_lane_granule(monkeypatch, m):
    """A "kmn" plan pads K and N no further than the 128-lane granule, so
    the bench's HLO counts the call's true shape and a prepared weight
    (laid out at that granule) is reused without a per-call re-pad."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")

    def up(x):
        return -(-x // tuning.LANE) * tuning.LANE

    for (k, n) in [(3840, 1024), (3840, 10240), (10240, 3840), (3840, 32000),
                   (2048, 2816), (2048, 576), (2816, 2048), (2048, 20480)]:
        plan = tuning.plan_matmul(m, n, k, pm_layout="kmn")
        assert up(k) % plan.bk == 0 and up(n) % plan.bn == 0, (k, n, plan)
