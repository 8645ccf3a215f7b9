"""Equivalence + planner tests for the vectorized Pallas kernels.

The kernels must compute exactly what the pre-refactor rank-1 kernels
computed: the streamed square-form contraction of
``core.matmul.pm_matmul_scan``.  Integer paths bit-match; float paths match
to reassociation tolerance (grouping changes the add order, nothing else).
The planner tests pin each kernel's one PM schedule and the plans the
benchmark cells' shapes resolve to.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import squares as sq
from repro.core.matmul import pm_matmul_scan
from repro.kernels import ops, tuning

RNG = np.random.default_rng(11)

RAGGED_SHAPES = [(1, 1, 1), (7, 13, 9), (100, 60, 130), (64, 128, 32),
                 (130, 257, 140)]

# (bm, bn, bk, kc) row-walk plans: one group per slab (kc == bk), rank-1
# (kc == 1, the seed dataflow), and mid groupings; two lane groups over a
# K of 200 padded to 256; bm 32 with a bn of 512 (clamped to these shapes)
PLANS = [
    dict(bm=32, bn=128, bk=32, kc=32),                     # one group
    dict(bm=16, bn=256, bk=128, kc=64),
    dict(bm=64, bn=128, bk=128, kc=1),                     # rank-1 (seed)
    dict(bm=64, bn=128, bk=128, kc=32),
    dict(bm=8, bn=128, bk=64, kc=16),
    dict(bm=8, bn=128, bk=64, kc=64),
    dict(bm=8, bn=128, bk=256, kc=128),
    dict(bm=32, bn=512, bk=256, kc=128),
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
@pytest.mark.parametrize("kc", [pytest.param(kc, id=f"{kc}-mkn")
                                for kc in (1, 16, 32, 64)])
def test_cpm_chunked_layouts_agree(kind, kc):
    m, k, n = 40, 64, 24
    x = jnp.asarray((RNG.normal(size=(m, k))
                     + 1j * RNG.normal(size=(m, k))).astype(np.complex64))
    y = jnp.asarray((RNG.normal(size=(k, n))
                     + 1j * RNG.normal(size=(k, n))).astype(np.complex64))
    op = getattr(ops, kind)
    re, im = op(x, y, bk=64, kc=kc)
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
        for kind in ("sq_matmul", "cpm3_matmul"):
            plan = tuning.plan_matmul(m, n, k, kind=kind)
            assert plan.bk % plan.kc == 0, plan


def test_planner_explicit_tiles_respected():
    plan = tuning.plan_matmul(512, 512, 512, bm=64, bn=128, bk=128, kc=16)
    assert (plan.bm, plan.bn, plan.bk, plan.kc) == (64, 128, 128, 16)


def test_planner_vmem_budget():
    from repro.core import cost_model as cm
    for plan in tuning.candidate_plans(2048, 2048, 2048):
        cost = cm.pm_walk_grid_cost(2048, 2048, 2048, *plan.astuple())
        assert cost.vmem_bytes <= tuning.VMEM_BUDGET
    for plan in tuning.candidate_plans(2048, 2048, 2048, kind="cpm3_matmul"):
        cost = cm.pm_grid_cost(2048, 2048, 2048, *plan.astuple(),
                               n_row_ops=2, n_col_ops=2, n_acc=2)
        assert cost.vmem_bytes <= tuning.VMEM_BUDGET


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


# ---------------------------------------------------------------------------
# The row walk: wide tiles, the batched/fold path, the chip's plans
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
    out = np.asarray(ops.sq_matmul(a, b, bm=bm, bn=bn, bk=256, kc=128))
    ref = np.asarray(pm_matmul_scan(a, b))
    if dtype == "int8":
        np.testing.assert_array_equal(out, ref)
    else:
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-2)


@pytest.mark.parametrize("dtype", ["float32", "int8"])
@pytest.mark.parametrize("fold", [False, True])
def test_kmn_batched_agrees(dtype, fold):
    """The batched and batch-folded row walks compute each element's
    streamed square-form contraction (int8 bit-exact)."""
    nb, m, k, n = 5, 16, 128, 256
    if dtype == "int8":
        a = jnp.asarray(RNG.integers(-128, 128, (nb, m, k)).astype(np.int8))
        b = jnp.asarray(RNG.integers(-128, 128, (nb, k, n)).astype(np.int8))
    else:
        a = jnp.asarray(RNG.normal(size=(nb, m, k)).astype(np.float32))
        b = jnp.asarray(RNG.normal(size=(nb, k, n)).astype(np.float32))
    out = np.asarray(ops.sq_matmul(a, b, fold=fold))
    ref = np.stack([np.asarray(pm_matmul_scan(a[i], b[i]))
                    for i in range(nb)])
    if dtype == "int8":
        np.testing.assert_array_equal(out, ref)
    else:
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-3)


def test_kmn_fold_width_keeps_vreg_budget():
    from repro.core import cost_model as cm
    plan = tuning.TilePlan(8, 512, 128, 128)
    fb = ops._pick_fb(plan, 64)
    assert fb * cm.kmn_acc_vregs(8, 512) <= cm.KMN_ACC_VREGS


@pytest.mark.parametrize("m", [8, 32])
def test_tpu_sq_matmul_plan_is_kmn(m):
    """sq_matmul walks rows, at the decode shapes with every row of the
    call in one tile (the weights stream once) and the tile within the
    vreg budget; the grouped kernel keeps the chunked blocks."""
    from repro.core import cost_model as cm
    assert tuning.pm_schedule("sq_matmul") == tuning.ROW_WALK == "kmn"
    plan = tuning.plan_matmul(m, 10240, 3840, sq.accum_dtype(jnp.bfloat16))
    assert plan.bm == m and plan.bn >= 512 and plan.kc == 128
    assert cm.kmn_acc_vregs(plan.bm, plan.bn) <= cm.KMN_ACC_VREGS
    assert tuning.pm_schedule("sq_grouped_matmul") == tuning.CHUNKED


def test_grouped_and_paged_kernels_stay_chunked(monkeypatch):
    """Every kernel but sq_matmul plans the chunked blocks, and the
    grouped call plans as the grouped kind; no kernel takes a schedule
    argument."""
    import inspect

    from repro.kernels.cpm3_matmul import cpm3_matmul_pallas
    from repro.kernels.cpm4_matmul import cpm4_matmul_pallas
    from repro.kernels.sq_conv2d import sq_conv2d_pallas
    from repro.kernels.sq_grouped_matmul import sq_grouped_matmul_pallas
    from repro.kernels.sq_matmul import sq_matmul_batched_pallas
    from repro.kernels.sq_paged_attn import sq_paged_attn
    for kind in ("cpm3_matmul", "cpm4_matmul", "sq_grouped_matmul",
                 "sq_conv2d", "sq_paged_attn"):
        assert tuning.pm_schedule(kind) == tuning.CHUNKED == "mkn"
    seen = []
    real = tuning.plan_matmul

    def spy(*args, **kw):
        seen.append(kw.get("kind", "sq_matmul"))
        return real(*args, **kw)

    monkeypatch.setattr(tuning, "plan_matmul", spy)
    monkeypatch.setattr(ops, "_sq_grouped_exec", lambda *a, **k: None)
    x = jnp.zeros((16, 256), jnp.float32)
    w = jnp.zeros((2, 256, 128), jnp.float32)
    ops.sq_grouped_matmul(x, w, jnp.zeros((2,), jnp.int32),
                          jnp.ones((1,), jnp.int32), bm=8, interpret=False)
    assert seen == ["sq_grouped_matmul"]
    for fn in (sq_matmul_batched_pallas, cpm3_matmul_pallas,
               cpm4_matmul_pallas, sq_conv2d_pallas, sq_grouped_matmul_pallas,
               sq_paged_attn):
        assert "pm_layout" not in inspect.signature(fn).parameters, fn


@pytest.mark.parametrize("shape,want", [
    ((8, 1408, 2048), (8, 128, 2048, 2048)),
    ((8, 2048, 1408), (8, 128, 1408, 1408)),
    ((32, 10240, 3840), (8, 128, 3840, 1920)),
    ((256, 256, 256), (32, 256, 256, 256)),
])
def test_chunked_layouts_plan_as_before(shape, want):
    """The chunked schedule is still ranked by the unchanged cost: the
    picks of the grouped kernel are the ones it had before the row walk
    existed (Moonlight's expert shapes among them)."""
    m, n, k = shape
    plan = tuning.plan_matmul(m, n, k, kind="sq_grouped_matmul")
    assert plan == tuning.TilePlan(*want)


@pytest.mark.parametrize("m", [8, 32, 256])
def test_kmn_plans_pad_only_to_the_lane_granule(m):
    """A row-walk plan pads K and N no further than the 128-lane granule,
    so the bench's HLO counts the call's true shape and a prepared weight
    (laid out at that granule) is reused without a per-call re-pad."""

    def up(x):
        return -(-x // tuning.LANE) * tuning.LANE

    for (k, n) in [(3840, 1024), (3840, 10240), (10240, 3840), (3840, 32000),
                   (2048, 2816), (2048, 576), (2816, 2048), (2048, 20480)]:
        plan = tuning.plan_matmul(m, n, k)
        assert up(k) % plan.bk == 0 and up(n) % plan.bn == 0, (k, n, plan)


# ---------------------------------------------------------------------------
# The plans the benchmark cells' shapes resolve to on the TPU, pinned
# ---------------------------------------------------------------------------

# sq_matmul calls of the cells (benchmarks/kernel_timing.py
# CELL_SQ_MATMUL_SHAPES): (batch, m, k, n) -> (bm, bn, bk, kc) and the
# fold width of a batched call
CELL_SQ_MATMUL_PLANS = [
    ((1, 8, 3840, 1024), (8, 1024, 1280, 128), 1),
    ((1, 8, 3840, 3840), (8, 3840, 384, 128), 1),
    ((1, 8, 3840, 10240), (8, 2560, 384, 128), 1),
    ((1, 8, 3840, 32000), (8, 3200, 384, 128), 1),
    ((1, 32, 3840, 1024), (32, 1024, 1280, 128), 1),
    ((1, 32, 3840, 3840), (32, 768, 1920, 128), 1),
    ((1, 32, 3840, 10240), (32, 1024, 1280, 128), 1),
    ((1, 32, 3840, 32000), (32, 640, 1920, 128), 1),
    ((1, 8, 10240, 3840), (8, 3840, 256, 128), 1),
    ((1, 32, 10240, 3840), (32, 768, 1280, 128), 1),
    ((1, 32, 2048, 3072), (32, 1024, 1024, 128), 1),
    ((1, 32, 2048, 576), (32, 640, 2048, 128), 1),
    ((1, 32, 2048, 2816), (16, 1408, 1024, 128), 1),
    ((1, 32, 2048, 11264), (32, 1024, 1024, 128), 1),
    ((1, 32, 2048, 20480), (32, 1024, 1024, 128), 1),
    ((1, 32, 2048, 2048), (32, 1024, 1024, 128), 1),
    ((1, 32, 2816, 2048), (32, 1024, 1408, 128), 1),
    ((1, 32, 11264, 2048), (32, 1024, 1408, 128), 1),
    ((16, 32, 128, 512), (32, 512, 128, 128), 2),
    ((16, 32, 512, 128), (32, 128, 512, 128), 8),
]
CELL_PLANS = (
    [pytest.param("sq_matmul", call, (plan, fb), id="sq_matmul-" +
                  "x".join(map(str, call))) for call, plan, fb in
     CELL_SQ_MATMUL_PLANS]
    # Moonlight's grouped experts: (bm, k, n) -> (bm, bn, bk, kc)
    + [pytest.param("grouped", (8, 2048, 1408), (8, 128, 2048, 2048),
                    id="grouped-8x2048x1408"),
       pytest.param("grouped", (8, 1408, 2048), (8, 128, 1408, 1408),
                    id="grouped-8x1408x2048")]
    # decode attention: (hd, tile) -> (kc_qk, kc_pv); danube's 8 KV heads
    # of 120, Moonlight's 576-wide latent pool, 128-token tiles
    + [pytest.param("paged", (120, 128), (120, 128), id="paged-danube"),
       pytest.param("paged", (576, 128), (576, 128), id="paged-moonlight")])


@pytest.mark.parametrize("kind,call,want", CELL_PLANS)
def test_cell_plans_unchanged(kind, call, want):
    """Every cell shape resolves to the plan the TPU ran before each
    kernel was held to one schedule (the plans were recorded from the
    TPU-side resolution of that tree)."""
    if kind == "sq_matmul":
        b, m, k, n = call
        plan = tuning.plan_matmul(m, n, k, sq.accum_dtype(jnp.bfloat16))
        assert (plan.astuple(), ops._pick_fb(plan, b)) == want
    elif kind == "grouped":
        bm, k, n = call
        assert tuning.plan_grouped_matmul(bm, n, k).astuple() == want
    else:
        p = tuning.plan_paged_attn(*call)
        assert (p.kc_qk, p.kc_pv) == want


def _record_exec(monkeypatch, name, plan_at, fb_at=None):
    """Stub ``ops.<name>`` (an execute half) with a recorder of the plan
    (and fold width) it is handed."""
    seen = []

    def rec(*args, **kw):
        seen.append((args[plan_at],
                     None if fb_at is None else args[fb_at]))

    monkeypatch.setattr(ops, name, rec)
    return seen


def _cplx(shape):
    return jnp.asarray((RNG.normal(size=shape)
                        + 1j * RNG.normal(size=shape)).astype(np.complex64))


BACKEND_FORMS = {
    # name: (execute half to stub, plan arg index, fb arg index, call)
    "sq_matmul": ("_sq_matmul_impl", 2, None, lambda i: ops.sq_matmul(
        jnp.zeros((8, 384)), jnp.zeros((384, 1280)), interpret=i)),
    "sq_matmul_batched": ("_sq_matmul_batched_impl", 3, 2,
                          lambda i: ops.sq_matmul(
                              jnp.zeros((3, 32, 512)),
                              jnp.zeros((3, 512, 256)), interpret=i)),
    "sq_matmul_folded": ("_sq_matmul_batched_impl", 3, 2,
                         lambda i: ops.sq_matmul(
                             jnp.zeros((16, 32, 128)),
                             jnp.zeros((16, 128, 512)), interpret=i,
                             fold=True)),
    "grouped": ("_sq_grouped_exec", 4, None, lambda i: ops.sq_grouped_matmul(
        jnp.zeros((16, 2048)), jnp.zeros((2, 2048, 1408), jnp.bfloat16),
        jnp.zeros((2,), jnp.int32), jnp.ones((1,), jnp.int32), bm=8,
        interpret=i)),
    "cpm3": ("_cpm3_impl", 4, None, lambda i: ops.cpm3_matmul(
        _cplx((40, 300)), _cplx((300, 200)), interpret=i)),
    "cpm4": ("_cpm4_impl", 4, None, lambda i: ops.cpm4_matmul(
        _cplx((40, 300)), _cplx((300, 200)), interpret=i)),
    "conv2d": ("_sq_conv2d_fused_impl", 2, None, lambda i: ops.sq_conv2d(
        jnp.zeros((2, 64, 34, 34)), jnp.zeros((64, 64, 3, 3)),
        interpret=i)),
}


@pytest.mark.parametrize("form", list(BACKEND_FORMS) + ["paged"])
def test_plan_is_backend_independent(monkeypatch, form):
    """Interpret mode runs the plan, and so the schedule, the chip runs:
    each call form hands its kernel the same plan for interpret=True as
    for interpret=False."""
    if form == "paged":
        from repro.kernels import sq_paged_attn as spa
        from repro.models import attention as attn

        seen = []
        monkeypatch.setattr(spa, "sq_paged_attn", lambda *a, **kw: seen.append(
            (kw["kc_qk"], kw["kc_pv"])) or jnp.zeros((1, 1, 8, 4, 120)))
        for interpret in (True, False):
            monkeypatch.setattr(ops, "default_interpret", lambda i=interpret: i)
            paged = {"tables": jnp.zeros((1, 96), jnp.int32), "block_size": 16,
                     "pos_pool": jnp.zeros((1536,), jnp.int32)}
            attn.paged_read(
                jnp.zeros((1, 1, 8, 4, 120)), jnp.zeros((1536, 8, 120)),
                jnp.zeros((1536, 8, 120)), jnp.zeros((1, 1), jnp.int32),
                paged, dt=jnp.dtype(jnp.bfloat16), window=None, softcap=0.0,
                mode="square_pallas", policy=None,
                gather_attend=lambda: None)
        assert len(seen) == 2 and seen[0] == seen[1] == (120, 128)
        return
    name, plan_at, fb_at, call = BACKEND_FORMS[form]
    seen = _record_exec(monkeypatch, name, plan_at, fb_at)
    call(True)
    call(False)
    assert len(seen) == 2 and seen[0] == seen[1], seen


# The cells' prepared weights at their decode M: (K, N) of the canonical
# operand, transposed tables for the tied heads
PREPARED_CELL_WEIGHTS = [
    pytest.param((32000, 3840), True, 8, id="danube-head"),
    pytest.param((20480, 2048), True, 32, id="moonlight-head"),
    pytest.param((2048, 576), False, 32, id="moonlight-wkv_a"),
    pytest.param((11264, 2048), False, 32, id="moonlight-lead-down"),
]


@pytest.mark.parametrize("shape,transpose,m", PREPARED_CELL_WEIGHTS)
def test_prepared_plan_is_the_execute_plan(monkeypatch, shape, transpose, m):
    """A prepared weight is padded as its execute plan pads it, so
    sq_matmul streams the prepared arrays untouched (no per-call slice
    and re-pad).  Traced abstractly: no weight is materialized."""
    from repro.core.prepared import prepare_operand

    seen = []
    monkeypatch.setattr(ops, "_sq_matmul_exec",
                        lambda a, bw, sb, n, plan, interpret: seen.append(
                            (bw, sb)))
    k = shape[0] if not transpose else shape[1]

    def run(w, a):
        prep = prepare_operand(w, transpose=transpose, site="cell")
        ops.sq_matmul(a, prep, interpret=True)
        assert seen[-1][0] is prep.canon and seen[-1][1] is prep.corr
        return prep.canon

    jax.eval_shape(run, jax.ShapeDtypeStruct(shape, jnp.float32),
                   jax.ShapeDtypeStruct((m, k), jnp.bfloat16))
    assert len(seen) == 1
