"""Edge-case grid for the fused window-streaming 2D square-conv kernel.

Every configuration is checked against ``jax.lax.conv_general_dilated``
(the multiplier ground truth) and against the materialized im2col route
(``ops.sq_conv2d_im2col``) -- the two must agree because they are the
same arithmetic through different dataflows.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import conv as cc
from repro.kernels import ops, tuning

RNG = np.random.default_rng(23)


def _lax_ref(x4, w4, strides, pads):
    dt = jnp.promote_types(x4.dtype, jnp.float32) \
        if not jnp.issubdtype(x4.dtype, jnp.integer) else jnp.int32
    return jax.lax.conv_general_dilated(
        x4.astype(dt), w4.astype(dt), strides, pads,
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


def _check(x4, w4, stride=1, padding="VALID", rtol=2e-3, atol=None):
    strides = cc.resolve_stride(stride)
    pads = cc.resolve_padding(padding, x4.shape[2:], w4.shape[2:], strides)
    k_vol = w4.shape[1] * w4.shape[2] * w4.shape[3]
    atol = atol if atol is not None else 2e-3 * k_vol
    ref = np.asarray(_lax_ref(x4, w4, strides, pads))
    fused = np.asarray(ops.sq_conv2d(x4, w4, stride=stride, padding=padding))
    im2col = np.asarray(ops.sq_conv2d_im2col(x4, w4, stride=stride,
                                             padding=padding))
    np.testing.assert_allclose(fused, ref, rtol=rtol, atol=atol)
    np.testing.assert_allclose(im2col, ref, rtol=rtol, atol=atol)
    np.testing.assert_allclose(fused, im2col, rtol=rtol, atol=atol)


# ---------------------------------------------------------------------------
# Edge-case grid: spatial / stride / padding / channel raggedness
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("hw,khw", [((17, 13), (3, 3)),   # odd spatial
                                    ((9, 23), (5, 3)),    # odd + rect taps
                                    ((8, 8), (8, 8)),     # kernel == input
                                    ((6, 31), (1, 7))])   # 1-row taps
def test_odd_spatial_sizes(hw, khw):
    x = jnp.asarray(RNG.normal(size=(1, 3) + hw).astype(np.float32))
    w = jnp.asarray(RNG.normal(size=(5, 3) + khw).astype(np.float32))
    _check(x, w)


@pytest.mark.parametrize("stride", [2, (2, 1), (1, 3), 3])
@pytest.mark.parametrize("padding", ["VALID", "SAME"])
def test_strides_and_padding(stride, padding):
    x = jnp.asarray(RNG.normal(size=(2, 4, 15, 18)).astype(np.float32))
    w = jnp.asarray(RNG.normal(size=(6, 4, 3, 3)).astype(np.float32))
    _check(x, w, stride=stride, padding=padding)


@pytest.mark.parametrize("padding", [1, 2, ((2, 0), (0, 3))])
def test_explicit_padding(padding):
    x = jnp.asarray(RNG.normal(size=(1, 2, 10, 11)).astype(np.float32))
    w = jnp.asarray(RNG.normal(size=(3, 2, 3, 5)).astype(np.float32))
    _check(x, w, padding=padding)


@pytest.mark.parametrize("cin,cout", [(5, 3), (1, 7), (13, 1), (65, 9)])
def test_ragged_channel_counts(cin, cout):
    """cin/cout off every tile granule: channel/filter padding must be
    exact (padded zeros contribute (0+0)^2 - 0 - 0 = 0)."""
    x = jnp.asarray(RNG.normal(size=(1, cin, 12, 12)).astype(np.float32))
    w = jnp.asarray(RNG.normal(size=(cout, cin, 3, 3)).astype(np.float32))
    _check(x, w)


def test_bf16_widening():
    """bf16 operands accumulate in f32 (the paper's bit-growth rule)."""
    x = jnp.asarray(RNG.normal(size=(1, 8, 14, 14)), jnp.bfloat16)
    w = jnp.asarray(RNG.normal(size=(4, 8, 3, 3)), jnp.bfloat16)
    out = ops.sq_conv2d(x, w)
    assert out.dtype == jnp.float32
    ref = np.asarray(_lax_ref(x, w, (1, 1), ((0, 0), (0, 0))))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=5e-2, atol=1.0)


def test_int8_bit_exact():
    x = jnp.asarray(RNG.integers(-30, 30, (2, 3, 11, 9)), jnp.int8)
    w = jnp.asarray(RNG.integers(-30, 30, (4, 3, 3, 3)), jnp.int8)
    out = np.asarray(ops.sq_conv2d(x, w, stride=2, padding="SAME"))
    strides = (2, 2)
    pads = cc.resolve_padding("SAME", (11, 9), (3, 3), strides)
    ref = np.asarray(_lax_ref(x, w, strides, pads))
    np.testing.assert_array_equal(out, ref)


def test_batched_matches_unbatched():
    xb = jnp.asarray(RNG.normal(size=(3, 6, 13, 13)).astype(np.float32))
    w = jnp.asarray(RNG.normal(size=(8, 6, 3, 3)).astype(np.float32))
    batched = np.asarray(ops.sq_conv2d(xb, w, padding="SAME"))
    for b in range(3):
        single = np.asarray(ops.sq_conv2d(xb[b], w, padding="SAME"))
        np.testing.assert_allclose(batched[b], single, rtol=1e-4, atol=1e-2)
    _check(xb, w, padding="SAME")


def test_rank_shorthands():
    """(H, W) x (kh, kw) and (H, W) x (co, kh, kw) keep the seed-era API."""
    x = jnp.asarray(RNG.normal(size=(16, 16)).astype(np.float32))
    w2 = jnp.asarray(RNG.normal(size=(3, 3)).astype(np.float32))
    w3 = jnp.asarray(RNG.normal(size=(4, 3, 3)).astype(np.float32))
    out2 = ops.sq_conv2d(x, w2)
    out3 = ops.sq_conv2d(x, w3)
    assert out2.shape == (14, 14) and out3.shape == (4, 14, 14)
    ref = np.asarray(_lax_ref(x[None, None], w3[:, None], (1, 1),
                              ((0, 0), (0, 0))))[0]
    np.testing.assert_allclose(np.asarray(out3), ref, rtol=2e-3, atol=2e-2)
    with pytest.raises(ValueError, match="channel mismatch"):
        ops.sq_conv2d(jnp.zeros((2, 8, 8)), jnp.zeros((4, 3, 3, 3)))


def test_batched_input_with_filter_shorthand_keeps_batch():
    """A rank-4 input must keep its batch axis even under the rank-2/3
    filter shorthands (regression: the output layout used to key on the
    filter rank alone and silently returned only batch element 0)."""
    x = jnp.asarray(RNG.normal(size=(4, 1, 8, 8)).astype(np.float32))
    w2 = jnp.asarray(RNG.normal(size=(3, 3)).astype(np.float32))
    out = ops.sq_conv2d(x, w2)
    assert out.shape == (4, 1, 6, 6)
    ref = np.asarray(_lax_ref(x, w2[None, None], (1, 1), ((0, 0), (0, 0))))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-3, atol=2e-2)
    out_i = ops.sq_conv2d_im2col(x, w2)
    assert out_i.shape == (4, 1, 6, 6)
    np.testing.assert_allclose(np.asarray(out_i), ref, rtol=2e-3, atol=2e-2)
    out_c = cc.conv2d(x, w2)
    assert out_c.shape == (4, 1, 6, 6)


def test_kernel_larger_than_input_raises():
    with pytest.raises(ValueError, match="larger than padded input"):
        ops.sq_conv2d(jnp.zeros((4, 4)), jnp.zeros((5, 5)))


def test_explicit_plan_overrides_respected():
    x = jnp.asarray(RNG.normal(size=(1, 6, 12, 12)).astype(np.float32))
    w = jnp.asarray(RNG.normal(size=(4, 6, 3, 3)).astype(np.float32))
    base = np.asarray(ops.sq_conv2d(x, w))
    for kwargs in [dict(bh=4, bw=5, bk=3, kc=9, bf=2),
                   dict(bh=10, bw=10, bk=6, kc=1, bf=4),
                   dict(bh=2, bw=12, bk=2, kc=6, bf=3)]:
        out = np.asarray(ops.sq_conv2d(x, w, **kwargs))
        np.testing.assert_allclose(out, base, rtol=1e-4, atol=1e-2)


def test_fused_path_never_gathers_patches():
    """Structural guarantee: the square_pallas route contains no gather --
    the im2col patch tensor is never materialized (the im2col reference,
    by contrast, is built from stacked patch slices)."""
    x = jnp.zeros((1, 8, 16, 16), jnp.float32)
    w = jnp.zeros((4, 8, 3, 3), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda x, w: ops.sq_conv2d(x, w))(x, w)
    assert "gather" not in str(jaxpr)


# ---------------------------------------------------------------------------
# core.conv.conv2d mode dispatch
# ---------------------------------------------------------------------------

def test_conv2d_mode_dispatch_agrees():
    x = jnp.asarray(RNG.normal(size=(1, 4, 10, 10)).astype(np.float32))
    w = jnp.asarray(RNG.normal(size=(3, 4, 3, 3)).astype(np.float32))
    ref = np.asarray(cc.conv2d(x, w, mode="standard", padding="SAME"))
    for mode in ("square_virtual", "square_exact", "square_pallas"):
        out = np.asarray(cc.conv2d(x, w, mode=mode, padding="SAME"))
        np.testing.assert_allclose(out, ref, rtol=2e-3, atol=0.1)


def test_conv2d_square_modes_int8_wide_accumulation():
    """int8 square modes must accumulate in int32 and agree bit-exactly --
    square_virtual's x2 carry rides the WIDE accumulator, not the int8
    conv output (regression: it used to widen an already-overflowed
    int8-accumulated conv)."""
    x = jnp.asarray(RNG.integers(-30, 30, (1, 3, 8, 8)), jnp.int8)
    w = jnp.asarray(RNG.integers(-30, 30, (2, 3, 3, 3)), jnp.int8)
    ref = np.asarray(_lax_ref(x, w, (1, 1), ((0, 0), (0, 0))))   # int32 acc
    for mode in ("square_virtual", "square_exact", "square_pallas"):
        out = np.asarray(cc.conv2d(x, w, mode=mode))
        np.testing.assert_array_equal(out, ref)


def test_conv2d_unknown_mode_raises():
    with pytest.raises(ValueError, match="unknown conv2d mode"):
        cc.conv2d(jnp.zeros((4, 4)), jnp.zeros((3, 3)), mode="square_scan")


# ---------------------------------------------------------------------------
# plan_conv2d
# ---------------------------------------------------------------------------

def test_plan_conv2d_kc_divides_flattened_axis():
    for (h, w, kh, kw, cin, cout) in [(32, 32, 3, 3, 64, 64),
                                      (15, 18, 5, 3, 7, 9),
                                      (12, 12, 3, 3, 1, 1)]:
        plan = tuning.plan_conv2d(h, w, kh, kw, cin, cout)
        assert (kh * kw * plan.bk) % plan.kc == 0, plan
        assert plan.bk <= cin and plan.bf <= cout


def test_plan_conv2d_explicit_wins():
    plan = tuning.plan_conv2d(32, 32, 3, 3, 64, 64, bh=8, bw=16, bk=32,
                              kc=16, bf=32)
    assert plan == tuning.Conv2DPlan(8, 16, 32, 16, 32)
