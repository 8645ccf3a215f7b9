"""Kernel wall-time microbenchmarks (CPU interpret mode for Pallas; jnp for
the algebraic paths).  Interpret-mode timings validate correctness cost, not
TPU performance -- TPU projections come from the roofline (§Roofline).

Row contract: every row dict carries ``name``, ``us_per_call``, ``shape``
and ``mode`` (plus optional extras) -- the same fields ``benchmarks/run.py
--json`` writes to ``BENCH_kernels.json`` so kernel speedups are trackable
across PRs.

``--sweep-sq-matmul OUT_JSONL`` times the planner's ``sq_matmul`` picks
on the TPU against other row-walk plans at the benchmark cells' shapes.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def _time(fn, *args, reps=5, warmup=2):
    """Min-of-reps wall time in us (min is robust to scheduler noise on the
    shared CPU runners these benches execute on)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6      # us


def _time_pair(fn_a, fn_b, *, reps=5, warmup=2):
    """Min-of-reps for two thunks with ALTERNATING measurement.

    A long benchmark run progressively throttles on quota-limited
    runners, so timing all of A's reps before B's biases whichever runs
    later -- enough to invert a same-process ratio.  Interleaving the
    reps keeps the A/B ratio load-drift-immune (both see the same
    machine state); used for every prepared-vs-raw pair."""
    for _ in range(warmup):
        jax.block_until_ready(fn_a())
        jax.block_until_ready(fn_b())
    best_a = best_b = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn_a())
        best_a = min(best_a, time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(fn_b())
        best_b = min(best_b, time.perf_counter() - t0)
    return best_a * 1e6, best_b * 1e6


def time_sq_matmul_kernel(m, n, k, plan, *, batch=1, reps=5, inner=10):
    """Seconds per call of the ``sq_matmul`` Pallas kernel alone under an
    explicit ``plan``: f32 operands already padded to the plan's tiles,
    zero corrections, ``inner`` calls issued back to back per
    synchronisation, the least over ``reps``.  On the chip this is the
    kernel's device time plus a share of one dispatch; the operand prep
    that ``ops.sq_matmul`` adds (widen, corrections, padding) is left
    out.  Chip timings only: it refuses to run off the TPU."""
    from repro.kernels.sq_matmul import sq_matmul_batched_pallas

    _require_tpu()
    rng = np.random.default_rng(0)
    mp, np_, kp = (-(-m // plan.bm) * plan.bm, -(-n // plan.bn) * plan.bn,
                   -(-k // plan.bk) * plan.bk)
    a = jnp.asarray(rng.normal(size=(batch, mp, kp)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(batch, kp, np_)), jnp.float32)
    sa = jnp.zeros((batch, mp, 1), jnp.float32)
    sb = jnp.zeros((batch, 1, np_), jnp.float32)
    fn = jax.jit(lambda a, b, sa, sb: sq_matmul_batched_pallas(
        a, b, sa, sb, bm=plan.bm, bn=plan.bn, bk=plan.bk, kc=plan.kc,
        interpret=False))
    jax.block_until_ready(fn(a, b, sa, sb))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(inner):
            out = fn(a, b, sa, sb)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / inner)
    return best


# The benchmark cells' sq_matmul calls, (batch, m, k, n): danube decodes 8
# rows and prefills 32-row chunks (q/o 3840, k/v 1024 padded, MLP 10240,
# head 32000); Moonlight decodes 32 rows (q 3072, latent 576, o 2048,
# shared experts 2816, dense MLP 11264, head 20480) and runs its absorbed
# latent projections as 16-head batched calls.
CELL_SQ_MATMUL_SHAPES = (
    [(1, m, 3840, n) for m in (8, 32) for n in (1024, 3840, 10240, 32000)]
    + [(1, 8, 10240, 3840), (1, 32, 10240, 3840)]
    + [(1, 32, 2048, n) for n in (3072, 576, 2816, 11264, 20480)]
    + [(1, 32, 2048, 2048), (1, 32, 2816, 2048), (1, 32, 11264, 2048)]
    + [(16, 32, 128, 512), (16, 32, 512, 128)])


def _require_tpu():
    if jax.default_backend() != "tpu":
        raise SystemExit(f"sq_matmul kernel timings need the TPU; this "
                         f"process runs on {jax.default_backend()!r}")


def sweep_sq_matmul(shapes=CELL_SQ_MATMUL_SHAPES, *, budgets=(16, 8, 4),
                    emit=print):
    """Time the planner's pick for each shape against alternatives: for
    each accumulator budget in ``budgets`` (vregs), the cost model's best
    row-walk plan whose tile fits it -- the smaller tiles that leave more
    of the register file to the partial sum and the broadcasts.  ``emit``
    receives one dict per plan; returns them all."""
    from repro.core import cost_model as cm
    from repro.kernels import tuning

    _require_tpu()
    rows = []
    for (batch, m, k, n) in shapes:
        pick = tuning.plan_matmul(m, n, k)
        plans = [pick]
        ranked = sorted(tuning.candidate_plans(m, n, k),
                        key=lambda p: cm.pm_walk_grid_cost(
                            m, n, k, *p.astuple()).weighted)
        for budget in budgets:
            fit = [p for p in ranked
                   if cm.kmn_acc_vregs(p.bm, p.bn) <= budget]
            if fit and fit[0] not in plans:
                plans.append(fit[0])
        for plan in plans:
            row = {"shape": [batch, m, k, n], "plan": list(plan.astuple()),
                   "pick": plan == pick,
                   "acc_vregs": cm.kmn_acc_vregs(plan.bm, plan.bn),
                   "model": cm.pm_walk_grid_cost(m, n, k,
                                                 *plan.astuple()).weighted}
            try:
                row["us"] = time_sq_matmul_kernel(m, n, k, plan,
                                                  batch=batch) * 1e6
            except Exception as e:          # a plan the chip refuses
                row["error"] = f"{type(e).__name__}: {e}"[:300]
            emit(row)
            rows.append(row)
    return rows


def prepared_rows():
    """Prepared-operand amortization rows (the paper's weight-stationary
    contract): the same kernel call with the column-operand prep (widen +
    Sb correction + tile padding) done per call vs done ONCE via
    core.prepared.prepare_operand.  Timed under eager/interpret execution,
    where the per-call prep is real work (under jit both trace identically;
    the prepared form is then free via jit caching)."""
    from repro.core.prepared import prepare_operand
    from repro.kernels import ops

    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.normal(size=(256, 256)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(256, 256)).astype(np.float32))
    prep = prepare_operand(b, m_hint=256)
    raw_us, prep_us = _time_pair(lambda: ops.sq_matmul(a, b),
                                 lambda: ops.sq_matmul(a, prep), reps=7)
    # decode-shaped GEMV block: M tiny relative to the (K, N) weight, so
    # the per-call column prep is a first-order cost -- the regime the
    # weight-stationary contract exists for (measured ~1.5x)
    ad = jnp.asarray(rng.normal(size=(8, 1024)).astype(np.float32))
    bd = jnp.asarray(rng.normal(size=(1024, 1024)).astype(np.float32))
    prepd = prepare_operand(bd, m_hint=8)
    rawd_us, prepd_us = _time_pair(lambda: ops.sq_matmul(ad, bd),
                                   lambda: ops.sq_matmul(ad, prepd), reps=7)
    return [
        {"name": "pallas_sq_matmul_raw256[interp]", "us_per_call": raw_us,
         "shape": "256x256x256", "mode": "f32/per-call-prep"},
        {"name": "pallas_sq_matmul_prepared256[interp]",
         "us_per_call": prep_us,
         "shape": "256x256x256", "mode": "f32/prepared"},
        {"name": "pallas_sq_matmul_raw_decode[interp]",
         "us_per_call": rawd_us,
         "shape": "8x1024x1024", "mode": "f32/per-call-prep"},
        {"name": "pallas_sq_matmul_prepared_decode[interp]",
         "us_per_call": prepd_us,
         "shape": "8x1024x1024", "mode": "f32/prepared"},
    ]


def routed_conv2d_rows():
    """Route-planner row: the tiny-K conv2d shape under plain
    ``square_pallas`` mode -- kernels.routing now auto-selects the im2col
    route here (cache-resident patch matrix, K volume 25), closing the
    ROADMAP conv-route-selection item.  The ``route`` field pins the
    choice so run.py --check flags a route flip.  (At B=1 this shape is
    near route-parity in wall clock -- the regime boundary encodes the
    interpret-mode measurements and the patch-blowup asymptotics.)"""
    from repro.core import conv as conv_core
    from repro.kernels import routing

    rng = np.random.default_rng(1)
    xi = jnp.asarray(rng.normal(size=(64, 64)).astype(np.float32))
    wi = jnp.asarray(rng.normal(size=(5, 5)).astype(np.float32))
    # derive the routed geometry from the arrays actually timed (VALID,
    # stride 1), so the recorded route can never drift from the call
    (h, w_), (kh, kw) = xi.shape, wi.shape
    route = routing.select_conv2d_route(h - kh + 1, w_ - kw + 1, kh, kw,
                                        1, 1, dtype=xi.dtype)
    return [
        {"name": "pallas_sq_conv2d_routed[interp]",
         "us_per_call": _time(
             lambda x, w: conv_core.conv2d(x, w, mode="square_pallas"),
             xi, wi, reps=15),
         "shape": f"{h}x{w_} k{kh}x{kw}", "mode": "f32/routed",
         "route": route.name},
    ]


def lm_forward_rows():
    """End-to-end amortization rows: a small-config LM forward + logits
    under ``square_pallas`` (interpret, eager -- each dense/vocab GEMM
    really runs the Pallas kernel), raw params vs
    ``LM.prepare_params`` prepared weights.  Captures the trajectory of
    the whole-datapath amortization win, not just kernel microbenches."""
    import jax.random as jrandom
    from repro.configs.base import ContractionPolicy, ModelConfig
    from repro.models.lm import build_model

    rng = np.random.default_rng(5)
    pol = ContractionPolicy.of(default="square_pallas",
                               attn_scores="standard", attn_pv="standard")
    # short sequence against wide weights: the serving-prefill regime
    # where the per-call weight prep is a first-order cost
    cfg = ModelConfig(name="bench-tiny", family="dense", n_layers=2,
                      d_model=512, n_heads=4, n_kv_heads=4, d_ff=2048,
                      vocab=16384, head_dim=128, dtype="float32",
                      scan_layers=False, remat="none", attn_chunk_q=8,
                      attn_chunk_kv=8, loss_chunk=8, max_seq=64,
                      matmul_mode="square_pallas", contraction_policy=pol)
    model = build_model(cfg)
    params = model.init(jrandom.PRNGKey(0))
    prepared = model.prepare_params(params)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (1, 8)), jnp.int32)

    def fwd(p):
        hidden, _, _ = model.forward(p, {"tokens": tokens})
        return model.logits(p, hidden)

    raw_us, prep_us = _time_pair(lambda: fwd(params), lambda: fwd(prepared),
                                 reps=3, warmup=1)
    shape = "L2 d512 ff2048 v16384 s8"
    return [
        {"name": "lm_forward_raw[interp]", "us_per_call": raw_us,
         "shape": shape, "mode": "f32/per-call-prep"},
        {"name": "lm_forward_prepared[interp]", "us_per_call": prep_us,
         "shape": shape, "mode": "f32/prepared"},
    ]


def matmul_modes(m=256, k=256, n=256):
    from repro.core import matmul as M
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
    rows = []
    for mode in ("standard", "square_virtual", "square_scan"):
        f = jax.jit(lambda a, b, mode=mode: M.matmul(a, b, mode=mode))
        rows.append({"name": f"matmul[{mode}]", "us_per_call": _time(f, a, b),
                     "shape": f"{m}x{k}x{n}", "mode": mode})
    return rows


def pallas_kernels():
    """The tracked Pallas kernel timings (planner-default tile plans), plus
    a rank-1 reference row (kc=1: one K index per loop iteration, the seed
    kernels' dataflow) so the planned-vs-rank-1 speedup is measured
    in-process, immune to machine-load drift between benchmark runs."""
    from repro.kernels import ops

    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.normal(size=(128, 128)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(128, 128)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(2048,)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(16,)).astype(np.float32))
    xi = jnp.asarray(rng.normal(size=(64, 64)).astype(np.float32))
    wi = jnp.asarray(rng.normal(size=(5, 5)).astype(np.float32))
    # CNN-layer conv2d shape (ROADMAP fused-conv target): 32x32, 64 -> 64
    # channels, 3x3 taps -- fused window streaming vs materialized im2col.
    # Tracked unbatched AND at batch 4: the batched pair is the headline --
    # the im2col route must materialize a B*oh*ow x cin*kh*kw patch matrix
    # (~17 MB at B=4) whose matmul has no cache-resident plan, while the
    # fused kernel runs one batch element per grid step at B=1 efficiency.
    xc = jnp.asarray(rng.normal(size=(64, 32, 32)).astype(np.float32))
    wc = jnp.asarray(rng.normal(size=(64, 64, 3, 3)).astype(np.float32))
    xcb = jnp.asarray(rng.normal(size=(4, 64, 32, 32)).astype(np.float32))
    zx = jnp.asarray((rng.normal(size=(64, 64))
                      + 1j * rng.normal(size=(64, 64))).astype(np.complex64))
    zy = jnp.asarray((rng.normal(size=(64, 64))
                      + 1j * rng.normal(size=(64, 64))).astype(np.complex64))
    # fused-vs-im2col pairs are measured INTERLEAVED (_time_pair): their
    # speedup_vs_im2col ratios feed the --check regression gate, so they
    # must be immune to progressive runner throttling across the bench.
    fused_us, im2col_us = _time_pair(
        lambda: ops.sq_conv2d(xc, wc), lambda: ops.sq_conv2d_im2col(xc, wc),
        reps=8)
    fused_b4_us, im2col_b4_us = _time_pair(
        lambda: ops.sq_conv2d(xcb, wc),
        lambda: ops.sq_conv2d_im2col(xcb, wc), reps=3)
    reps = 15
    return [
        {"name": "pallas_sq_matmul[interp]",
         "us_per_call": _time(ops.sq_matmul, a, b, reps=reps),
         "shape": "128x128x128", "mode": "f32"},
        {"name": "pallas_sq_matmul_rank1[interp]",
         "us_per_call": _time(
             lambda a, b: ops.sq_matmul(a, b, kc=1),
             a, b, reps=reps),
         "shape": "128x128x128", "mode": "f32/rank1-ref"},
        {"name": "pallas_cpm3_matmul[interp]",
         "us_per_call": _time(lambda x, y: ops.cpm3_matmul(x, y)[0], zx, zy,
                              reps=reps),
         "shape": "64x64x64", "mode": "c64"},
        {"name": "pallas_cpm4_matmul[interp]",
         "us_per_call": _time(lambda x, y: ops.cpm4_matmul(x, y)[0], zx, zy,
                              reps=reps),
         "shape": "64x64x64", "mode": "c64"},
        {"name": "pallas_sq_conv[interp]",
         "us_per_call": _time(ops.sq_conv, x, w, reps=reps),
         "shape": "L=2048 taps=16", "mode": "f32"},
        # historical row: same name, same 64x64 k5x5 workload as every
        # prior BENCH_kernels.json -- ops.sq_conv2d now routes it through
        # the fused kernel (the mode field records the route change)
        {"name": "pallas_sq_conv2d[interp]",
         "us_per_call": _time(ops.sq_conv2d, xi, wi, reps=reps),
         "shape": "64x64 k5x5", "mode": "f32/fused"},
        {"name": "pallas_sq_conv2d_fused[interp]",
         "us_per_call": fused_us,
         "shape": "32x32x64->64 k3x3", "mode": "f32/fused"},
        {"name": "pallas_sq_conv2d_im2col[interp]",
         "us_per_call": im2col_us,
         "shape": "32x32x64->64 k3x3", "mode": "f32/im2col"},
        {"name": "pallas_sq_conv2d_fused_b4[interp]",
         "us_per_call": fused_b4_us,
         "shape": "b4 32x32x64->64 k3x3", "mode": "f32/fused"},
        {"name": "pallas_sq_conv2d_im2col_b4[interp]",
         "us_per_call": im2col_b4_us,
         "shape": "b4 32x32x64->64 k3x3", "mode": "f32/im2col"},
    ]


if __name__ == "__main__":
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sweep-sq-matmul", metavar="OUT_JSONL", required=True,
                    help="time the planner's sq_matmul picks against "
                         "other row-walk plans at the cells' shapes, one "
                         "JSON line a plan")
    args = ap.parse_args()
    _require_tpu()
    dev = jax.devices()[0]
    with open(args.sweep_sq_matmul, "w") as f:
        def emit(row):
            row["device_kind"] = dev.device_kind
            line = json.dumps(row)
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()
        sweep_sq_matmul(emit=emit)
