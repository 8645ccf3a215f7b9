"""Training benchmark: the square-routed train step vs the multiplier
baseline (ROADMAP direction 4, "training as a workload").

One small-but-real LM runs N fixed-seed AdamW steps under three modes:

- ``standard``       -- multiplier-baseline GEMMs (the reference row);
- ``square_virtual`` -- every contraction square-routed through the MXU
                        identity, forward AND backward: the fs_einsum
                        custom VJP re-enters the dispatcher for dL/dx and
                        dL/dW as ``<site>.bwd_x`` / ``<site>.bwd_w``
                        (the gated pair);
- ``square_pallas``  -- the Pallas kernel route (informational on this
                        interpret host).

A fourth row, ``train_step_square_guarded[jit]``, runs the same
square-virtual step through :class:`repro.train.step.GuardedStep` -- the
compiled numerics guard (host-callback finite probes + drain/demote/
re-jit, docs/robustness.md) -- and is gated near unguarded parity with
``guard_trips == 0`` on this clean run: the guard must cost ~nothing
until it fires, and must not perturb the bit trajectory.

Reported per row: steady-state step time (jitted, trace excluded,
interleaved across modes so the gated ratio is immune to runner-load
drift), the fraction of TOTAL train FLOPs square-routed (forward + the
custom-VJP backward), the backward-only square fraction, and the
loss-curve **bit-trajectory hash** over the N steps
(:func:`repro.optim.adamw.tree_fingerprint` of the per-step loss
sequence -- bit-identical across runs on one host, so trajectory drift
across commits is visible in the JSON diff).

The square fractions come from the COMPILED audit
(:func:`repro.core.counting.track_compiled_contractions` over a trace
made under :func:`~repro.core.counting.compiled_audit`): they cover
every executed step of the trajectory, cached-jit executions included.
The bench previously audited only the first (tracing) call -- steps
2..N ran entirely unobserved -- and the old trace-time path on a cached
step still warns-and-zeros (:class:`~repro.core.counting
.EmptyAuditWarning`), which this bench asserts on every run so the
pre-fix blind spot stays pinned.

``BENCH_training.json`` feeds ``run.py --check``: the square-routed step
must hold ``speedup_vs_standard >= 1.0 - tol``, the square row's
backward fraction must stay >= 0.9 (a VJP regression that silently
reroutes backward GEMMs to the multiplier baseline fails here), and the
guarded row must hold near-parity vs the unguarded square row with zero
trips and an identical bit trajectory.
"""
from __future__ import annotations

import dataclasses
import json
import time
import warnings
from typing import Dict, List

import numpy as np

import jax

from repro.configs.base import ContractionPolicy, ModelConfig
from repro.core import counting
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models.lm import build_model
from repro.optim import adamw
from repro.train import step as step_mod

TRAINING_JSON = "BENCH_training.json"

# Train-bench model: the serving-bench geometry (qkv/out 256x256, ffn
# 256<->1024, vocab-logits 4096) shrunk to 2 layers so a jitted train
# step -- forward, VJP backward, AdamW -- stays interpret-host friendly.
# The attention softmax path rides the policy split like production
# configs do; everything else (including the loss vocab GEMM and every
# backward contraction) square-routes.
BENCH_POLICY = ContractionPolicy.of(attn_scores="standard",
                                    attn_pv="standard")
BENCH_CFG = ModelConfig(
    name="train-bench", family="dense", n_layers=2, d_model=256,
    n_heads=4, n_kv_heads=4, d_ff=1024, vocab=4096, head_dim=64,
    dtype="float32", scan_layers=False, remat="none", attn_chunk_q=32,
    attn_chunk_kv=32, loss_chunk=32, max_seq=128,
    matmul_mode="square_virtual", contraction_policy=BENCH_POLICY)

BATCH, SEQ = 2, 64          # forward GEMM rows M = BATCH * SEQ = 128
N_STEPS = 4                 # fixed-seed trajectory length (and timing span)
DATA_SEED = 123

# Tolerance floor for the square-vs-standard step-time gate.  On this
# CPU host the virtual-square step pays its O(M*K + K*N) correction
# terms without an MXU to hide them behind (~0.89x standard measured);
# the floor keeps the gate meaningful -- it still catches a step that
# goes catastrophically slow or a backward that stops square-routing --
# while the parity regime stays the TPU (same stance as the serving
# bench's LONG_ROW_TOL_FLOOR; see docs/tuning.md).
TRAIN_ROW_TOL_FLOOR = 0.2

# Floor for the guarded-vs-unguarded parity gate: the clean-path guard
# overhead is the in-graph probe reduces plus one effects_barrier drain
# per step -- host-callback latency the interpret host cannot hide
# (~0.77x unguarded measured here; the floor leaves noise headroom
# while still catching a guard whose happy path goes catastrophic).
GUARDED_ROW_TOL_FLOOR = 0.4

# Modes in the bench: (row key, matmul_mode); the square_guarded row is
# derived from square_virtual via GuardedStep in training_rows().
MODES = (("standard", "standard"),
         ("square_virtual", "square_virtual"),
         ("square_pallas", "square_pallas"))


def _setup(mode: str):
    """(raw step fn, params, opt_state, batches) for one mode.  The raw
    (unjitted) builder output is returned so callers control the jit:
    the timing closure, the compiled-audit closure and the GuardedStep
    wrapper each need their own trace."""
    if mode == "standard":
        cfg = dataclasses.replace(BENCH_CFG, matmul_mode="standard",
                                  contraction_policy=None)
    else:
        cfg = dataclasses.replace(BENCH_CFG, matmul_mode=mode)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = adamw.adamw_init(params)
    data = SyntheticLM(DataConfig(global_batch=BATCH, seq_len=SEQ,
                                  vocab=cfg.vocab, seed=DATA_SEED), cfg)
    batches = data.take(N_STEPS)
    raw = step_mod.make_train_step(model, step_mod.TrainConfig())
    return raw, params, opt, batches


def _compiled_fractions(raw, params, opt, batches):
    """Square fractions covering EVERY step of the trajectory (the
    compiled audit: runtime notes fire per execution, cached or not),
    plus a pinned demonstration that the old trace-time audit of a
    cached step warns-and-zeros -- the pre-fix bench reported fractions
    for the tracing call only."""
    with counting.compiled_audit():
        audited = jax.jit(lambda *a: raw(*a))
        p1, o1, _ = audited(params, opt, batches[0])      # trace + run
        jax.block_until_ready(p1)
    with counting.track_compiled_contractions() as ctr:
        p, o = params, opt
        for batch in batches:
            p, o, metrics = audited(p, o, batch)
        jax.block_until_ready(p)

    # the OLD audit path on the (now cached) step: zero notes + warning
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        _, old_ctr = step_mod.audit_step(audited, params, opt, batches[0])
    assert old_ctr.total_mults == 0, \
        "trace-time audit unexpectedly saw a cached-jit execution"
    assert any(issubclass(c.category, counting.EmptyAuditWarning)
               for c in caught), \
        "EmptyAuditWarning pin lost: the trace-time audit of a cached " \
        "step no longer warns"
    return ctr


def _run_steps(step, params, opt, batches):
    """Run the fixed-seed trajectory; returns (losses, final params)."""
    losses = []
    for batch in batches:
        params, opt, metrics = step(params, opt, batch)
        losses.append(float(np.asarray(metrics["loss"])))
    jax.block_until_ready(params)
    return losses, params


def training_rows() -> List[Dict]:
    """Measure the train-step configurations; returns BENCH rows."""
    runs: Dict[str, Dict] = {}
    for key, mode in MODES:
        raw, params, opt, batches = _setup(mode)
        # Compiled audit (separate trace): fractions over the WHOLE
        # trajectory -- every step observed, not just the tracing call.
        ctr = _compiled_fractions(raw, params, opt, batches)
        # Clean timing closure: no baked runtime notes, no probes.
        step = jax.jit(raw)
        p1, o1, _ = step(params, opt, batches[0])       # trace
        jax.block_until_ready(p1)
        losses, final = _run_steps(step, params, opt, batches)
        runs[key] = {
            "step": step, "params": params, "opt": opt, "batches": batches,
            "fraction_square": ctr.fraction_square,
            "fraction_square_bwd": ctr.fraction_square_bwd,
            "bwd_mults": ctr.bwd_mults,
            "losses": losses,
            "loss_traj_hash": adamw.tree_fingerprint(
                np.asarray(losses, np.float32)),
            "params_hash": adamw.tree_fingerprint(final),
        }

    # The guarded row: the SAME square-virtual step under the compiled
    # numerics guard (probes baked into the trace, pending-trip drain
    # after every call).  A clean run must be trip-free and bit-identical
    # to the unguarded square row -- the guard's cost is the probe
    # reduces + one effects_barrier per step, gated near parity below.
    raw_sq, params, opt, batches = _setup("square_virtual")
    guarded = step_mod.GuardedStep(raw_sq, jit=True)
    p1, o1, _ = guarded(params, opt, batches[0])        # trace
    jax.block_until_ready(p1)
    losses_g, final_g = _run_steps(guarded, params, opt, batches)
    runs["square_guarded"] = {
        "step": guarded, "params": params, "opt": opt, "batches": batches,
        "fraction_square": runs["square_virtual"]["fraction_square"],
        "fraction_square_bwd": runs["square_virtual"]["fraction_square_bwd"],
        "bwd_mults": runs["square_virtual"]["bwd_mults"],
        "losses": losses_g,
        "loss_traj_hash": adamw.tree_fingerprint(
            np.asarray(losses_g, np.float32)),
        "params_hash": adamw.tree_fingerprint(final_g),
    }

    # Steady-state step timing on the already-traced closures, modes
    # interleaved per rep so the gated standard/square ratio is a
    # same-process, load-drift-immune quantity.
    keys = [key for key, _ in MODES] + ["square_guarded"]
    best_s = {key: float("inf") for key in keys}
    for _ in range(3):
        for key in keys:
            r = runs[key]
            t0 = time.monotonic()
            _run_steps(r["step"], r["params"], r["opt"], r["batches"])
            dt = (time.monotonic() - t0) / N_STEPS
            best_s[key] = min(best_s[key], dt)

    rows = []
    for key, mode in MODES + (("square_guarded", "square_virtual"),):
        r = runs[key]
        row = {
            "name": f"train_step_{key}[jit]",
            "mode": mode,
            "shape": f"L{BENCH_CFG.n_layers} d{BENCH_CFG.d_model} "
                     f"v{BENCH_CFG.padded_vocab} B{BATCH} S{SEQ}",
            "us_per_step": best_s[key] * 1e6,
            "steps": N_STEPS,
            "loss_first": r["losses"][0],
            "loss_last": r["losses"][-1],
            "losses_finite": bool(np.isfinite(r["losses"]).all()),
            "fraction_square": r["fraction_square"],
            "fraction_square_bwd": r["fraction_square_bwd"],
            "bwd_mults": r["bwd_mults"],
            "loss_traj_hash": r["loss_traj_hash"],
            "params_hash": r["params_hash"],
        }
        if key != "standard":
            row["speedup_vs_standard"] = \
                best_s["standard"] / best_s[key] if best_s[key] else 0.0
        if key == "square_guarded":
            stats = runs["square_guarded"]["step"].stats()
            row["guard_trips"] = stats["guard_trips"]
            row["guard_rejits"] = stats["rejits"]
            row["speedup_vs_unguarded"] = \
                best_s["square_virtual"] / best_s[key] if best_s[key] else 0.0
        rows.append(row)
    return rows


def build_training_payload(rows: List[Dict]) -> Dict:
    return {"rows": rows}


def write_training_json(payload: Dict, path: str = TRAINING_JSON) -> Dict:
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"\nwrote {path}")
    return payload


def check_training(payload: Dict, tol: float) -> List[str]:
    """Regression gate over the training rows (called by run.py --check):

    - the square-routed (``square_virtual``) step must hold
      ``speedup_vs_standard >= 1.0 - tol`` (tol floored at
      :data:`TRAIN_ROW_TOL_FLOOR` -- the interpret-host correction-term
      slack, see the constant's comment) vs the multiplier baseline;
    - the square row must keep >= 0.9 of its TOTAL train FLOPs
      square-routed AND >= 0.9 of its backward volume square-routed
      (``fraction_square_bwd``): a custom-VJP regression that silently
      reroutes dL/dx / dL/dW to the standard path fails here, exactly the
      pre-VJP behavior this bench exists to pin;
    - every row's fixed-seed loss trajectory must be finite, with the
      bit-trajectory hash present (trajectory drift shows as a hash
      change in the committed JSON).

    The guarded row (``train_step_square_guarded[jit]``) is gated on
    three axes: **near-parity** vs the unguarded square row
    (``speedup_vs_unguarded >= 1.0 - max(tol, GUARDED_ROW_TOL_FLOOR)``
    -- the clean-path cost of the baked probes + per-step drain),
    **zero guard trips** on this clean run (a tripping bench means the
    probes are firing on healthy numerics), and a **bit trajectory
    identical** to the unguarded square row (the guard must observe,
    never perturb).

    The ``square_pallas`` row is informational on interpret hosts (same
    near-parity story as the fused conv/paged-attn kernels -- the kernel
    regime is the TPU; see docs/tuning.md) and is NOT time-gated.
    """
    failures = []
    rows = {r["name"]: r for r in payload.get("rows", [])}
    sq = rows.get("train_step_square_virtual[jit]")
    if sq is None:
        failures.append("training: square_virtual row missing")
    else:
        step_tol = max(tol, TRAIN_ROW_TOL_FLOOR)
        ratio = sq.get("speedup_vs_standard", 0.0)
        if ratio < 1.0 - step_tol:
            failures.append(f"training: square_virtual step ratio "
                            f"{ratio:.2f} < {1.0 - step_tol:.2f} vs standard")
        if sq.get("fraction_square", 0.0) < 0.9:
            failures.append(f"training: fraction_square "
                            f"{sq.get('fraction_square', 0.0):.2f} < 0.90")
        if sq.get("fraction_square_bwd", 0.0) < 0.9:
            failures.append(
                f"training: backward square fraction "
                f"{sq.get('fraction_square_bwd', 0.0):.2f} < 0.90 "
                f"(custom-VJP backward not square-routed)")
    g = rows.get("train_step_square_guarded[jit]")
    if g is None:
        failures.append("training: square_guarded row missing")
    else:
        gtol = max(tol, GUARDED_ROW_TOL_FLOOR)
        ratio = g.get("speedup_vs_unguarded", 0.0)
        if ratio < 1.0 - gtol:
            failures.append(f"training: guarded step ratio {ratio:.2f} < "
                            f"{1.0 - gtol:.2f} vs unguarded square")
        if g.get("guard_trips", -1) != 0:
            failures.append(f"training: guarded clean run tripped "
                            f"{g.get('guard_trips')} time(s) (expected 0)")
        if sq is not None and \
                g.get("loss_traj_hash") != sq.get("loss_traj_hash"):
            failures.append("training: guarded loss trajectory diverged "
                            "from the unguarded square row (the guard "
                            "must observe, never perturb)")
    for name, row in rows.items():
        if not row.get("losses_finite", False):
            failures.append(f"training: {name} loss trajectory not finite")
        if not row.get("loss_traj_hash"):
            failures.append(f"training: {name} missing loss_traj_hash")
    return failures
