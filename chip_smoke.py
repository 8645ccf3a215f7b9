"""One-chip smoke run of the serving and training paths on a TPU.

    python chip_smoke.py

Drives the main path once through the entry points a user calls, at the
full published widths of the registry's models, with random weights made
from a fixed seed:

- serving: ``Engine`` pages h2o-danube-3-4b (24 layers, d3840, bf16) and
  serves 4 seeded requests of 64-256 prompt tokens and 32 new tokens, once
  with ``matmul_mode="standard"`` and once on the paper's square path
  (``square_pallas`` + ``SQUARE_GEMMS_POLICY``, prepared weights), whose
  decode step reads the paged KV pool through the fused Pallas kernel;
- training: ``Trainer`` takes 3 steps of fairsquare-demo (12 layers, d768,
  vocab 32000) at global batch 8 x 512 tokens in both modes, so the
  square path's custom-VJP backward kernels run too.

It fails (non-zero exit, no result line) unless every request ends
``COMPLETED`` with 32 tokens, no route was demoted and no step failed,
the square decode step holds a Pallas kernel (``tpu_custom_call``), and
the square path's logits and first-step loss agree with the standard
path within the tolerances below.  The last line of stdout is one JSON
object naming the device.  One process holds the chip throughout; the
run needs a TPU and exits non-zero before any work on any other backend.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import SQUARE_GEMMS_POLICY  # noqa: E402
from repro.data.pipeline import DataConfig, SyntheticLM  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.kernels import routing  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import make_requests  # noqa: E402
from repro.models.lm import build_model  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.serve.engine import Engine, EngineConfig  # noqa: E402
from repro.train import step as step_mod  # noqa: E402
from repro.train.trainer import Trainer, TrainerConfig  # noqa: E402

SEED = 0
SERVE_ARCH = "h2o-danube-3-4b"
N_REQUESTS, PROMPT_LO, PROMPT_HI, NEW_TOKENS = 4, 64, 256, 32
ENGINE = dict(max_slots=4, block_size=16, blocks_per_seq=20,
              num_blocks=1 + 4 * 20, prefill_chunk=32,
              max_new_tokens=NEW_TOKENS)
TRAIN_ARCH = "fairsquare-demo"
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 512, 3

# Square vs standard logits: max |difference| over max |standard logit|.
# The square path accumulates (a+b)^2 in f32, and its row/column
# corrections cancel terms ~sqrt(K)*|x|/|w| larger than the product (the
# PM dynamic-range caveat, docs/training.md): one GEMM of unit
# activations against 1/sqrt(K)-scaled bf16 weights is off by ~3e-4 of
# its range at K=3840 and ~2e-3 at K=10240 (CPU interpret).  A 24-layer
# random-init model amplifies that to a few 1e-2 at the logits (4.8e-2
# prefill, 3.4e-2 decode on a v5e).  0.15 leaves 3x room over that and
# stays far below what a wrong kernel gives: a dropped correction term or
# a misaddressed KV block moves logits by O(1) of their range.
LOGITS_RTOL = 0.15
# Square vs standard first-step loss, absolute.  At init the loss is
# ~ln(32000) = 10.4, averaged over 4096 tokens, which averages the same
# drift down to ~1e-3 (5.8e-4 on a v5e); 5e-2 is far above that and far
# below what a broken path gives.
LOSS_ATOL = 5e-2


def square(cfg):
    """The paper's path: square kernels everywhere but the softmax GEMMs."""
    return dataclasses.replace(cfg, matmul_mode="square_pallas",
                               contraction_policy=SQUARE_GEMMS_POLICY)


def standard(cfg):
    return dataclasses.replace(cfg, matmul_mode="standard",
                               contraction_policy=None)


def rel_diff(x, ref) -> float:
    return float(np.max(np.abs(x - ref)) / np.max(np.abs(ref)))


def probe(engine, prompt):
    """Logits of one prefill chunk and of the decode step after it, through
    the engine's own compiled steps, on a fixed block layout (blocks
    1..blocks_per_seq for slot 0, every other slot idle).  The calls are
    functional: the engine's cache and pool are untouched."""
    c = engine.cfg
    table = np.arange(1, c.blocks_per_seq + 1, dtype=np.int32)
    C = c.prefill_chunk
    hidden, cache, pos_pool = engine._chunk(
        engine.params, engine.cache, engine.pos_pool,
        jnp.asarray(table[None]), jnp.asarray(prompt[None, :C]),
        jnp.arange(C, dtype=jnp.int32)[None])
    prefill = engine._logits_at(engine.params, hidden, jnp.int32(C - 1))[0]
    tables = np.zeros((c.max_slots, c.blocks_per_seq), np.int32)
    tables[0] = table
    toks = np.zeros((c.max_slots, 1), np.int32)
    toks[0, 0] = prompt[C]
    poss = np.full((c.max_slots, 1), -1, np.int32)
    poss[0, 0] = C
    args = (engine.params, cache, pos_pool, jnp.asarray(tables),
            jnp.asarray(toks), jnp.asarray(poss))
    decode = engine._decode(*args)[0][0]
    return (np.asarray(prefill, np.float32), np.asarray(decode, np.float32),
            args)


def serve(cfg, params, *, prepared: bool, label: str):
    """Serve the seeded requests through one Engine; returns the probe
    logits and the decode step's compiled HLO text."""
    model = build_model(cfg)
    reqs = make_requests(cfg, N_REQUESTS, seed=SEED, lo=PROMPT_LO,
                         hi=PROMPT_HI + 1)
    engine = Engine(model, params, EngineConfig(**ENGINE, prepared=prepared))
    t0 = time.perf_counter()
    prefill, decode, args = probe(engine, np.asarray(reqs[0].tokens))
    t_probe = time.perf_counter() - t0
    t0 = time.perf_counter()
    results = engine.run(reqs)
    t_serve = time.perf_counter() - t0
    hlo = engine._decode.lower(*args).compile().as_text()
    m = engine.metrics
    print(f"serve[{label}]: compile+probe {t_probe:.1f}s | "
          f"{m.tokens_out} tokens from {len(results)} requests in "
          f"{t_serve:.1f}s | {m.prefill_chunks} prefill chunks, "
          f"{m.decode_steps} decode steps, {m.step_failures} step failures",
          flush=True)
    bad = {rid: (str(r.status), len(r.tokens), r.error)
           for rid, r in results.items()
           if not r.ok or len(r.tokens) != NEW_TOKENS}
    if len(results) != N_REQUESTS or bad:
        raise SystemExit(f"serve[{label}]: requests did not complete: {bad}")
    if m.step_failures:
        raise SystemExit(f"serve[{label}]: {m.step_failures} step failures")
    return prefill, decode, hlo


def check_routes(label: str):
    demoted = [h["key"] for h in routing.route_health().snapshot()
               if h["demoted"]]
    if demoted:
        raise SystemExit(f"{label}: routes demoted: {demoted}")


def serve_phase():
    cfg = get_config(SERVE_ARCH)
    t0 = time.perf_counter()
    params = build_model(cfg).init(jax.random.PRNGKey(SEED))
    jax.block_until_ready(params)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    print(f"serve: {SERVE_ARCH} {cfg.n_layers} layers d{cfg.d_model}, "
          f"{n / 1e9:.2f}B params ({cfg.dtype}) made in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    route = routing.select_paged_attn_route(
        1, ENGINE["blocks_per_seq"] * ENGINE["block_size"],
        batch=ENGINE["max_slots"], kv_heads=cfg.n_kv_heads,
        group=cfg.n_heads // cfg.n_kv_heads, hd=cfg.resolved_head_dim,
        dtype=jnp.dtype(cfg.dtype))
    print(f"serve: decode paged-attention route {route.name!r} "
          f"({route.reason})", flush=True)
    if route.name != "kernel":
        raise SystemExit("serve: decode does not take the paged kernel")

    ref_pre, ref_dec, _ = serve(standard(cfg), params, prepared=False,
                                label="standard")
    gc.collect()
    sq_pre, sq_dec, hlo = serve(square(cfg), params, prepared=True,
                                label="square")
    check_routes("serve")
    n_kernels = hlo.count("tpu_custom_call")
    d_pre, d_dec = rel_diff(sq_pre, ref_pre), rel_diff(sq_dec, ref_dec)
    print(f"serve: square decode step holds {n_kernels} tpu_custom_call "
          f"op(s); logits rel diff prefill {d_pre:.3e}, decode {d_dec:.3e} "
          f"(tol {LOGITS_RTOL:g}); argmax match prefill "
          f"{int(sq_pre.argmax() == ref_pre.argmax())}, decode "
          f"{int(sq_dec.argmax() == ref_dec.argmax())}", flush=True)
    if not n_kernels:
        raise SystemExit("serve: the square decode step holds no Pallas "
                         "kernel")
    if not (np.isfinite(sq_pre).all() and np.isfinite(sq_dec).all()):
        raise SystemExit("serve: non-finite square-path logits")
    if max(d_pre, d_dec) > LOGITS_RTOL:
        raise SystemExit("serve: square logits disagree with standard")


def train(cfg, label: str):
    """3 Trainer steps; returns the loss trajectory."""
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(SEED))
    tcfg = step_mod.TrainConfig(opt=adamw.AdamWConfig(
        lr=3e-4, warmup_steps=1, total_steps=TRAIN_STEPS))
    step = jax.jit(step_mod.make_train_step(model, tcfg))
    data = SyntheticLM(DataConfig(global_batch=TRAIN_BATCH,
                                  seq_len=TRAIN_SEQ, vocab=cfg.vocab,
                                  seed=SEED), cfg)
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory() as ckpt_dir:
        out = Trainer(TrainerConfig(total_steps=TRAIN_STEPS,
                                    ckpt_every=TRAIN_STEPS + 1,
                                    ckpt_dir=ckpt_dir, log_every=1),
                      step, params, adamw.adamw_init(params), data).run()
    dt = time.perf_counter() - t0
    losses = out["loss_trajectory"]
    audit = out["contraction_audit"] or {}
    print(f"train[{label}]: {out['final_step']} steps in {dt:.1f}s "
          f"(compile included) | losses "
          + " ".join(f"{x:.5f}" for x in losses)
          + f" | step failures {out['step_failures']}, rollbacks "
          f"{out['rollbacks']}, ckpt failures {out['ckpt_failures']} | "
          f"square fraction fwd {audit.get('fraction_square', 0):.3f} "
          f"bwd {audit.get('fraction_square_bwd', 0):.3f}", flush=True)
    if (out["final_step"] != TRAIN_STEPS or len(losses) != TRAIN_STEPS
            or not np.isfinite(losses).all() or out["step_failures"]
            or out["rollbacks"] or out["ckpt_failures"]):
        raise SystemExit(f"train[{label}]: run did not complete cleanly")
    return losses


def train_phase():
    cfg = get_config(TRAIN_ARCH)
    ref = train(standard(cfg), "standard")
    sq = train(square(cfg), "square")
    check_routes("train")
    d = abs(sq[0] - ref[0])
    print(f"train: first-step loss |square - standard| {d:.3e} "
          f"(tol {LOSS_ATOL:g})", flush=True)
    if d > LOSS_ATOL:
        raise SystemExit("train: square first-step loss disagrees")


def main():
    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{dev.platform!r}")
    if kops.default_interpret():
        raise SystemExit("chip_smoke: kernels would run in interpret mode")
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"device: {dev.device_kind} x{len(jax.devices())} | compile "
          f"cache {cache_dir} ({entries} entries at start)", flush=True)
    t0 = time.perf_counter()
    serve_phase()
    gc.collect()
    train_phase()
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"all phases passed in {time.perf_counter() - t0:.1f}s | compile "
          f"cache {cache_dir} ({entries} entries at end)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
