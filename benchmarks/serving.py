"""Serving-engine benchmark: utilization-shaped, not single-call-shaped.

Runs the paged continuous-batching engine (`repro.serve.engine`) over a
ragged request mix on a small-but-real LM and reports the serving metrics
the ROADMAP north star cares about: tokens/s, time-to-first-token,
cache-block utilization, batch occupancy -- and the paper's quantity, the
fraction of serving contraction FLOPs routed through square-form
arithmetic (`core/counting`).

Five engine configurations ride one workload:

- ``standard``        -- multiplier-baseline GEMMs (context row);
- ``square_raw``      -- ``square_pallas`` GEMMs, weights prepared per
                         call (the per-call column prep is real work);
- ``square_prepared`` -- the same square route with ``LM.prepare_params``
                         run ONCE at engine start (paper §4-§5: the
                         weight-stationary regime decode serving lives in);
- ``square_guarded``  -- square_prepared plus the full resilience layer
                         (``EngineConfig(guard=True)``: per-step logits
                         finiteness checks AND the core-layer square-route
                         guard, live because the bench is eager).  Its
                         gated ratio vs square_prepared is the measured
                         cost of the guard-rails on the happy path.
- ``square_traced``   -- square_prepared with structured tracing
                         (``repro.obs.trace``) live for the whole run.
                         Its gated ratio vs square_prepared (>= 0.9 -
                         tol) bounds the cost of full observability; the
                         prepared row itself runs with tracing disabled,
                         so its own gates double as the
                         tracing-off-is-free check.

Execution is EAGER (``EngineConfig(jit=False)``: the engine steps run
op-by-op, like the prepared-operand rows in ``kernel_timing.py``): under
jit both paths trace identically and the prep is free via jit caching;
eager/interpret execution is where the amortization contract is
measurable.  The square_raw / square_prepared runs are INTERLEAVED across
reps so their ratio is immune to runner-load drift (same rationale as
``kernel_timing._time_pair``).

A second, JITTED row family (:func:`long_context_rows`) covers the
regime the eager rows cannot reach: ~512-token prefills decoding against
long block tables, where the paged-attention read itself is the
interesting cost.  One workload runs under both read routes (the fused
square kernel vs the dense gather; `REPRO_ROUTE=paged_attn=...` pinned
at trace time) on pre-warmed engines, so the gated ratio is
steady-state serving throughput with trace/compile excluded -- plus an
SWA pair (window eviction on/off) whose gated quantity is the
deterministic ``peak_blocks_used`` footprint.

``BENCH_serving.json`` rows feed the ``run.py --check`` regression gate:
the prepared-square row must stay >= 1.0x the raw-square row (minus
``$BENCH_CHECK_TOL``), the kernel-route row >= 1.0x - tol the gather
row with identical greedy tokens, and the evicting SWA engine strictly
below the retaining one on ``peak_blocks_used``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
from typing import Dict, List

import jax

from repro.configs.base import ContractionPolicy, ModelConfig
from repro.core import counting
from repro.launch.serve import make_requests
from repro.models.lm import build_model
from repro.obs import trace as obs_trace
from repro.serve.engine import Engine, EngineConfig, EngineMetrics
from repro.serve.server import Request

SERVING_JSON = "BENCH_serving.json"

# Serving-bench model: small enough for eager interpret execution, real
# enough that decode hits the engine's characteristic GEMM shapes
# (qkv/out 256x256, ffn 256<->1024, vocab logits 4096) at slot-batch M.
# scan_layers=False so LM.prepare_params covers the WHOLE stack.
BENCH_POLICY = ContractionPolicy.of(attn_scores="standard",
                                    attn_pv="standard")
BENCH_CFG = ModelConfig(
    name="serve-bench", family="dense", n_layers=2, d_model=256,
    n_heads=4, n_kv_heads=4, d_ff=1024, vocab=4096, head_dim=64,
    dtype="float32", scan_layers=False, remat="none", attn_chunk_q=16,
    attn_chunk_kv=16, loss_chunk=16, max_seq=128,
    matmul_mode="square_pallas", contraction_policy=BENCH_POLICY)

ENGINE_KW = dict(max_slots=8, block_size=8, num_blocks=64, blocks_per_seq=6,
                 prefill_chunk=16, max_new_tokens=4)
N_REQUESTS = 8

# Long-context paged-decode geometry: the regime the fused paged-attention
# kernel exists for -- ~512-token prefills whose block tables are long
# enough (T = blocks_per_seq * block_size = 640 >= PAGED_KERNEL_MIN_T)
# that the gather route's per-step (B, T, KV, hd) copy is real traffic.
# KV=1 (MQA-shaped) keeps the kernel grid small under interpret mode
# while the gathered window stays full-size.  These rows run JITTED
# (unlike the eager rows above): the kernel-vs-gather contest is a
# steady-state serving contest, so each engine is warmed once (paying
# trace+compile) and then timed over fresh requests on the same jit
# closures -- route pinned via REPRO_ROUTE at trace time.
LONG_CFG = dataclasses.replace(BENCH_CFG, name="serve-bench-long",
                               n_kv_heads=1, max_seq=1024)
# the SWA variant: same geometry with every layer windowed, so the
# engine's block-level eviction (EngineConfig.window_eviction) can
# retire aged blocks; window == block_size keeps the live footprint at
# ceil(window/bs) + 1 = 2 blocks/seq no matter how long decode runs
SWA_CFG = dataclasses.replace(LONG_CFG, name="serve-bench-swa", window=64)
LONG_ENGINE_KW = dict(max_slots=2, block_size=64, num_blocks=24,
                      blocks_per_seq=10, prefill_chunk=128,
                      max_new_tokens=32)
N_LONG = 2
LONG_LO, LONG_HI = 512, 521

# Tolerance floor for the kernel-vs-gather tokens/s gate.  The fused
# kernel's no-copy dataflow pays off on the TPU "mkn" schedule; on this
# CPU/interpret proxy host the per-grid-step op overhead keeps the
# attention call itself behind the gather copy (same story as the
# fused-vs-im2col conv near-parity -- see docs/tuning.md), so the
# engine-level ratio sits a little under 1.0 (~0.8 measured).  The gate
# still catches a route that goes catastrophically slow or diverges; on
# TPU hosts tighten $BENCH_CHECK_TOL and re-measure.
LONG_ROW_TOL_FLOOR = 0.25


@contextlib.contextmanager
def _pinned_paged_route(route: str):
    """Pin the paged-attention route for everything traced inside."""
    prev = os.environ.get("REPRO_ROUTE")
    os.environ["REPRO_ROUTE"] = f"paged_attn={route}"
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop("REPRO_ROUTE", None)
        else:
            os.environ["REPRO_ROUTE"] = prev


def _run_once(model, params, *, prepared: bool, guard: bool = False,
              traced: bool = False) -> Engine:
    eng = Engine(model, params, EngineConfig(prepared=prepared, jit=False,
                                             guard=guard, **ENGINE_KW))
    reqs = make_requests(model.cfg, N_REQUESTS, seed=17, lo=4, hi=13)
    if traced:
        # full structured tracing live for the whole run (the overhead
        # row): every tick/prefill/decode span lands in the ring buffer
        with obs_trace.capture() as tr:
            eng.run(reqs)
        eng.trace_records = len(tr.records())
    else:
        eng.run(reqs)
    return eng


def _row(name: str, mode: str, eng: Engine, cfg: ModelConfig = BENCH_CFG,
         kw: Dict = ENGINE_KW, **extra) -> Dict:
    m = eng.metrics
    row = {"name": name, "mode": mode,
           "shape": f"L{cfg.n_layers} d{cfg.d_model} "
                    f"v{cfg.padded_vocab} slots{kw['max_slots']}",
           "tokens_per_s": m.tokens_per_s,
           "tokens_out": m.tokens_out,
           "mean_ttft_s": m.mean_ttft_s,
           "mean_block_utilization": m.mean_utilization,
           "peak_blocks_used": m.peak_blocks_used,
           "batch_occupancy": m.batch_occupancy,
           "preemptions": m.preemptions}
    row.update(extra)
    return row


def serving_rows(reps: int = 2) -> List[Dict]:
    """Measure the three engine configurations; returns BENCH rows."""
    model_sq = build_model(BENCH_CFG)
    params = model_sq.init(jax.random.PRNGKey(0))
    cfg_std = dataclasses.replace(BENCH_CFG, matmul_mode="standard",
                                  contraction_policy=None)
    model_std = build_model(cfg_std)

    # square-routed fraction of serving FLOPs, counted on an eager run
    # (trace-time counting records nothing under cached jit); this run
    # doubles as the raw-config warmup
    with counting.track_contractions() as ctr:
        eng_counted = _run_once(model_sq, params, prepared=False)
    fraction_square = ctr.fraction_square
    audit = ctr.summary()
    # cross-validate the observability layer against the audit: publish
    # the audit into the counted engine's registry and read the gauge
    # back out of the snapshot -- run.py --check gates the two agreeing
    snap = eng_counted.obs_snapshot(audit=audit)
    registry_fraction_square = snap["gauges"]["counting_fraction_square"]
    c = snap["counters"]
    registry_conserved = (
        sum(c[f"engine_requests_{k}_total"]
            for k in ("completed", "rejected", "shed", "timeouts",
                      "failures", "cancelled"))
        == c["engine_requests_submitted_total"])

    # one warmup per remaining config: the first run of each pays one-time
    # costs (plan-cache fills, allocator warmup)
    # that would otherwise bias whichever config runs first
    _run_once(model_sq, params, prepared=True)
    _run_once(model_sq, params, prepared=True, guard=True)
    _run_once(model_sq, params, prepared=True, traced=True)
    _run_once(model_std, params, prepared=False)

    best: Dict[str, Engine] = {}
    for _ in range(reps):
        # interleave raw/prepared/guarded/traced so the gated ratios are
        # immune to progressive runner throttling across the bench
        for key, model, prep, grd, trc in (
                ("raw", model_sq, False, False, False),
                ("prepared", model_sq, True, False, False),
                ("guarded", model_sq, True, True, False),
                ("traced", model_sq, True, False, True),
                ("standard", model_std, False, False, False)):
            eng = _run_once(model, params, prepared=prep, guard=grd,
                            traced=trc)
            if key not in best or (eng.metrics.tokens_per_s
                                   > best[key].metrics.tokens_per_s):
                best[key] = eng

    tps_raw = best["raw"].metrics.tokens_per_s
    tps_prep = best["prepared"].metrics.tokens_per_s
    tps_grd = best["guarded"].metrics.tokens_per_s
    tps_trc = best["traced"].metrics.tokens_per_s
    return [
        _row("serving_engine_standard[interp-eager]", "standard",
             best["standard"]),
        _row("serving_engine_square_raw[interp-eager]",
             "square_pallas/per-call-prep", best["raw"],
             fraction_square=fraction_square,
             registry_fraction_square=registry_fraction_square,
             registry_conserved=registry_conserved),
        _row("serving_engine_square_prepared[interp-eager]",
             "square_pallas/prepared", best["prepared"],
             fraction_square=fraction_square,
             speedup_vs_raw=tps_prep / tps_raw if tps_raw else 0.0),
        _row("serving_engine_square_guarded[interp-eager]",
             "square_pallas/prepared+guard", best["guarded"],
             guard_trips=best["guarded"].metrics.guard_trips,
             speedup_vs_prepared=tps_grd / tps_prep if tps_prep else 0.0),
        _row("serving_engine_square_traced[interp-eager]",
             "square_pallas/prepared+trace", best["traced"],
             trace_records=getattr(best["traced"], "trace_records", 0),
             speedup_vs_prepared=tps_trc / tps_prep if tps_prep else 0.0),
    ]


def _long_requests(rid0: int) -> List[Request]:
    """The long-context workload, re-submittable with fresh rids (results
    are keyed by rid, so a reused engine needs distinct ids per run)."""
    return [Request(rid0 + r.rid, r.tokens)
            for r in make_requests(LONG_CFG, N_LONG, seed=29,
                                   lo=LONG_LO, hi=LONG_HI)]


def long_context_rows(reps: int = 3) -> List[Dict]:
    """Long-context paged-decode rows (jitted): the fused paged-attention
    kernel vs the dense gather route on one workload, plus the SWA
    windowed-eviction footprint pair.  Greedy tokens must agree between
    the routes and between eviction on/off -- recorded per row
    (``tokens_match_*``) and gated by :func:`check_serving`."""
    model = build_model(LONG_CFG)
    params = model.init(jax.random.PRNGKey(1))
    nxt = [0]

    # the "kernel" engine runs under ``paged_attn=auto``: the planner's
    # own cost rule sends decode steps (S=1, T=640) to the kernel and
    # prefill chunks (S=128) to gather -- the production dispatch, not a
    # blanket pin.  The baseline engine pins ``gather`` outright.
    ROUTE_ENV = {"kernel": "auto", "gather": "gather"}

    def _run(eng: Engine, route: str, measured: bool) -> List[List[int]]:
        rid0, nxt[0] = nxt[0], nxt[0] + N_LONG
        if measured:
            eng.metrics = EngineMetrics()     # drop warmup trace+compile
        with _pinned_paged_route(ROUTE_ENV.get(route, route)):
            res = eng.run(_long_requests(rid0))
        assert all(res[rid0 + i].ok for i in range(N_LONG))
        return [list(res[rid0 + i].tokens) for i in range(N_LONG)]

    engines: Dict[str, Engine] = {}
    for route in ("gather", "kernel"):
        engines[route] = Engine(model, params,
                                EngineConfig(prepared=True, jit=True,
                                             **LONG_ENGINE_KW))
        _run(engines[route], route, measured=False)     # warmup: compile
    best: Dict[str, Dict] = {}
    tokens: Dict[str, List] = {}
    for _ in range(reps):
        # interleaved like the eager rows: the gated ratio is same-process
        for route in ("gather", "kernel"):
            tokens[route] = _run(engines[route], route, measured=True)
            m = engines[route].metrics
            if route not in best \
                    or m.tokens_per_s > best[route]["tokens_per_s"]:
                best[route] = _row(
                    f"serving_engine_long_{route}[jit]",
                    f"square_pallas/paged-{route}", engines[route],
                    cfg=LONG_CFG, kw=LONG_ENGINE_KW)
    tps_g = best["gather"]["tokens_per_s"]
    best["kernel"]["speedup_vs_gather"] = \
        best["kernel"]["tokens_per_s"] / tps_g if tps_g else 0.0
    best["kernel"]["tokens_match_gather"] = \
        tokens["kernel"] == tokens["gather"]

    # SWA eviction pair: peak_blocks_used is allocator bookkeeping, fully
    # deterministic -- one run per side suffices.  The kernel route rides
    # along so the window mask path gets jitted bench coverage too.
    model_swa = build_model(SWA_CFG)
    params_swa = model_swa.init(jax.random.PRNGKey(1))
    swa_rows, swa_tokens = {}, {}
    for evict in (False, True):
        eng = Engine(model_swa, params_swa,
                     EngineConfig(prepared=True, jit=True,
                                  window_eviction=evict, **LONG_ENGINE_KW))
        key = "evict" if evict else "retain"
        swa_tokens[key] = _run(eng, "kernel", measured=False)
        swa_rows[key] = _row(f"serving_engine_swa_{key}[jit]",
                             f"square_pallas/window-{key}", eng,
                             cfg=SWA_CFG, kw=LONG_ENGINE_KW)
    swa_rows["evict"]["blocks_vs_retain"] = (
        swa_rows["evict"]["peak_blocks_used"]
        / swa_rows["retain"]["peak_blocks_used"]
        if swa_rows["retain"]["peak_blocks_used"] else 1.0)
    swa_rows["evict"]["tokens_match_retain"] = \
        swa_tokens["evict"] == swa_tokens["retain"]
    return [best["gather"], best["kernel"],
            swa_rows["retain"], swa_rows["evict"]]


def build_serving_payload(rows: List[Dict]) -> Dict:
    return {"rows": rows}


def write_serving_json(payload: Dict, path: str = SERVING_JSON) -> Dict:
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"\nwrote {path}")
    return payload


def check_serving(payload: Dict, tol: float) -> List[str]:
    """Regression gate over the serving rows (called by run.py --check):

    - the prepared-square engine must not serve slower than the raw-square
      engine (``speedup_vs_raw >= 1.0 - tol`` -- the acceptance bar for
      the weight-stationary serving contract);
    - the square engine must keep its contraction FLOPs square-routed
      (``fraction_square >= 0.9``: a dispatch regression that silently
      reroutes serving GEMMs to the multiplier baseline fails here);
    - the guard-rails must stay cheap on the happy path: the guarded
      engine's tokens/s must hold ``speedup_vs_prepared >= 1.0 - tol``
      against the unguarded prepared engine, with zero guard trips on a
      healthy workload;
    - the fused paged-attention kernel must hold its route on the
      long-context rows: ``speedup_vs_gather >= 1.0 - tol`` (tol floored
      at :data:`LONG_ROW_TOL_FLOOR` -- the interpret-host slack, see the
      constant's comment) in steady-state serving, with greedy tokens
      identical to the gather route (``tokens_match_gather``);
    - SWA windowed eviction must actually cap the footprint:
      the evicting engine's ``peak_blocks_used`` strictly below the
      retain-everything engine's, with identical greedy tokens
      (``tokens_match_retain``);
    - the observability layer must agree with the ground truth: the
      registry's ``counting_fraction_square`` gauge must equal the
      counting audit's fraction, and the registry's terminal request
      counters must partition submissions (``registry_conserved``);
    - tracing must stay cheap: the fully-traced engine's tokens/s must
      hold ``speedup_vs_prepared >= 0.9 - tol``, with at least one span
      actually recorded (``trace_records > 0``).
    """
    failures = []
    rows = {r["name"]: r for r in payload.get("rows", [])}
    prep = rows.get("serving_engine_square_prepared[interp-eager]")
    if prep is None:
        failures.append("serving: prepared-square row missing")
    else:
        ratio = prep.get("speedup_vs_raw", 0.0)
        if ratio < 1.0 - tol:
            failures.append(f"serving: prepared-square tokens/s ratio "
                            f"{ratio:.2f} < {1.0 - tol:.2f} vs raw-square")
        if prep.get("fraction_square", 0.0) < 0.9:
            failures.append(
                f"serving: fraction_square "
                f"{prep.get('fraction_square', 0.0):.2f} < 0.90")
    grd = rows.get("serving_engine_square_guarded[interp-eager]")
    if grd is None:
        failures.append("serving: guarded-square row missing")
    else:
        ratio = grd.get("speedup_vs_prepared", 0.0)
        if ratio < 1.0 - tol:
            failures.append(f"serving: guarded tokens/s ratio {ratio:.2f} "
                            f"< {1.0 - tol:.2f} vs prepared (resilience "
                            f"overhead regression)")
        if grd.get("guard_trips", 0) != 0:
            failures.append(f"serving: {grd['guard_trips']} guard trips "
                            f"on the healthy bench workload")
    raw = rows.get("serving_engine_square_raw[interp-eager]")
    if raw is not None and "registry_fraction_square" in raw:
        if abs(raw["registry_fraction_square"]
               - raw.get("fraction_square", 0.0)) > 1e-9:
            failures.append(
                f"serving: registry fraction_square gauge "
                f"({raw['registry_fraction_square']:.4f}) disagrees with "
                f"the counting audit ({raw.get('fraction_square', 0.0):.4f})")
        if not raw.get("registry_conserved", False):
            failures.append("serving: registry terminal counters do not "
                            "partition submitted requests")
    trc = rows.get("serving_engine_square_traced[interp-eager]")
    if trc is None:
        failures.append("serving: traced-engine row missing")
    else:
        ratio = trc.get("speedup_vs_prepared", 0.0)
        if ratio < 0.9 - tol:
            failures.append(f"serving: traced-engine tokens/s ratio "
                            f"{ratio:.2f} < {0.9 - tol:.2f} vs prepared "
                            f"(tracing overhead regression)")
        if trc.get("trace_records", 0) <= 0:
            failures.append("serving: traced-engine row recorded no spans")
    krn = rows.get("serving_engine_long_kernel[jit]")
    if krn is None:
        failures.append("serving: long-context kernel row missing")
    else:
        ltol = max(tol, LONG_ROW_TOL_FLOOR)
        ratio = krn.get("speedup_vs_gather", 0.0)
        if ratio < 1.0 - ltol:
            failures.append(f"serving: paged-attn kernel tokens/s ratio "
                            f"{ratio:.2f} < {1.0 - ltol:.2f} vs gather on "
                            f"the long-context rows")
        if not krn.get("tokens_match_gather", False):
            failures.append("serving: kernel-route greedy tokens diverge "
                            "from the gather route")
    evict = rows.get("serving_engine_swa_evict[jit]")
    retain = rows.get("serving_engine_swa_retain[jit]")
    if evict is None or retain is None:
        failures.append("serving: SWA eviction row pair missing")
    else:
        if evict["peak_blocks_used"] >= retain["peak_blocks_used"]:
            failures.append(
                f"serving: windowed eviction did not reduce "
                f"peak_blocks_used ({evict['peak_blocks_used']} vs "
                f"{retain['peak_blocks_used']} retained)")
        if not evict.get("tokens_match_retain", False):
            failures.append("serving: SWA eviction changed greedy tokens")
    return failures


if __name__ == "__main__":
    rows = serving_rows() + long_context_rows()
    for r in rows:
        print(r)
    write_serving_json(build_serving_payload(rows))
