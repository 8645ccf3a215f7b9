"""Serving launcher: the paged continuous-batching engine (default) or the
dense reference Server (``--legacy``), with A/B switches for the
fair-square datapath:

    PYTHONPATH=src python -m repro.launch.serve --arch fairsquare-demo \
        --reduced --requests 8 --max-new 16

    # prepared-square serving (weight-stationary decode, paper §4-§5):
    PYTHONPATH=src python -m repro.launch.serve --arch fairsquare-demo \
        --reduced --prepared --matmul-mode square_pallas \
        --policy square_gemms

``--route`` pins the square_pallas execution route for the whole run
(sets ``REPRO_ROUTE``; see kernels/routing.py), e.g. ``--route
matmul=fold``, ``--route paged_attn=gather`` (force the dense
paged-attention read), or ``--route virtual``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.configs.base import SQUARE_GEMMS_POLICY
from repro.launch.compile_cache import enable_compile_cache
from repro.models.blocks import PAGEABLE_KINDS
from repro.models.lm import build_model
from repro.obs import trace as obs_trace
from repro.obs.export import write_chrome_trace
from repro.serve.engine import Engine, EngineConfig
from repro.serve.server import Request, ServeConfig, Server


def make_requests(cfg, n: int, seed: int = 0, lo: int = 4, hi: int = 24):
    rng = np.random.default_rng(seed)
    reqs = []
    for rid in range(n):
        plen = int(rng.integers(lo, hi))
        extras = {}
        if cfg.prefix_tokens:
            extras["patches"] = rng.normal(
                size=(cfg.prefix_tokens, cfg.d_model)).astype(np.float32)
        if cfg.encoder_layers:
            extras["frames"] = rng.normal(
                size=(cfg.encoder_seq, cfg.d_model)).astype(np.float32)
        reqs.append(Request(rid, rng.integers(0, cfg.vocab, plen,
                                              dtype=np.int32), extras or None))
    return reqs


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="fairsquare-demo")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--matmul-mode", default=None)
    ap.add_argument("--policy", choices=["none", "square_gemms"],
                    default="none",
                    help="per-site contraction policy (square_gemms = "
                         "square everywhere but the attention softmax path)")
    ap.add_argument("--route", default=None,
                    help="pin the square_pallas route (REPRO_ROUTE syntax: "
                         "a route name or matmul=...,conv2d=...)")
    ap.add_argument("--prepared", action="store_true",
                    help="LM.prepare_params once at start: weight-"
                         "stationary prepared operands on every serving "
                         "GEMM")
    ap.add_argument("--legacy", action="store_true",
                    help="dense reference Server instead of the paged "
                         "engine")
    # legacy batch geometry
    ap.add_argument("--max-batch", type=int, default=4)
    # engine geometry
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--blocks", type=int, default=64)
    ap.add_argument("--blocks-per-seq", type=int, default=8)
    ap.add_argument("--prefill-chunk", type=int, default=32)
    # resilience (engine only)
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline from submit, in ms "
                         "(expired requests end TIMED_OUT with partial "
                         "tokens)")
    ap.add_argument("--queue-limit", type=int, default=None,
                    help="bounded admission queue depth; overflow is shed "
                         "per --shed-policy")
    ap.add_argument("--shed-policy", choices=["reject-new", "evict-oldest"],
                    default="reject-new",
                    help="full-queue policy: refuse the newcomer, or evict "
                         "the oldest queued request")
    ap.add_argument("--guard", action="store_true",
                    help="numerics guard: fail non-finite-logits slots "
                         "cleanly and let the core-layer route-health "
                         "breaker demote saturating square-route sites")
    # observability (docs/observability.md)
    ap.add_argument("--metrics-file", default=None,
                    help="write the engine's registry snapshot (counters, "
                         "gauges, histogram percentiles, route health) as "
                         "JSON; render with scripts/obs_report.py")
    ap.add_argument("--trace-out", default=None,
                    help="enable structured tracing and write a Chrome "
                         "trace_event JSON (load in Perfetto / "
                         "chrome://tracing)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.trace_out:
        obs_trace.enable()

    if args.route:
        os.environ["REPRO_ROUTE"] = args.route

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.matmul_mode:
        cfg = dataclasses.replace(cfg, matmul_mode=args.matmul_mode)
    if args.policy == "square_gemms":
        cfg = dataclasses.replace(cfg,
                                  contraction_policy=SQUARE_GEMMS_POLICY)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    legacy = args.legacy
    if not legacy and (cfg.encoder_layers or cfg.prefix_tokens
                       or any(k not in PAGEABLE_KINDS
                              for k in cfg.layer_kinds)):
        print(f"note: arch {cfg.name!r} has non-KV decode state; "
              f"falling back to the dense reference Server")
        legacy = True

    reqs = make_requests(cfg, args.requests)

    if legacy:
        if args.prepared:
            params = model.prepare_params(params)
        server = Server(model, params,
                        ServeConfig(max_batch=args.max_batch, cache_len=128,
                                    max_new_tokens=args.max_new))
        t0 = time.perf_counter()
        results = server.run(reqs)
        dt = time.perf_counter() - t0
        total_new = sum(len(v) for v in results.values())
        print(f"[legacy] served {len(results)} requests, {total_new} tokens "
              f"in {dt:.2f}s ({total_new / dt:.1f} tok/s)")
    else:
        ecfg = EngineConfig(max_slots=args.slots, block_size=args.block_size,
                            num_blocks=args.blocks,
                            blocks_per_seq=args.blocks_per_seq,
                            prefill_chunk=args.prefill_chunk,
                            max_new_tokens=args.max_new,
                            prepared=args.prepared,
                            deadline_s=(args.deadline_ms / 1e3
                                        if args.deadline_ms is not None
                                        else None),
                            queue_limit=args.queue_limit,
                            shed_policy=args.shed_policy,
                            guard=args.guard)
        engine = Engine(model, params, ecfg)
        eresults = engine.run(reqs)
        m = engine.metrics
        print(f"[engine] served {len(eresults)} requests, {m.tokens_out} "
              f"tokens in {m.wall_s:.2f}s ({m.tokens_per_s:.1f} tok/s, "
              f"mode={cfg.matmul_mode}, prepared={args.prepared})")
        print(f"  ttft mean {m.mean_ttft_s * 1e3:.0f}ms | block util "
              f"{m.mean_utilization:.0%} (peak {m.peak_blocks_used} blk) | "
              f"occupancy {m.batch_occupancy:.2f} slots/step | "
              f"{m.prefill_chunks} prefill chunks, {m.decode_steps} decode "
              f"steps, {m.preemptions} preemptions")
        by_status = {}
        for r in eresults.values():
            by_status[str(r.status)] = by_status.get(str(r.status), 0) + 1
        print(f"  terminals: {by_status} | shed {m.shed} | timeouts "
              f"{m.timeouts} | guard trips {m.guard_trips}")
        summ = m.summary()
        print(f"  ttft p50/p95/p99 {summ['ttft_p50_s'] * 1e3:.0f}/"
              f"{summ['ttft_p95_s'] * 1e3:.0f}/"
              f"{summ['ttft_p99_s'] * 1e3:.0f}ms | decode step p50 "
              f"{summ['decode_step_p50_s'] * 1e3:.1f}ms")
        snap = engine.obs_snapshot()
        health = snap["route_health"]
        demoted = [h["key"] for h in health if h["demoted"]]
        line = (f"  route health: {len(health)} tracked site(s), "
                f"{len(demoted)} demoted")
        if demoted:
            line += " -> " + ", ".join(demoted)
        print(line)
        if args.metrics_file:
            with open(args.metrics_file, "w") as f:
                json.dump(snap, f, indent=1, sort_keys=True)
            print(f"  metrics snapshot -> {args.metrics_file}")
        results = {rid: r.tokens for rid, r in eresults.items()}
        unfinished = sorted(rid for rid, r in eresults.items() if not r.ok)
    if legacy and args.metrics_file:
        print("note: --metrics-file needs the paged engine's registry; "
              "ignored under --legacy")
    if args.trace_out:
        tr = obs_trace.get_tracer()
        write_chrome_trace(tr, args.trace_out)
        print(f"  trace -> {args.trace_out} ({len(tr.records())} records, "
              f"{tr.dropped} dropped)")
    for rid in sorted(results)[:4]:
        print(f"  req {rid}: {results[rid][:8]}...")
    assert len(results) == args.requests
    if not legacy and unfinished:
        raise SystemExit(f"{len(unfinished)} request(s) did not complete: "
                         + "; ".join(f"{rid}: {eresults[rid].status} "
                                     f"{eresults[rid].error or ''}".strip()
                                     for rid in unfinished))
    return results


if __name__ == "__main__":
    main()
