"""Serving under a closed loop of clients, through the program's ``Engine``.

Set-up makes the weights (one jitted call), builds the engine, compiles
what the window will run and fills every slot with the clients' first
requests; the window opens once every slot is decoding.  After each
``Engine.step()`` the harness reads how many tokens each of its requests
holds (the step has synced: it samples on the host) and stamps the new
ones with the host clock.  A client's request ends at its drawn length,
by ``Engine.cancel``, and its next request is due at that moment.

Correctness, after the window: every request that the run served tokens
to (finished, or in flight when the window closed) goes through the plain
reference with its prompt and served tokens; the numbers compared are
taken from the gaps by which each served token's reference logit lies
below the reference's best (``bench/checks.py``).
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from bench import checks, flops, program, traffic, weights
from bench import stats as stats_mod
from bench.drivers import common

__all__ = ["run"]


@dataclasses.dataclass
class _Live:
    req: traffic.Request
    rid: int
    handle: object             # the engine's Request object
    due: float
    submitted: float
    seen: int = 0
    first: Optional[float] = None
    last: Optional[float] = None
    ended: bool = False


def _submit(eng, gen, c, rid, due, Req):
    r = gen.next(c)
    h = Req(rid, r.prompt)
    with jax.profiler.TraceAnnotation("bench.submit"):
        eng.submit([h])
    return _Live(r, rid, h, due, time.perf_counter())


def run(ctx: common.Context) -> common.Record:
    from repro.serve.server import Request as Req
    cfg, mix = ctx.cfg, ctx.traffic
    mcfg = weights.model(cfg)
    model, _ = program.build(cfg)
    key = weights.base_key(ctx.seed)
    params = common.make_weights(mcfg, key)
    eng = program.engine(model, params, mix["engine"], cfg["prepared"])
    del params
    gen = traffic.closed_loop(mix, ctx.seed, cfg["vocab"])
    program.warm_position_resets(
        eng, gen.freed_blocks(mix["engine"]["block_size"]))
    ctx.hooks.get("engine", lambda e: None)(eng)

    live: Dict[int, _Live] = {}
    done: List[_Live] = []
    stats = common.Window()
    next_rid = 0
    for c in range(gen.clients):
        now = time.perf_counter()
        live[next_rid] = _submit(eng, gen, c, next_rid, now, Req)
        next_rid += 1

    slots = eng.slots
    in_window = False
    t_open = t_close = 0.0
    ticks: List[tuple] = []       # (t, decode contexts, prefill tokens,
    #                               first tokens, tick seconds)
    chunk_gap: List[bool] = []    # per gap: its tick ran a prefill chunk
    first_at: List[float] = []    # when each first token of the window came

    def tick():
        nonlocal next_rid
        pre = [(s.req.rid, s.state, s.n_prefilled, s.pos) if s else None
               for s in slots]
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.engine_step"):
            eng.step()
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.client"):
            dec, pre_tokens, firsts = [], [], 0
            for i, s in enumerate(slots):
                p = pre[i]
                if p is None:
                    if s is None:
                        continue
                    p = (s.req.rid, "prefill", 0, 0)   # admitted this tick
                rid, state, n_pre, pos = p
                same = s is not None and s.req.rid == rid
                if state == "decode":
                    if not same or s.pos == pos + 1:
                        dec.append(pos + 1)
                else:
                    n_now = (s.n_prefilled if same else
                             len(live[rid].req.prompt) if rid in live else n_pre)
                    pre_tokens.extend(range(n_pre + 1, n_now + 1))
                    if not same or s.state == "decode":
                        firsts += 1
            for rid, lv in list(live.items()):
                n = len(lv.handle.out or [])
                if n < lv.seen:            # preempted: its tokens come again
                    lv.seen = n
                for _ in range(n - lv.seen):
                    if lv.first is None:
                        lv.first = t
                        if in_window:
                            stats.ttft.append(t - lv.due)
                            first_at.append(t)
                    elif in_window:
                        stats.gaps.append(t - lv.last)
                        chunk_gap.append(bool(pre_tokens))
                    lv.last = t
                    if in_window:
                        stats.tokens += 1
                lv.seen = n
                if n >= lv.req.out_len and not lv.ended:
                    eng.cancel(rid)
                    lv.ended = True
            for res in eng.drain_finished():
                lv = live.pop(res.rid)
                if res.status.value not in ("cancelled", "completed") \
                        or len(res.tokens) < lv.req.out_len:
                    stats.failed += int(in_window)
                    stats.errors.append(f"{res.rid}: {res.status} "
                                        f"{res.error}")
                done.append(lv)
                due = time.perf_counter()
                nxt = _submit(eng, gen, lv.req.client, next_rid, due, Req)
                live[next_rid] = nxt
                next_rid += 1
                if in_window:
                    stats.attempted += 1
                    stats.lateness.append(nxt.submitted - nxt.due)
        if in_window:
            ticks.append((t, dec, pre_tokens, firsts, t - t0))

    # ---- set-up: fill every slot (the traffic needs a full batch)
    fill_ticks = 0
    while not all(s is not None and s.state == "decode" for s in slots):
        tick()
        fill_ticks += 1
        if fill_ticks > ctx.max_fill_ticks:
            raise RuntimeError(f"slots not all decoding after "
                               f"{fill_ticks} ticks")
    prof = common.Profiler() if ctx.trace else None
    m = eng.metrics
    c0 = (m.decode_steps, m.decode_slot_steps, m.prefill_chunks)
    stats.attempted = len(live)
    in_window = True
    ctx.count_compiles(True)
    with common.window(prof):
        t_open = time.perf_counter()
        ctx.setup_s = t_open - ctx.t_process
        while time.perf_counter() - t_open < ctx.seconds:
            tick()
        t_close = time.perf_counter()
    ctx.count_compiles(False)
    in_window = False
    c1 = (m.decode_steps, m.decode_slot_steps, m.prefill_chunks)

    rec = common.Record(ctx, kind="serve")
    rec.window_s = t_close - t_open
    rec.stats = stats
    rec.decode_steps = c1[0] - c0[0]
    rec.decode_slot_steps = c1[1] - c0[1]
    rec.prefill_chunks = c1[2] - c0[2]
    rec.attempted, rec.failed = stats.attempted, stats.failed
    rec.model_flops = sum(
        sum(flops.forward_flops(mcfg, c, logits=True) for c in dec)
        + sum(flops.forward_flops(mcfg, c, logits=False) for c in pre)
        + firsts * flops.logits_flops(mcfg)
        for _, dec, pre, firsts, _ in ticks)
    pk = flops.peaks(ctx.device_kind) if ctx.device_kind else None
    rec.paged_attn_least_s = None if pk is None else sum(
        flops.paged_attn_least_s(mcfg, dec, pk) for _, dec, *_ in ticks
        if dec)
    rec.memory_peak_bytes = common.memory_peak()
    if prof is not None:
        execs = {"decode": rec.decode_steps, "chunk": rec.prefill_chunks,
                 "logits_at": sum(t[3] for t in ticks)}
        rec.programs = common.programs(program.serve_programs(eng), execs)
        rec.trace = prof.reduce(rec.programs)

    # ---- correctness, once the program's state is gone
    served = [(lv, min(lv.seen, lv.req.out_len))
              for lv in done + list(live.values()) if lv.seen]
    del eng, live, slots
    gc.collect()
    seqs = [(np.asarray(lv.req.prompt),
             np.asarray(lv.handle.out[:n], np.int32))
            for lv, n in sorted(served, key=lambda x: x[0].rid)]
    ctx.hooks.get("served", lambda s: s)(seqs)
    t_check = time.perf_counter()
    gaps = checks.served_gaps(mcfg, key, seqs)
    rec.served, rec.gaps = seqs, gaps
    rec.compare(checks.served_numbers(gaps))
    rec.notes = dict(_tick_notes(ticks, stats.gaps, chunk_gap),
                     ttft_s=stats.ttft,
                     first_token_at_s=[t - t_open for t in first_at],
                     served=[(len(p), len(o)) for p, o in seqs],
                     logit_gap_max=float(np.max(gaps)),
                     unknown_kernels=sorted(
                         {k for prog, _ in (rec.programs or {}).values()
                          for k in prog.unknown}),
                     check_s=time.perf_counter() - t_check,
                     errors=stats.errors[:5])
    return rec


def _tick_notes(ticks, gaps, chunk_gap) -> dict:
    """How the window's ticks and gaps split between plain decode steps
    and steps that also ran a prefill chunk."""
    chunk = [t[4] for t in ticks if t[2] or t[3]]
    plain = [t[4] for t in ticks if not (t[2] or t[3])]
    p95 = stats_mod.quantile(gaps, 0.95)
    out = {"ticks": len(ticks), "chunk_ticks": len(chunk),
           "plain_tick_s": stats_mod.quantile(plain, 0.5),
           "chunk_tick_s": stats_mod.quantile(chunk, 0.5),
           "chunk_gap_share": (sum(chunk_gap) / len(chunk_gap)
                               if chunk_gap else None)}
    if p95 is not None and chunk:
        # the p95 gap sits in the chunk mode when every plain gap lies
        # below it and chunk gaps lie on both sides of it
        plain_gaps = [g for g, c in zip(gaps, chunk_gap) if not c]
        chunk_gaps = [g for g, c in zip(gaps, chunk_gap) if c]
        out["p95_in_chunk_mode"] = bool(
            chunk_gaps and max(plain_gaps or [0.0]) < p95
            and min(chunk_gaps) <= p95 <= max(chunk_gaps))
    return out
