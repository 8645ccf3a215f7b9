"""Serving-engine integration tests: continuous batching over the paged
cache, chunked prefill, slot recycling, EOS / exhaustion, preemption, and
token-for-token equivalence against sequential one-request-at-a-time
generation through the dense reference Server -- plus the resilience
surface: terminal statuses, deadlines, the bounded admission queue's shed
policies, cancellation, and the preemption budget."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.serve import make_requests
from repro.models.lm import build_model
from repro.serve.engine import Engine, EngineConfig, RequestStatus
from repro.serve.server import Request, ServeConfig, Server


def _model(arch="deepseek-7b"):
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _ragged_requests(cfg, n, lo=3, hi=20, seed=0):
    return make_requests(cfg, n, seed=seed, lo=lo, hi=hi)


def _toks(results):
    """{rid: generated ids} view of an engine result dict."""
    return {rid: r.tokens for rid, r in results.items()}


def _sequential_reference(model, params, requests, max_new, cache_len=64,
                          eos_id=-1):
    """One-request-at-a-time generation: Server with a single slot serves
    the queue strictly sequentially."""
    srv = Server(model, params, ServeConfig(max_batch=1, cache_len=cache_len,
                                            max_new_tokens=max_new,
                                            eos_id=eos_id))
    return srv.run([Request(r.rid, r.tokens) for r in requests])


def test_engine_eight_concurrent_ragged_matches_sequential():
    """The acceptance bar: >= 8 concurrent ragged-length requests through
    the paged cache with per-slot positions, token-for-token equal to
    sequential generation."""
    cfg, model, params = _model()
    reqs = _ragged_requests(cfg, 10)
    eng = Engine(model, params, EngineConfig(
        max_slots=8, block_size=8, num_blocks=64, blocks_per_seq=8,
        prefill_chunk=8, max_new_tokens=6))
    results = eng.run([Request(r.rid, r.tokens) for r in reqs])
    ref = _sequential_reference(model, params, reqs, max_new=6)
    assert sorted(results) == list(range(10))
    assert all(r.ok for r in results.values())
    assert _toks(results) == ref
    m = eng.metrics
    assert m.tokens_out == 60
    assert m.completed == 10
    assert m.batch_occupancy > 1.0        # decode really ran batched
    assert 0.0 < m.mean_utilization <= 1.0
    assert len(m.ttft_s) == 10


def test_engine_slot_recycling_more_requests_than_slots():
    cfg, model, params = _model()
    reqs = _ragged_requests(cfg, 9, seed=2)
    eng = Engine(model, params, EngineConfig(
        max_slots=3, block_size=8, num_blocks=32, blocks_per_seq=6,
        prefill_chunk=16, max_new_tokens=4))
    results = eng.run([Request(r.rid, r.tokens) for r in reqs])
    assert sorted(results) == list(range(9))
    assert _toks(results) == _sequential_reference(model, params, reqs,
                                                   max_new=4)
    # 9 requests over 3 slots: blocks were freed and reallocated
    assert eng.allocator.used_blocks == 0
    assert eng.metrics.peak_blocks_used <= 31


def test_engine_max_new_tokens_exhaustion():
    cfg, model, params = _model()
    reqs = _ragged_requests(cfg, 5, seed=3)
    eng = Engine(model, params, EngineConfig(
        max_slots=4, block_size=8, num_blocks=32, blocks_per_seq=6,
        prefill_chunk=8, max_new_tokens=5))
    results = eng.run(reqs)
    assert all(len(v.tokens) == 5 for v in results.values())


def test_engine_eos_mid_batch():
    """A slot hitting EOS frees its blocks and recycles while the rest of
    the batch keeps decoding."""
    cfg, model, params = _model()
    reqs = _ragged_requests(cfg, 6, seed=4)
    # find a token some (not all) requests emit first, use it as EOS
    probe = Engine(model, params, EngineConfig(
        max_slots=6, block_size=8, num_blocks=64, blocks_per_seq=6,
        prefill_chunk=16, max_new_tokens=3))
    first = {rid: res.tokens[0]
             for rid, res in probe.run([Request(r.rid, r.tokens)
                                        for r in reqs]).items()}
    eos = first[0]
    stoppers = {rid for rid, t in first.items() if t == eos}
    assert stoppers and len(stoppers) < len(reqs)

    eng = Engine(model, params, EngineConfig(
        max_slots=6, block_size=8, num_blocks=64, blocks_per_seq=6,
        prefill_chunk=16, max_new_tokens=6, eos_id=int(eos)))
    results = eng.run([Request(r.rid, r.tokens) for r in reqs])
    ref = _sequential_reference(model, params, reqs, max_new=6,
                                eos_id=int(eos))
    assert _toks(results) == ref
    for rid in stoppers:
        assert results[rid].tokens == [eos]   # stopped at the first token
    assert any(len(v.tokens) > 1 for v in results.values())
    assert eng.allocator.used_blocks == 0


def test_engine_prefill_chunking_edges():
    """Prompt shorter than one chunk, an exact chunk multiple, and a
    many-chunk prompt must all match the sequential reference."""
    cfg, model, params = _model()
    rng = np.random.default_rng(6)
    reqs = [Request(0, rng.integers(0, cfg.vocab, 3, dtype=np.int32)),
            Request(1, rng.integers(0, cfg.vocab, 8, dtype=np.int32)),
            Request(2, rng.integers(0, cfg.vocab, 21, dtype=np.int32))]
    eng = Engine(model, params, EngineConfig(
        max_slots=3, block_size=4, num_blocks=32, blocks_per_seq=8,
        prefill_chunk=4, max_new_tokens=4))
    results = eng.run([Request(r.rid, r.tokens) for r in reqs])
    assert _toks(results) == _sequential_reference(model, params, reqs,
                                                   max_new=4)
    assert eng.metrics.prefill_chunks >= 1 + 2 + 6


def test_engine_preemption_regenerates_identically():
    """A pool too small for all admitted sequences to finish forces
    preemption; the preempted request regenerates deterministically, so
    results still match the sequential reference."""
    cfg, model, params = _model()
    reqs = _ragged_requests(cfg, 4, lo=10, hi=14, seed=7)
    eng = Engine(model, params, EngineConfig(
        max_slots=4, block_size=4, num_blocks=13, blocks_per_seq=8,
        prefill_chunk=16, max_new_tokens=8))
    results = eng.run([Request(r.rid, r.tokens) for r in reqs])
    assert _toks(results) == _sequential_reference(model, params, reqs,
                                                   max_new=8)
    assert eng.metrics.preemptions > 0
    # delivered-token accounting rolls back on preemption: tokens_out must
    # equal what reached the caller, not include discarded generations
    assert eng.metrics.tokens_out == sum(len(v.tokens)
                                         for v in results.values())
    assert len(eng.metrics.ttft_s) == len(reqs)


def test_engine_prepared_weights_match_raw():
    """prepared=True (LM.prepare_params at engine start, every decode GEMM
    on the prepared square route) must not change a single token."""
    cfg, model, params = _model()
    reqs = _ragged_requests(cfg, 6, seed=8)
    kw = dict(max_slots=4, block_size=8, num_blocks=32, blocks_per_seq=6,
              prefill_chunk=8, max_new_tokens=5)
    raw = Engine(model, params, EngineConfig(**kw))
    prep = Engine(model, params, EngineConfig(prepared=True, **kw))
    r_raw = raw.run([Request(r.rid, r.tokens) for r in reqs])
    r_prep = prep.run([Request(r.rid, r.tokens) for r in reqs])
    assert _toks(r_raw) == _toks(r_prep)


@pytest.mark.parametrize("arch", ["moonlight-16b-a3b", "mixtral-8x7b"])
def test_engine_moe_arch(arch):
    cfg, model, params = _model(arch)
    reqs = _ragged_requests(cfg, 4, seed=9)
    eng = Engine(model, params, EngineConfig(
        max_slots=4, block_size=8, num_blocks=32, blocks_per_seq=6,
        prefill_chunk=8, max_new_tokens=4))
    results = eng.run([Request(r.rid, r.tokens) for r in reqs])
    assert _toks(results) == _sequential_reference(model, params, reqs,
                                                   max_new=4)


def test_eviction_window_helper():
    from repro.serve.engine import eviction_window
    assert eviction_window(get_config("deepseek-7b").reduced()) is None
    swa = get_config("starcoder2-3b").reduced()
    assert eviction_window(swa) == swa.window
    tiny = dataclasses.replace(swa, window=8)
    assert eviction_window(tiny) == 8


def test_engine_window_eviction_caps_footprint_identically():
    """SWA decode with block eviction on must emit the same tokens as
    with it off (aged blocks are already masked), free every block at the
    end, and show a strictly lower peak pool footprint."""
    window = 8
    cfg = dataclasses.replace(get_config("starcoder2-3b").reduced(),
                              window=window)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    reqs = _ragged_requests(cfg, 4, seed=4, lo=10, hi=24)
    kw = dict(max_slots=4, block_size=4, num_blocks=48, blocks_per_seq=10,
              prefill_chunk=8, max_new_tokens=8)
    eng_off = Engine(model, params,
                     EngineConfig(window_eviction=False, **kw))
    res_off = eng_off.run([Request(r.rid, r.tokens) for r in reqs])
    eng_on = Engine(model, params, EngineConfig(**kw))
    res_on = eng_on.run([Request(r.rid, r.tokens) for r in reqs])
    assert _toks(res_on) == _toks(res_off)
    assert all(r.ok for r in res_on.values())
    assert eng_on.allocator.used_blocks == 0          # zero leaks
    cap_per_seq = -(-window // 4) + 1
    assert eng_on.metrics.peak_blocks_used <= 4 * cap_per_seq
    assert eng_on.metrics.peak_blocks_used \
        < eng_off.metrics.peak_blocks_used


def test_engine_rejects_unsupported_archs_and_oversize():
    """Unsupported architectures still raise at construction (a config
    bug, not a request fault); invalid REQUESTS get a terminal REJECTED
    status instead of an exception -- one bad request must never kill a
    batch."""
    cfg, model, params = _model("whisper-large-v3")
    with pytest.raises(ValueError):
        Engine(model, params, EngineConfig())
    cfg, model, params = _model()
    eng = Engine(model, params, EngineConfig(
        max_slots=2, block_size=4, num_blocks=16, blocks_per_seq=4,
        max_new_tokens=8))
    eng.submit([Request(0, np.zeros(12, np.int32)),   # 12 + 8 > 16 ceiling
                Request(1, np.zeros(0, np.int32))])   # empty prompt
    assert eng.results[0].status is RequestStatus.REJECTED
    assert "ceiling" in eng.results[0].error
    assert eng.results[1].status is RequestStatus.REJECTED
    assert eng.results[1].tokens == []
    assert eng.metrics.rejected == 2 and eng.metrics.shed == 0
    assert not eng.queue                     # neither was enqueued
    # a valid request alongside rejected ones still completes
    good = _ragged_requests(cfg, 1, lo=4, hi=6, seed=11)[0]
    res = eng.run([Request(2, good.tokens)])
    assert res[2].ok and len(res[2].tokens) == 8
    assert set(res) == {0, 1, 2}             # rejections stay in results


def test_engine_duplicate_rid_raises():
    """Duplicate rids are a caller bug (results are keyed by rid): the
    one submit-time condition that raises rather than rejects, whether
    the collision is within one batch or against an earlier request."""
    cfg, model, params = _model()
    eng = Engine(model, params, EngineConfig(
        max_slots=2, block_size=8, num_blocks=32, blocks_per_seq=4,
        prefill_chunk=8, max_new_tokens=3))
    reqs = _ragged_requests(cfg, 2, lo=4, hi=8, seed=12)
    with pytest.raises(ValueError, match="duplicate request id"):
        eng.submit([Request(7, reqs[0].tokens), Request(7, reqs[1].tokens)])
    eng2 = Engine(model, params, EngineConfig(
        max_slots=2, block_size=8, num_blocks=32, blocks_per_seq=4,
        prefill_chunk=8, max_new_tokens=3))
    eng2.run([Request(7, reqs[0].tokens)])
    with pytest.raises(ValueError, match="duplicate request id"):
        eng2.submit([Request(7, reqs[1].tokens)])  # collides with finished


def test_engine_bounded_queue_reject_new():
    """queue_limit + reject-new: overflow requests are REJECTED (and
    counted as shed) at submit; admitted ones complete normally."""
    cfg, model, params = _model()
    reqs = _ragged_requests(cfg, 6, lo=4, hi=8, seed=13)
    eng = Engine(model, params, EngineConfig(
        max_slots=2, block_size=8, num_blocks=32, blocks_per_seq=4,
        prefill_chunk=8, max_new_tokens=3,
        queue_limit=3, shed_policy="reject-new"))
    results = eng.run([Request(r.rid, r.tokens) for r in reqs])
    shed = {rid for rid, r in results.items()
            if r.status is RequestStatus.REJECTED}
    assert shed == {3, 4, 5}                  # the newest three
    done = {rid: r.tokens for rid, r in results.items() if r.ok}
    ref = _sequential_reference(model, params, reqs[:3], max_new=3)
    assert done == ref
    m = eng.metrics
    assert m.shed == 3 and m.rejected == 3 and m.peak_queue_depth == 3
    # shed requests never enter TTFT accounting
    assert set(m.ttft_s) == {0, 1, 2}


def test_engine_bounded_queue_evict_oldest():
    """queue_limit + evict-oldest: the oldest QUEUED request is shed to
    admit the newcomer; in-flight work is never evicted by admission."""
    cfg, model, params = _model()
    reqs = _ragged_requests(cfg, 6, lo=4, hi=8, seed=13)
    eng = Engine(model, params, EngineConfig(
        max_slots=2, block_size=8, num_blocks=32, blocks_per_seq=4,
        prefill_chunk=8, max_new_tokens=3,
        queue_limit=3, shed_policy="evict-oldest"))
    results = eng.run([Request(r.rid, r.tokens) for r in reqs])
    shed = {rid for rid, r in results.items()
            if r.status is RequestStatus.REJECTED}
    assert shed == {0, 1, 2}                  # the oldest three
    done = {rid: r.tokens for rid, r in results.items() if r.ok}
    ref = _sequential_reference(model, params, reqs[3:], max_new=3)
    assert done == ref
    assert eng.metrics.shed == 3


def test_engine_deadline_expiry_and_per_request_override():
    """An already-expired config deadline times every request out (partial
    or empty tokens, blocks recycled); a per-request deadline override
    lets one request opt out and complete."""
    cfg, model, params = _model()
    reqs = _ragged_requests(cfg, 3, lo=4, hi=8, seed=14)
    eng = Engine(model, params, EngineConfig(
        max_slots=2, block_size=8, num_blocks=32, blocks_per_seq=4,
        prefill_chunk=8, max_new_tokens=3, deadline_s=0.0))
    batch = [Request(r.rid, r.tokens) for r in reqs]
    batch[1].deadline_s = 3600.0              # override: effectively none
    free0 = eng.allocator.free_blocks
    results = eng.run(batch)
    assert results[0].status is RequestStatus.TIMED_OUT
    assert results[2].status is RequestStatus.TIMED_OUT
    assert results[1].ok and len(results[1].tokens) == 3
    assert eng.allocator.free_blocks == free0
    assert eng.metrics.timeouts == 2


def test_engine_max_wall_budget_zero_times_out_everything():
    cfg, model, params = _model()
    reqs = _ragged_requests(cfg, 3, lo=4, hi=8, seed=15)
    eng = Engine(model, params, EngineConfig(
        max_slots=2, block_size=8, num_blocks=32, blocks_per_seq=4,
        prefill_chunk=8, max_new_tokens=3, max_wall_s=0.0))
    results = eng.run([Request(r.rid, r.tokens) for r in reqs])
    assert all(r.status is RequestStatus.TIMED_OUT
               for r in results.values())
    assert eng.allocator.used_blocks == 0


def test_engine_cancel_queued_and_inflight():
    cfg, model, params = _model()
    reqs = _ragged_requests(cfg, 4, lo=4, hi=8, seed=16)
    eng = Engine(model, params, EngineConfig(
        max_slots=2, block_size=8, num_blocks=32, blocks_per_seq=4,
        prefill_chunk=8, max_new_tokens=6))
    eng.submit([Request(r.rid, r.tokens) for r in reqs])
    assert eng.cancel(3)                      # still queued (2 slots)
    while eng.step():
        if 0 in {s.req.rid for s in eng.slots if s is not None} \
                and (eng.results.get(0) is None) and eng.cancel(0):
            break
    while eng.step():
        pass
    results = dict(eng.results)
    assert results[3].status is RequestStatus.CANCELLED
    assert results[3].tokens == []
    assert results[0].status is RequestStatus.CANCELLED
    assert results[1].ok and results[2].ok
    assert eng.metrics.cancelled == 2
    assert eng.allocator.used_blocks == 0
    assert not eng.cancel(99)                 # unknown rid: no-op


def test_engine_preemption_budget_fails_cleanly():
    """With max_preemptions=0 a pool too small to finish both requests
    FAILS the younger one (partial tokens kept, blocks freed) instead of
    thrashing; the older request still completes exactly."""
    cfg, model, params = _model()
    reqs = _ragged_requests(cfg, 4, lo=10, hi=14, seed=7)
    eng = Engine(model, params, EngineConfig(
        max_slots=4, block_size=4, num_blocks=13, blocks_per_seq=8,
        prefill_chunk=16, max_new_tokens=8, max_preemptions=0))
    results = eng.run([Request(r.rid, r.tokens) for r in reqs])
    failed = {rid for rid, r in results.items()
              if r.status is RequestStatus.FAILED}
    assert failed and eng.metrics.failures == len(failed)
    ref = _sequential_reference(model, params, reqs, max_new=8)
    for rid, r in results.items():
        if r.ok:
            assert r.tokens == ref[rid]
        else:
            assert "preemption budget" in r.error
    assert eng.allocator.used_blocks == 0
    # FAILED partials were delivered work: tokens_out counts them too
    assert eng.metrics.tokens_out == sum(len(r.tokens)
                                         for r in results.values())


def test_engine_drain_finished_streams_terminals():
    cfg, model, params = _model()
    reqs = _ragged_requests(cfg, 3, lo=4, hi=8, seed=17)
    eng = Engine(model, params, EngineConfig(
        max_slots=2, block_size=8, num_blocks=32, blocks_per_seq=4,
        prefill_chunk=8, max_new_tokens=3))
    eng.submit([Request(r.rid, r.tokens) for r in reqs])
    seen = []
    while eng.step():
        seen.extend(eng.drain_finished())
    seen.extend(eng.drain_finished())
    assert sorted(r.rid for r in seen) == [0, 1, 2]
    assert all(r.ok for r in seen)
    assert eng.drain_finished() == []         # drained exactly once


def test_engine_metrics_summary_never_divides_by_zero():
    """summary() on a fresh engine -- and on one whose every request was
    shed before any model work -- must return finite numbers."""
    cfg, model, params = _model()
    eng = Engine(model, params, EngineConfig(
        max_slots=2, block_size=8, num_blocks=32, blocks_per_seq=4,
        max_new_tokens=3))
    s = eng.metrics.summary()
    assert s["tokens_per_s"] == 0.0 and s["mean_ttft_s"] == 0.0
    assert s["batch_occupancy"] == 0.0
    eng2 = Engine(model, params, EngineConfig(
        max_slots=2, block_size=8, num_blocks=32, blocks_per_seq=4,
        max_new_tokens=3, queue_limit=0, shed_policy="reject-new"))
    reqs = _ragged_requests(cfg, 2, lo=4, hi=8, seed=18)
    results = eng2.run([Request(r.rid, r.tokens) for r in reqs])
    assert all(r.status is RequestStatus.REJECTED for r in results.values())
    s = eng2.metrics.summary()
    assert s["mean_ttft_s"] == 0.0            # no TTFT entries, no ZeroDiv
    assert s["rejected"] == 2


def test_engine_config_validates_shed_policy():
    with pytest.raises(ValueError, match="shed_policy"):
        EngineConfig(shed_policy="drop-everything")


# ------------------------------------------------------------- tracing

class _Nesting:
    """An ``annotate`` factory that records each span with the names of
    the spans open around it when it was entered."""

    def __init__(self):
        self.stack, self.seen = [], []

    def __call__(self, name):
        outer = self

        class _Ann:
            def __enter__(self):
                outer.seen.append((name, tuple(outer.stack)))
                outer.stack.append(name)

            def __exit__(self, *exc):
                assert outer.stack.pop() == name
        return _Ann()


def _swa_world(window=8):
    cfg = dataclasses.replace(get_config("starcoder2-3b").reduced(),
                              window=window)
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


SWA_ENGINE = dict(max_slots=4, block_size=4, num_blocks=48, blocks_per_seq=10,
                  prefill_chunk=8, max_new_tokens=8)


def test_traced_engine_records_the_span_table():
    """A traced run with a client that submits and cancels between ticks
    records every span of the engine's table and closes them all.  The
    client's calls are outermost; every other span nests under
    ``engine.tick``, or under the call (``cancel`` releases blocks)."""
    from repro.obs import trace as obs_trace
    cfg, model, params = _swa_world()
    reqs = _ragged_requests(cfg, 4, seed=4, lo=10, hi=24)
    eng = Engine(model, params, EngineConfig(**SWA_ENGINE))
    nest = _Nesting()
    with obs_trace.capture(annotate=nest) as tr:
        eng.submit([Request(r.rid, r.tokens) for r in reqs[:3]])
        ticks = 0
        while eng.step():
            ticks += 1
            if ticks == 6:
                eng.submit([Request(reqs[3].rid, reqs[3].tokens)])
                assert eng.cancel(reqs[0].rid)
    assert tr.open_spans == 0 and not nest.stack
    names = {n for n, _ in nest.seen}
    assert names == {"engine.tick", "engine.admit", "engine.prefill_chunk",
                     "engine.first_token", "engine.grow", "engine.decode_step",
                     "engine.sync", "engine.reset_pos", "engine.submit",
                     "engine.cancel"}
    for name, around in nest.seen:
        if name in ("engine.submit", "engine.cancel"):
            assert "engine.tick" not in around, name
        elif name == "engine.tick":
            assert around == (), around
        else:
            assert around[:1] in (("engine.tick",), ("engine.cancel",)), \
                (name, around)
    recs = [r for r in tr.records() if r.dur is not None]
    assert [n for n, _ in nest.seen] == [r.name for r in sorted(
        recs, key=lambda r: (r.ts, -r.dur))]
    per_req = ("engine.prefill_chunk", "engine.first_token", "engine.cancel")
    assert all("rid" in r.args for r in recs if r.name in per_req)
    assert {r.args["what"] for r in recs if r.name == "engine.sync"} \
        == {"sample"}
    assert all(r.args["n"] > 0 for r in recs if r.name == "engine.reset_pos")
    assert sum(r.name == "engine.tick" for r in recs) == ticks + 1


def test_engine_programs_carry_stable_names():
    """The three step programs compile as modules named after them, on
    the first jit and on every re-jit (which must build fresh closures)."""
    cfg, model, params = _model()
    eng = Engine(model, params, EngineConfig(
        max_slots=2, block_size=8, num_blocks=16, blocks_per_seq=4,
        prefill_chunk=8, max_new_tokens=3))
    first = {n: getattr(eng, "_" + n) for n in ("decode", "chunk",
                                                "logits_at")}
    eng._jit_model_fns()
    for name, old in first.items():
        fn = getattr(eng, "_" + name)
        assert fn is not old
        assert fn.__name__ == old.__name__ == name
    base = (eng.params, eng.cache, eng.pos_pool)
    i32 = np.int32
    decode = eng._decode.lower(*base, np.zeros((2, 4), i32),
                               np.zeros((2, 1), i32), np.zeros((2, 1), i32))
    chunk = eng._chunk.lower(*base, np.zeros((1, 4), i32),
                             np.zeros((1, 8), i32), np.zeros((1, 8), i32))
    hidden = jax.ShapeDtypeStruct((1, 8, cfg.d_model), np.float32)
    logits_at = eng._logits_at.lower(eng.params, hidden, np.int32(0))
    for name, low in (("decode", decode), ("chunk", chunk),
                      ("logits_at", logits_at)):
        assert f"module @jit_{name} " in low.as_text()


def test_decode_step_histogram_times_the_synced_step():
    """Each ``engine_decode_step_seconds`` observation runs from the
    dispatch through the synced sample: its interval holds the step's
    ``engine.sync`` span and lies inside the step's ``engine.decode_step``
    span."""
    import time

    from repro.obs import trace as obs_trace
    cfg, model, params = _model()
    reqs = _ragged_requests(cfg, 3, lo=4, hi=12, seed=5)
    eng = Engine(model, params, EngineConfig(
        max_slots=4, block_size=8, num_blocks=32, blocks_per_seq=4,
        prefill_chunk=8, max_new_tokens=5))
    seen = []

    class Spy:
        def __init__(self, hist):
            self.hist = hist

        def observe(self, v):
            seen.append((time.perf_counter(), v))
            self.hist.observe(v)

        def __getattr__(self, name):
            return getattr(self.hist, name)
    eng.metrics.decode_step_hist = Spy(eng.metrics.decode_step_hist)
    with obs_trace.capture() as tr:           # the tracer's clock is
        eng.run([Request(r.rid, r.tokens) for r in reqs])  # perf_counter
    recs = tr.records()
    steps = [r for r in recs if r.name == "engine.decode_step"]
    syncs = [r for r in recs if r.name == "engine.sync"]
    assert len(seen) == len(steps) == eng.metrics.decode_steps > 0
    for (t_end, v), step in zip(seen, steps):
        t0 = t_end - v
        assert step.ts <= t0 and t_end <= step.ts + step.dur
        inside = [s for s in syncs if t0 <= s.ts and s.ts + s.dur <= t_end]
        assert [s.args["what"] for s in inside] == ["sample"]


def test_engine_uploads_block_tables_by_value():
    """Every program sees the block table as it was at dispatch.  The host
    edits the table in place (grow, evict, release) while a program
    dispatched earlier can still be reading it, so an upload that aliased
    the host buffer (as the CPU backend may for an aligned array) would
    let later edits reach it."""
    cfg, model, params = _swa_world()
    reqs = _ragged_requests(cfg, 4, seed=4, lo=10, hi=24)
    eng = Engine(model, params, EngineConfig(**SWA_ENGINE))
    uploads = []
    for name in ("_chunk", "_decode"):
        fn = getattr(eng, name)

        def spy(params, cache, pos_pool, tables, *rest, _fn=fn):
            uploads.append((tables, np.array(tables)))
            return _fn(params, cache, pos_pool, tables, *rest)
        setattr(eng, name, spy)
    res = eng.run([Request(r.rid, r.tokens) for r in reqs])
    assert all(r.ok for r in res.values())
    assert eng.metrics.prefill_chunks > 0 and eng.metrics.decode_steps > 0
    assert len(uploads) == (eng.metrics.prefill_chunks
                            + eng.metrics.decode_steps)
    for dev, at_dispatch in uploads:
        np.testing.assert_array_equal(np.asarray(dev), at_dispatch)


@pytest.mark.parametrize("window,walked", [(None, 18), (6, 12)])
def test_engine_counts_paged_columns_walked(window, walked):
    """The decode steps' paged-attention columns, counted on the host:
    prompts of 5 and 13 tokens, 4 new tokens each (the first from
    prefill), 4-token blocks, 8-column tables.  The 3 decode steps of the
    first request query positions 5, 6, 7 (columns [0, 2)); the second's
    13, 14, 15 (columns [0, 4)): 3 * 2 + 3 * 4 = 18 walked.  A 6-token
    window starts the second's walk at column (13 - 5) // 4 = 2, so 3 * 2
    + 3 * 2 = 12.  Spanned: 6 slot-steps * 8 columns = 48."""
    cfg, model, params = _model()
    if window is not None:
        cfg = dataclasses.replace(cfg, window=window)
        model = build_model(cfg)
    eng = Engine(model, params, EngineConfig(
        max_slots=2, block_size=4, num_blocks=24, blocks_per_seq=8,
        prefill_chunk=16, max_new_tokens=4))
    reqs = [Request(0, list(range(1, 6))), Request(1, list(range(1, 14)))]
    results = eng.run(reqs)
    assert all(r.ok and len(r.tokens) == 4 for r in results.values())
    m = eng.metrics
    assert m.decode_slot_steps == 6
    assert (m.paged_cols_walked, m.paged_cols_spanned) == (walked, 48)
    assert m.summary()["paged_walk_share"] == pytest.approx(walked / 48)


def test_position_resets_pad_to_a_power_of_two():
    """A reset of n freed blocks scatters a power-of-two bucket of blocks,
    padded with the null block, so only log2 of the counts compile; the
    freed blocks' positions return to EMPTY_POS and no other slot moves."""
    from repro.models.attention import EMPTY_POS
    from repro.serve.paged import NULL_BLOCK

    cfg, model, params = _model()
    eng = Engine(model, params, EngineConfig(
        max_slots=2, block_size=4, num_blocks=16, blocks_per_seq=4,
        prefill_chunk=4, max_new_tokens=3))
    seen = []
    index = eng.tables.reset_slots_index

    def spy(blocks):
        seen.append(list(blocks))
        return index(blocks)

    eng.tables.reset_slots_index = spy
    before = np.arange(16 * 4, dtype=np.int32)
    eng.pos_pool = jax.numpy.asarray(before)
    for freed in ([3], [5, 6, 7], [1, 2, 8, 9, 10]):
        eng._reset_pos(freed)
    assert [len(b) for b in seen] == [1, 4, 8]
    assert all(b[len(f):] == [NULL_BLOCK] * (len(b) - len(f))
               for b, f in zip(seen, ([3], [5, 6, 7], [1, 2, 8, 9, 10])))
    want = before.copy()
    for b in (0, 1, 2, 3, 5, 6, 7, 8, 9, 10):
        want[b * 4:(b + 1) * 4] = EMPTY_POS
    np.testing.assert_array_equal(np.asarray(eng.pos_pool), want)


def test_heap_frozen_once_the_step_programs_have_run():
    """After the first tick in which both a decode step and a prefill
    chunk have run, the engine has collected once and frozen the heap, so
    a later full collection walks only what serving allocated since; the
    engine is still freed by reference counting once dropped."""
    import gc
    import weakref

    cfg, model, params = _model()
    eng = Engine(model, params, EngineConfig(
        max_slots=2, block_size=8, num_blocks=16, blocks_per_seq=4,
        prefill_chunk=8, max_new_tokens=3))
    reqs = _ragged_requests(cfg, 2, lo=3, hi=12)
    try:
        eng.submit([Request(r.rid, r.tokens) for r in reqs])
        while not (eng.metrics.decode_steps and eng.metrics.prefill_chunks):
            assert not eng._heap_frozen
            eng.step()
        assert eng._heap_frozen and gc.get_freeze_count() > 0
        while eng.step():
            pass
        assert all(r.ok for r in eng.drain_finished())
        ref = weakref.ref(eng)
        gc.disable()
        try:
            del eng
            assert ref() is None
        finally:
            gc.enable()
    finally:
        gc.unfreeze()
