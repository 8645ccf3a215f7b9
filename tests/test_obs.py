"""Observability layer unit suite (repro.obs): tracer semantics, metric
types, exporters, and the route/guard surfacing hooks.

The load-bearing properties pinned here:

- span balance survives ANY unwind (Exception and BaseException) and the
  disabled path is allocation-free no-ops;
- the ring bound drops oldest records, counted, never grows the heap;
- Counter monotonicity is a *type* property (negative inc raises);
- histogram percentiles interpolate inside the landing bucket and the
  +Inf bucket floors instead of fabricating a tail;
- the Chrome export is loadable trace_event JSON and the request
  breakdown reconstructs queue/prefill/ttft/decode from lifecycle
  events alone;
- RouteHealth.snapshot() carries the operator-facing payload (trip
  ordinals).
"""
import json

import pytest

from repro.kernels import routing
from repro.obs import export as obs_export
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace


class FakeClock:
    """Deterministic injectable clock: each read advances by ``step``."""

    def __init__(self, step: float = 1.0):
        self.t = 0.0
        self.step = step

    def __call__(self) -> float:
        t, self.t = self.t, self.t + self.step
        return t


# ---------------------------------------------------------------- tracer

def test_span_records_duration_with_injected_clock():
    tr = obs_trace.Tracer(clock=FakeClock())
    with tr.span("work", cat="t", k=1):
        pass
    (rec,) = tr.records()
    assert rec.name == "work" and rec.cat == "t" and rec.args == {"k": 1}
    assert rec.ts == 0.0 and rec.dur == 1.0      # two clock reads apart
    assert tr.open_spans == 0


def test_span_balance_and_error_tag_through_exception():
    tr = obs_trace.Tracer(clock=FakeClock())
    with pytest.raises(ValueError):
        with tr.span("outer"):
            with tr.span("inner"):
                raise ValueError("boom")
    assert tr.open_spans == 0
    inner, outer = tr.records()                  # inner closes first
    assert inner.name == "inner" and inner.args["error"] == "ValueError"
    assert outer.args["error"] == "ValueError"


def test_span_balance_through_base_exception():
    class Kill(BaseException):
        pass

    tr = obs_trace.Tracer()
    with pytest.raises(Kill):
        with tr.span("doomed"):
            raise Kill()
    assert tr.open_spans == 0
    assert tr.records()[0].args["error"] == "Kill"


def test_ring_bound_drops_oldest_and_counts():
    tr = obs_trace.Tracer(capacity=4, clock=FakeClock())
    for i in range(10):
        tr.event(f"e{i}")
    recs = tr.records()
    assert [r.name for r in recs] == ["e6", "e7", "e8", "e9"]
    assert tr.emitted == 10 and tr.dropped == 6


def test_disabled_module_path_is_shared_noop():
    obs_trace.disable()
    assert not obs_trace.enabled()
    # the disabled span is ONE shared nullcontext -- no allocation
    assert obs_trace.span("a") is obs_trace.span("b")
    obs_trace.event("ignored", rid=1)            # must not raise
    with obs_trace.span("ignored"):
        pass


def test_capture_restores_previous_tracer_state():
    obs_trace.disable()
    with obs_trace.capture(clock=FakeClock()) as tr:
        assert obs_trace.enabled() and obs_trace.get_tracer() is tr
        obs_trace.event("inside", rid=7)
        with obs_trace.span("s", cat="c"):
            pass
    assert not obs_trace.enabled()
    names = [r.name for r in tr.records()]
    assert names == ["inside", "s"]


def test_tracer_rejects_nonpositive_capacity():
    with pytest.raises(ValueError):
        obs_trace.Tracer(capacity=0)


class FakeAnnotation:
    """An ``annotate`` factory that logs every enter and exit."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        log = self.log

        class _Ann:
            def __enter__(self):
                log.append(("enter", name))

            def __exit__(self, exc_type, exc, tb):
                log.append(("exit", name,
                            exc_type.__name__ if exc_type else None))
        return _Ann()


def test_spans_forward_to_the_annotate_factory_in_order():
    ann = FakeAnnotation()
    with obs_trace.capture(clock=FakeClock(), annotate=ann) as tr:
        with obs_trace.span("outer", rid=3):
            with obs_trace.span("inner"):
                pass
        obs_trace.event("instant")               # events are not forwarded
    assert ann.log == [("enter", "outer"), ("enter", "inner"),
                       ("exit", "inner", None), ("exit", "outer", None)]
    assert [r.name for r in tr.records()] == ["inner", "outer", "instant"]
    assert tr.records()[1].args == {"rid": 3}
    assert tr.open_spans == 0


def test_forwarded_spans_balance_through_base_exception():
    class Kill(BaseException):
        pass

    ann = FakeAnnotation()
    tr = obs_trace.Tracer(annotate=ann)
    with pytest.raises(Kill):
        with tr.span("outer"):
            with tr.span("inner"):
                raise Kill()
    assert tr.open_spans == 0
    assert ann.log == [("enter", "outer"), ("enter", "inner"),
                       ("exit", "inner", "Kill"), ("exit", "outer", "Kill")]
    assert all(r.args["error"] == "Kill" for r in tr.records())


def test_disabled_path_stays_the_shared_noop_after_forwarding():
    ann = FakeAnnotation()
    obs_trace.enable(annotate=ann)
    with obs_trace.span("on"):
        pass
    obs_trace.disable()
    assert obs_trace.span("a") is obs_trace.span("b")
    with obs_trace.span("off"):
        pass
    assert ann.log == [("enter", "on"), ("exit", "on", None)]


# --------------------------------------------------------------- metrics

def test_counter_is_monotonic_by_type():
    reg = obs_metrics.MetricsRegistry()
    c = reg.counter("ops_total")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    assert c.value == 3.5                        # rejected, not applied


def test_registry_get_or_create_and_type_conflict():
    reg = obs_metrics.MetricsRegistry()
    assert reg.counter("x_total") is reg.counter("x_total")
    with pytest.raises(ValueError):
        reg.gauge("x_total")
    # same name, different labels: distinct time series
    a = reg.gauge("g", labels={"key": "a"})
    b = reg.gauge("g", labels={"key": "b"})
    assert a is not b


def test_histogram_percentiles_interpolate():
    h = obs_metrics.Histogram("lat", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 1.5, 3.0):
        h.observe(v)
    assert h.count == 4 and h.sum == pytest.approx(6.5)
    # rank 2 of 4 lands in the (1, 2] bucket holding obs #2-#3
    assert 1.0 <= h.quantile(0.5) <= 2.0
    assert h.quantile(1.0) == pytest.approx(4.0)
    assert h.quantile(0.0) == 0.0 or h.quantile(0.0) <= 1.0


def test_histogram_inf_bucket_floors():
    h = obs_metrics.Histogram("lat", buckets=(1.0, 2.0))
    h.observe(100.0)                             # lands in +Inf
    # the +Inf bucket reports its lower edge, never a fabricated tail
    assert h.quantile(0.99) == pytest.approx(2.0)
    assert h.summary()["p50"] == pytest.approx(2.0)


def test_histogram_empty_and_validation():
    h = obs_metrics.Histogram("lat", buckets=(1.0, 2.0))
    assert h.quantile(0.5) == 0.0 and h.mean == 0.0
    with pytest.raises(ValueError):
        h.quantile(1.5)
    with pytest.raises(ValueError):
        obs_metrics.Histogram("bad", buckets=(2.0, 1.0))


def test_snapshot_shape():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("c_total").inc(3)
    reg.gauge("g").set(1.25)
    reg.histogram("h", buckets=(1.0,)).observe(0.5)
    snap = reg.snapshot()
    assert snap["counters"] == {"c_total": 3.0}
    assert snap["gauges"] == {"g": 1.25}
    hs = snap["histograms"]["h"]
    assert {"count", "sum", "mean", "p50", "p95", "p99"} <= set(hs)
    assert json.loads(json.dumps(snap)) == snap  # JSON-serializable


def test_prometheus_text_format():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("req_total", help="requests").inc(2)
    reg.gauge("depth", labels={"q": "main"}).set(4)
    h = reg.histogram("lat_seconds", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = reg.to_prometheus()
    assert "# HELP req_total requests" in text
    assert "# TYPE req_total counter" in text
    assert "req_total 2.0" in text
    assert 'depth{q="main"} 4.0' in text
    # histogram buckets are CUMULATIVE and close with +Inf / sum / count
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="1.0"} 2' in text
    assert 'lat_seconds_bucket{le="+Inf"} 3' in text
    assert "lat_seconds_count 3" in text
    assert f"lat_seconds_sum {0.05 + 0.5 + 5.0}" in text


def test_publish_contraction_audit_gauges():
    reg = obs_metrics.MetricsRegistry()
    obs_metrics.publish_contraction_audit(
        {"total_mults": 100, "multiplies_replaced_by_squares": 90,
         "fraction_square": 0.9, "bwd_mults": 40,
         "fraction_square_bwd": 0.8, "fraction_demoted": 0.0,
         "demoted_sites": ["a", "b"]}, reg)
    g = reg.snapshot()["gauges"]
    assert g["counting_fraction_square"] == 0.9
    assert g["counting_fraction_square_bwd"] == 0.8
    assert g["counting_demoted_sites"] == 2.0


# -------------------------------------------------------------- exporters

def _lifecycle_tracer():
    tr = obs_trace.Tracer(clock=FakeClock(step=0.0))
    clk = tr._clock

    def at(t, fn, *a, **kw):
        clk.t = t
        fn(*a, **kw)

    at(0.0, tr.event, "request.submit", rid=1)
    at(1.0, tr.event, "request.admit", rid=1, slot=0)
    # one prefill chunk span: 2.0 -> 2.5
    clk.t = 2.0
    sp = tr.span("engine.prefill_chunk", cat="engine", rid=1, lo=0, n=8)
    sp.__enter__()
    clk.t = 2.5
    sp.__exit__(None, None, None)
    at(3.0, tr.event, "request.first_token", rid=1, ttft_s=3.0)
    at(5.0, tr.event, "request.terminal", rid=1, status="completed")
    at(0.5, tr.event, "request.submit", rid=2)
    at(4.0, tr.event, "request.terminal", rid=2, status="rejected")
    return tr


def test_request_breakdown_reconstructs_stages():
    bd = obs_export.request_breakdown(_lifecycle_tracer())
    r1 = bd[1]
    assert r1["queue_s"] == pytest.approx(1.0)
    assert r1["prefill_s"] == pytest.approx(0.5)
    assert r1["ttft_s"] == pytest.approx(3.0)
    assert r1["decode_s"] == pytest.approx(2.0)
    assert r1["total_s"] == pytest.approx(5.0)
    assert r1["status"] == "completed"
    r2 = bd[2]                                   # never admitted
    assert r2["queue_s"] is None and r2["ttft_s"] is None
    assert r2["total_s"] == pytest.approx(3.5)
    assert r2["status"] == "rejected"


def test_chrome_trace_is_valid_trace_event_json(tmp_path):
    tr = _lifecycle_tracer()
    path = obs_export.write_chrome_trace(tr, str(tmp_path / "t.json"),
                                         process_name="unit")
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    assert events[0]["ph"] == "M"                # process_name metadata
    assert events[0]["args"]["name"] == "unit"
    phs = {e["ph"] for e in events}
    assert phs <= {"M", "X", "i"}
    for e in events[1:]:
        assert e["ts"] >= 0                      # rebased to min ts
        assert isinstance(e["tid"], int)
        if e["ph"] == "X":
            assert e["dur"] >= 0
        elif e["ph"] == "i":
            assert e["s"] == "t"
    assert doc["otherData"]["dropped_records"] == 0
    # the earliest record (rid=1 submit at clock 0.0) rebases to ts 0
    xs = [e for e in events[1:] if e["name"] == "request.submit"]
    assert min(e["ts"] for e in xs) == 0.0


# ---------------------------------------------------- route health hooks

def test_route_health_snapshot_fields():
    routing.reset_route_health()
    try:
        h = routing.route_health()
        for _ in range(2):
            h.record_trip("sq_matmul:site_a", limit=3, reason="test")
        for _ in range(3):
            h.record_trip("sq_matmul:site_b", limit=3, reason="test")
        snap = h.snapshot()
        assert [e["key"] for e in snap] == ["sq_matmul:site_a",
                                           "sq_matmul:site_b"]
        a, b = snap
        assert a["trips"] == 2 and not a["demoted"]
        assert b["trips"] == 3 and b["demoted"]
        # trip ordinals order the breaker history: a tripped twice, then
        # b three times (the sequence counter is process-wide, so assert
        # relative order, not absolute values)
        assert a["first_trip"] < a["last_trip"] < b["first_trip"]
        assert a["last_trip"] - a["first_trip"] == 1
        assert b["last_trip"] - b["first_trip"] == 2
        reg = obs_metrics.MetricsRegistry()
        obs_metrics.publish_route_health(snap, reg)
        g = reg.snapshot()["gauges"]
        assert g["route_health_sites"] == 2.0
        assert g["route_health_demoted_sites"] == 1.0
        assert g['route_health_trips{key="sq_matmul:site_b"}'] == 3.0
        assert g['route_health_demoted{key="sq_matmul:site_a"}'] == 0.0
    finally:
        routing.reset_route_health()


def test_guard_trip_emits_trace_events():
    routing.reset_route_health()
    try:
        with obs_trace.capture() as tr:
            h = routing.route_health()
            for _ in range(3):
                h.record_trip("sq_matmul:evt", limit=3, reason="test")
        names = [r.name for r in tr.records()]
        assert names.count("guard.trip") == 3
        assert names.count("guard.demote") == 1
    finally:
        routing.reset_route_health()


def test_unified_snapshot_covers_whole_stack(tmp_path, capsys):
    """The ISSUE-10 acceptance shape: ONE registry snapshot carrying,
    for the same run, engine throughput + TTFT percentiles, the
    square-routed fraction fwd AND bwd (equal to the counting audit),
    guard/route-health state, and checkpoint commit events -- validated
    by scripts/check_obs.py and rendered by scripts/obs_report.py."""
    import importlib.util
    import pathlib

    import jax

    from repro.configs.base import ModelConfig
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.launch.serve import make_requests
    from repro.models.lm import build_model
    from repro.optim import adamw
    from repro.serve.engine import Engine, EngineConfig
    from repro.serve.server import Request
    from repro.train import step as step_mod
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = ModelConfig(
        name="tiny-obs", family="dense", n_layers=2, d_model=32, n_heads=2,
        n_kv_heads=2, d_ff=64, vocab=128, head_dim=16, dtype="float32",
        scan_layers=False, remat="none", attn_chunk_q=16, attn_chunk_kv=16,
        loss_chunk=16, max_seq=64, matmul_mode="square_virtual")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    reg = obs_metrics.MetricsRegistry()          # ONE registry, whole stack

    eng = Engine(model, params,
                 EngineConfig(max_slots=2, block_size=8, num_blocks=16,
                              blocks_per_seq=4, prefill_chunk=8,
                              max_new_tokens=3),
                 registry=reg)
    reqs = make_requests(cfg, 3, seed=0, lo=4, hi=12)
    results = eng.run([Request(r.rid, r.tokens) for r in reqs])
    assert all(r.ok for r in results.values())

    step = jax.jit(step_mod.make_train_step(model, step_mod.TrainConfig()))
    data = SyntheticLM(DataConfig(global_batch=2, seq_len=16,
                                  vocab=cfg.vocab, seed=7), cfg)
    trainer = Trainer(TrainerConfig(total_steps=3, ckpt_every=2,
                                    ckpt_dir=str(tmp_path / "ckpt"),
                                    audit_contractions=True),
                      step, model.init(jax.random.PRNGKey(1)),
                      adamw.adamw_init(params), data, registry=reg)
    res = trainer.run()
    assert res["final_step"] == 3

    snap = eng.obs_snapshot(audit=trainer.contraction_audit)
    snap["contraction_audit"] = dict(trainer.contraction_audit)
    c, g, h = snap["counters"], snap["gauges"], snap["histograms"]
    # engine throughput + TTFT percentiles
    assert snap["engine"]["tokens_per_s"] > 0
    ttft = h["engine_ttft_seconds"]
    assert ttft["count"] > 0 and ttft["p50"] <= ttft["p95"] <= ttft["p99"]
    # square fraction fwd AND bwd, equal to the counting audit
    audit = trainer.contraction_audit
    assert g["counting_fraction_square"] == audit["fraction_square"] >= 0.9
    assert (g["counting_fraction_square_bwd"]
            == audit["fraction_square_bwd"] >= 0.9)
    # guard / route-health state
    assert c["engine_guard_trips_total"] == 0.0
    assert "route_health_sites" in g and "counting_demoted_sites" in g
    # checkpoint commit events + trainer step ledger, same snapshot
    assert c["ckpt_commits_total"] >= 1
    assert c["train_steps_total"] == 3

    # check_obs.py accepts it; obs_report.py renders it
    path = tmp_path / "snap.json"
    path.write_text(json.dumps(snap))
    root = pathlib.Path(__file__).resolve().parent.parent / "scripts"

    def load(name):
        spec = importlib.util.spec_from_file_location(name,
                                                      root / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    check_obs = load("check_obs")
    assert check_obs.main(["--snapshot", str(path)]) == 0
    obs_report = load("obs_report")
    obs_report.render(snap)
    out = capsys.readouterr().out
    assert "tok/s" in out and "square-route audit" in out.lower()
