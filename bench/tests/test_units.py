"""The benchmark's yardstick, piece by piece: rates and percentiles, the
traffic generator, operation and byte counts, the HLO reading, the trace
reduction, the configuration files and the lookups by name."""
import base64
import json
import math
import os
import re
import statistics
import subprocess
import sys

import numpy as np
import pytest

from bench import arch, flops, harness, hlo, program, stats, trace, traffic
from repro.configs import get_config

ROOT = harness.ROOT
FIXTURES = os.path.join(ROOT, "bench", "fixtures")


# ------------------------------------------------------------------ stats

def test_quantile_over_all_samples():
    xs = [5.0, 1.0, 4.0, 2.0, 3.0]
    assert stats.quantile(xs, 0.5) == 3.0
    assert stats.quantile(xs, 0.0) == 1.0 and stats.quantile(xs, 1.0) == 5.0
    assert stats.quantile(xs, 0.95) == pytest.approx(np.quantile(xs, 0.95))
    big = list(np.random.default_rng(0).lognormal(size=1001))
    assert stats.quantile(big, 0.95) == pytest.approx(np.quantile(big, 0.95))
    assert stats.quantile([], 0.5) is None
    with pytest.raises(ValueError):
        stats.quantile(xs, 1.5)


def test_rate_over_the_whole_window():
    assert stats.rate(300, 60.0) == 5.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


# ---------------------------------------------------------------- traffic

REASON = json.load(open(os.path.join(ROOT, "bench", "traffic", "reason.json")))


def test_quantiles_match_the_stated_distribution():
    d = REASON["prompt"]
    xs = traffic.quantiles(d, 1000)
    assert xs == sorted(xs) and min(xs) >= d["lo"] and max(xs) <= d["hi"]
    assert abs(statistics.median(xs) - d["median"]) <= 1
    # the log-spread between the quartiles is sigma * 2 * 0.6745
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert math.log(q3 / q1) == pytest.approx(2 * 0.6745 * d["sigma"],
                                              rel=0.02)
    u = traffic.quantiles({"dist": "uniform", "lo": 16, "hi": 48}, 33)
    assert u == list(range(16, 49))
    with pytest.raises(ValueError):
        traffic.quantiles({"dist": "zipf", "lo": 1, "hi": 2}, 4)


def _requests(seed, n=4):
    gen = traffic.closed_loop(REASON, seed, 32000)
    return [gen.next(c) for c in range(gen.clients) for _ in range(n)]


def test_closed_loop_is_deterministic_per_seed():
    a, b = _requests(2 ** 33 + 1), _requests(2 ** 33 + 1)
    assert [(r.client, r.out_len) for r in a] == [(r.client, r.out_len)
                                                  for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = _requests(7)
    assert not all(np.array_equal(x.prompt, y.prompt)
                   for x, y in zip(a, c) if len(x.prompt) == len(y.prompt))


def test_every_seed_replays_the_same_schedule_with_other_prompts():
    a, b = _requests(1), _requests(2)
    assert [(r.client, r.index, len(r.prompt), r.out_len) for r in a] == \
        [(r.client, r.index, len(r.prompt), r.out_len) for r in b]
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    gen = traffic.closed_loop(REASON, 1, 32000)
    plens = sorted(p for client in gen.schedule for p, _ in client)
    olens = sorted(o for client in gen.schedule for _, o in client)
    n = REASON["clients"] * REASON["rounds"]
    assert plens == traffic.quantiles(REASON["prompt"], n)
    assert olens == traffic.quantiles(REASON["output"], n)
    for r in a:
        assert REASON["prompt"]["lo"] <= len(r.prompt) <= REASON["prompt"]["hi"]
        assert 0 <= r.prompt.min() and r.prompt.max() < 32000


def test_stagger_starts_clients_part_way_through_their_first_answers():
    gen = traffic.closed_loop(REASON, 5, 32000)
    flat = traffic.closed_loop(dict(REASON, stagger=False), 5, 32000)
    C = gen.clients
    for c in range(C):
        full = flat.next(c).out_len
        first = gen.next(c).out_len
        assert first == max(1, math.ceil(full * (C - c - 0.5) / C))
        # only the first answer is cut; the loop then runs its schedule
        assert gen.next(c).out_len == flat.next(c).out_len
    assert [gen.next(0).out_len for _ in range(REASON["rounds"] - 2)] == \
        [flat.next(0).out_len for _ in range(REASON["rounds"] - 2)]
    # a client that wraps round its schedule replays its whole first answer
    assert gen.next(0).out_len == gen.schedule[0][0][1]


def test_freed_block_counts_cover_every_request_of_the_loop():
    gen = traffic.closed_loop(REASON, 5, 32000)
    bs = 16
    counts = set(gen.freed_blocks(bs))
    for c in range(gen.clients):
        for _ in range(REASON["rounds"] + 2):
            r = gen.next(c)
            # a request cancelled at its drawn length holds every token
            # written back: the prompt and all answer tokens but the last
            assert -(-(len(r.prompt) + r.out_len - 1) // bs) in counts
    assert max(counts) <= REASON["engine"]["blocks_per_seq"]
    assert len(counts) < REASON["engine"]["blocks_per_seq"]


# ----------------------------------------------------------- flops, peaks

def test_peaks_are_published_and_unknown_devices_fail():
    pk = flops.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")


def test_matmul_least_time_on_known_shapes():
    pk = flops.peaks("TPU v5 lite")
    # decode GEMM: 8 rows through a 3840 x 10240 weight reads the weight
    t, bound = flops.matmul_least_s(8, 3840, 10240, pk)
    assert bound == "memory"
    assert t == pytest.approx((8 * 3840 + 3840 * 10240 + 8 * 10240) * 2
                              / 819e9)
    # a square 4096^3 product is compute-bound: 2 * 4096^3 / 197e12
    t, bound = flops.matmul_least_s(4096, 4096, 4096, pk)
    assert bound == "compute" and t == pytest.approx(2 * 4096 ** 3 / 197e12)


def test_model_operations_from_shapes():
    cfg = json.load(open(os.path.join(ROOT, "bench", "configs",
                                      "danube3-4b-sq.json")))
    dense = arch.of(cfg)
    per_layer = 3840 * (32 + 16) * 120 + 32 * 120 * 3840 + 3 * 3840 * 10240
    assert dense.matmul_params(cfg) == 24 * per_layer
    assert dense.attention_flops(cfg, 100) == 4 * 24 * 32 * 120 * 100
    assert dense.attention_flops(cfg, 10000) == dense.attention_flops(cfg,
                                                                      4096)
    assert flops.forward_flops(cfg, 1, True) == pytest.approx(
        2 * 24 * per_layer + 4 * 24 * 32 * 120 + 2 * 3840 * 32000)


def test_paged_attention_least_time():
    pk = flops.peaks("TPU v5 lite")
    cfg = json.load(open(os.path.join(ROOT, "bench", "configs",
                                      "danube3-4b-sq.json")))
    t = flops.paged_attn_least_s(cfg, [100, 300], pk)
    moved = 2 * 24 * 400 * 8 * 120 * 2
    assert t == pytest.approx(moved / 819e9)       # memory-bound at decode


# -------------------------------------------------------------------- hlo

def _body(name: bytes) -> str:
    return base64.b64encode(b"ML\xefR..." + name + b"\x00loc").decode()


HLO = f"""HloModule jit_step, entry_computation_layout={{()->f32[8,256]}}

%fused_dot (p0: bf16[8,256], p1: bf16[256,256]) -> bf16[8,256] {{
  %p0 = bf16[8,256]{{1,0}} parameter(0)
  %p1 = bf16[256,256]{{1,0}} parameter(1)
  ROOT %convolution.5 = bf16[8,256]{{1,0:T(8,128)(2,1)}} convolution(%p0, %p1), dim_labels=bf_io->bf
}}

%body (arg: (s32[], f32[1,8,256])) -> (s32[], f32[1,8,256]) {{
  %arg = (s32[], f32[1,8,256]) parameter(0)
  %a = f32[1,8,256]{{2,1,0}} get-tuple-element(%arg), index=1
  %b = f32[1,256,256]{{2,1,0}} constant({{...}})
  %sa = f32[1,8,1]{{2,1,0}} constant({{...}})
  %sb = f32[1,1,256]{{2,1,0}} constant({{...}})
  %_sq_matmul_exec.3 = f32[1,8,256]{{2,1,0:T(8,128)S(1)}} custom-call(%a, %b, %sa, %sb), custom_call_target="tpu_custom_call", backend_config={{"flag_configs":[],"custom_call_config":{{"body":"{_body(b"sq_matmul_kernel")}","needs_layout_passes":true}}}}
  %x = bf16[8,256]{{1,0}} constant({{...}})
  %w = bf16[256,256]{{1,0}} constant({{...}})
  %fusion.16 = bf16[8,256]{{1,0}} fusion(%x, %w), kind=kOutput, calls=%fused_dot
  %i = s32[] get-tuple-element(%arg), index=0
  ROOT %t = (s32[], f32[1,8,256]) tuple(%i, %_sq_matmul_exec.3)
}}

%cond (arg.1: (s32[], f32[1,8,256])) -> pred[] {{
  %arg.1 = (s32[], f32[1,8,256]) parameter(0)
  %i.1 = s32[] get-tuple-element(%arg.1), index=0
  %constant.24 = s32[]{{:T(128)}} constant(24)
  ROOT %lt.0 = pred[]{{:T(512)}} compare(%i.1, %constant.24), direction=LT
}}

ENTRY %main (q: f32[1,1,4,120], t: s32[8,96]) -> f32[8,256] {{
  %init = (s32[], f32[1,8,256]) parameter(0)
  %while = (s32[], f32[1,8,256]) while(%init), condition=%cond, body=%body
  %t = s32[8,96]{{1,0}} parameter(1)
  %q = f32[8,8,4,120]{{3,2,1,0}} parameter(2)
  %qp = s32[8,4,1]{{2,1,0}} parameter(3)
  %kt = f32[769,8,120,16]{{3,2,1,0}} parameter(4)
  %vr = f32[769,8,16,120]{{3,2,1,0}} parameter(5)
  %pp = s32[769,1,16]{{2,1,0}} parameter(6)
  %attn.1 = f32[8,8,4,120]{{3,2,1,0}} custom-call(%t, %q, %qp, %kt, %vr, %pp), custom_call_target="tpu_custom_call", backend_config={{"custom_call_config":{{"body":"{_body(b"sq_paged_attn_kernel")}"}}}}
  %lhs = f32[8,3840]{{1,0}} parameter(7)
  %rhs = f32[32000,3840]{{1,0}} parameter(8)
  ROOT %dot.1 = f32[8,32000]{{1,0}} dot(%lhs, %rhs), lhs_contracting_dims={{1}}, rhs_contracting_dims={{1}}
}}
"""


def test_hlo_contractions_with_loop_trip_counts():
    prog = hlo.parse(HLO)
    by = {c.name: c for c in prog.contractions}
    assert prog.unknown_trip_counts == 0
    sq = by["_sq_matmul_exec.3"]
    assert (sq.kind, sq.count, sq.flops) == ("sq_matmul", 24,
                                             2.0 * 8 * 256 * 256)
    conv = by["convolution.5"]
    assert (conv.kind, conv.count, conv.flops) == ("mxu", 24,
                                                   2.0 * 8 * 256 * 256)
    pa = by["attn.1"]
    assert pa.kind == "sq_paged_attn" and pa.count == 1
    assert pa.flops == 4.0 * 8 * 8 * 4 * 120 * (96 * 16)
    assert by["dot.1"].flops == 2.0 * 8 * 32000 * 3840
    assert prog.kernel_of == {"_sq_matmul_exec.3": "sq_matmul",
                              "attn.1": "sq_paged_attn"}
    assert prog.square_flops() == pytest.approx(
        24 * sq.flops + pa.flops)
    assert prog.flops() == pytest.approx(prog.square_flops() + 24 * conv.flops
                                         + by["dot.1"].flops)


# ------------------------------------------------------------------ trace

def _fixture_events():
    with open(os.path.join(FIXTURES, "trace_small.json")) as f:
        raw = json.load(f)
    return [trace.Event(p, l, n, s, d) for p, l, n, s, d in raw]


def test_trace_reduction_on_a_recorded_trace():
    """An excerpt of a traced decode window on a TPU v5e: the ops of one
    engine step, the harness's spans, and the window span cut to the
    excerpt."""
    evs = _fixture_events()
    lo, hi = trace.window_of(evs)
    dev = [e for e in evs if e.plane.startswith("/device:TPU:")
           and e.start_ns < hi and e.start_ns + e.dur_ns > lo]
    clip = lambda e: min(e.start_ns + e.dur_ns, hi) - max(e.start_ns, lo)
    head = lambda e: e.name.split(" = ")[0].lstrip("%")
    opcode = lambda e: re.search(r" ([a-z][a-z0-9_-]*)\(", e.name).group(1)
    kernel_of = {head(e): "sq_matmul" for e in dev
                 if head(e).startswith("_sq_matmul_exec")}
    assert kernel_of, "the excerpt holds sq_matmul calls"
    red = trace.reduce(evs, kernel_of)
    # busy: the union of the clipped op intervals, by a direct sweep
    iv = sorted((max(e.start_ns, lo), min(e.start_ns + e.dur_ns, hi))
                for e in dev)
    busy, end = 0.0, -1.0
    for a, b in iv:
        if b > end:
            busy += b - max(a, end)
            end = b
    assert red.devices == 1
    assert red.busy_s == pytest.approx(busy * 1e-9)
    assert red.window_s == pytest.approx((hi - lo) * 1e-9)
    assert 0 < red.busy_s <= red.window_s
    leaves = [e for e in dev if opcode(e) not in trace.CONTAINERS]
    assert len(leaves) < len(dev), "the excerpt holds the step's while loop"
    assert sum(red.kernel_s.values()) == pytest.approx(
        sum(map(clip, leaves)) * 1e-9)
    want = sum(clip(e) for e in leaves if head(e) in kernel_of)
    assert red.kernel_s["sq_matmul"] == pytest.approx(want * 1e-9)
    assert "while" not in red.kernel_s
    assert len(red.idle_gaps) <= 10 and len(red.device_ops) <= 10
    gaps = [g for _, g in red.idle_gaps]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= red.window_s - red.busy_s + 1e-9


def test_union_and_idle_gap_naming():
    mk = lambda p, n, s, d: trace.Event(p, "XLA Ops" if "device" in p else "t",
                                        n, s, d)
    evs = [mk("/host:CPU", "bench.window", 0, 100),
           mk("/host:CPU", "bench.client", 40, 30),
           mk("/device:TPU:0", "fusion.1", 0, 30),
           mk("/device:TPU:0", "fusion.2", 20, 20),      # overlaps
           mk("/device:TPU:0", "_sq_matmul_exec.3", 75, 25)]
    red = trace.reduce(evs, {"_sq_matmul_exec.3": "sq_matmul"})
    assert red.busy_s == pytest.approx(65e-9)
    assert red.kernel_s["fusion"] == pytest.approx(50e-9)
    assert red.kernel_s["sq_matmul"] == pytest.approx(25e-9)
    assert red.idle_gaps == [("client", pytest.approx(35e-9))]
    assert trace.union_ns([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]


# --------------------------------------------------- configs and lookups

BENCH = harness.benchmark()


CONFIGS = sorted(os.listdir(os.path.join(ROOT, "bench", "configs")))


@pytest.mark.parametrize("name", CONFIGS)
def test_config_files_match_the_registry(name):
    """Every key the architecture hands the program's ``ModelConfig`` is the
    registry's, unless the configuration lists it in ``reduced`` with its
    published value; no width is ever reduced."""
    cfg = json.load(open(os.path.join(ROOT, "bench", "configs", name)))
    reg = get_config(cfg["registry"])
    mod = arch.of(cfg)
    assert name == cfg["name"] + ".json" and cfg["source"] and cfg["published"]
    for conf in BENCH["configs"]:
        if conf["name"] == cfg["name"]:
            assert conf["reduced"] == cfg["reduced"]
            assert conf["file"] == "bench/configs/" + name
    mc = program.model_config(cfg)
    for k in mod.model_kwargs(cfg):
        want = getattr(reg, "resolved_" + k, getattr(reg, k))
        if k in cfg["reduced"]:
            assert getattr(mc, k) != want or k in cfg.get("published", {})
        else:
            assert getattr(mc, k) == want, k
    for k in cfg["reduced"]:
        assert k not in mod.WIDTHS and not k.endswith(("_dim", "_rank")), k
        assert k in cfg.get("published", {})


def test_benchmark_names_files_that_exist():
    names = set()
    for cell in BENCH["workloads"]:
        entry, cfg, mix, limits = harness.load_cell(cell["name"])
        assert entry is cell or entry == cell
        assert os.path.exists(os.path.join(ROOT, "bench", "drivers",
                                           mix["kind"] + ".py"))
        assert limits["limits"]
        for trace_on in (False, True):
            for m in harness.cell_metrics(BENCH, cell["name"], trace_on):
                assert callable(harness.metric_reader(m["name"]))
                names.add(m["name"])
    every = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    assert names == every


def test_a_new_metric_and_cell_need_no_edit(tmp_path):
    """A later change adds a metric and a cell by adding files and
    entries: the lookups find them with no existing file edited."""
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append(dict(bench["workloads"][0],
                                   name="danube3-4b-sq.newmix",
                                   traffic="newmix"))
    bench["per_layer"].append({"name": "new_counter", "unit": "count",
                               "better": "higher", "source": "program_counter",
                               "layer": "Scheduler", "moves": "output_tok_s",
                               "workloads": ["danube3-4b-sq.newmix"]})
    for sub in ("metrics", "traffic", "limits", "configs"):
        (tmp_path / "bench" / sub).mkdir(parents=True)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    for conf in bench["configs"]:
        (tmp_path / conf["file"]).write_text(
            open(os.path.join(ROOT, conf["file"])).read())
    (tmp_path / "bench" / "traffic" / "newmix.json").write_text(
        json.dumps(dict(REASON, clients=2)))
    (tmp_path / "bench" / "limits" / "danube3-4b-sq.newmix.json").write_text(
        json.dumps({"limits": {"logit_gap_mean": 1.0}}))
    (tmp_path / "bench" / "metrics" / "new_counter.py").write_text(
        "def read(rec):\n    return 42.0\n")
    root = str(tmp_path)
    entry, cfg, mix, limits = harness.load_cell("danube3-4b-sq.newmix", root)
    assert mix["clients"] == 2 and cfg["name"] == "danube3-4b-sq"
    got = harness.cell_metrics(harness.benchmark(root),
                               "danube3-4b-sq.newmix", True)
    assert [m["name"] for m in got] == ["new_counter"]
    assert harness.metric_reader("new_counter", root)(None) == 42.0
    with pytest.raises(FileNotFoundError):
        harness.metric_reader("no_such_metric", root)


def test_a_run_refuses_a_device_that_is_not_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(ROOT, "bench", "run.py"),
                        "--workload", BENCH["workloads"][0]["name"],
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
