"""Runs one cell of ``BENCHMARK.json`` and prints its result line.

Everything that belongs to one configuration, traffic mix, cell or metric
is a file of its own, found by the name ``BENCHMARK.json`` gives it:

- configuration: the ``file`` its entry names (``bench/configs/<name>.json``),
                 whose ``arch`` names its architecture,
                 ``bench/arch/<arch>.py``;
- traffic mix:   ``bench/traffic/<traffic>.json``, whose ``kind`` names the
                 driver, ``bench/drivers/<kind>.py``;
- limits:        ``bench/limits/<workload>.json``, the limits of the numbers
                 that decide ``correct``;
- metric:        ``bench/metrics/<name>.py``, a reader ``read(record)`` that
                 returns the metric's value, or None where it finds nothing.

A run that finds no TPU, or fewer chips than the cell asks for, exits
non-zero before any work and prints no result.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import math
import os
import sys
import time
from typing import Callable, Dict, List, Optional

from bench import arch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

__all__ = ["main", "run_cell", "load_cell", "metric_reader", "NoChip"]


class NoChip(RuntimeError):
    """No accelerator of the kind the cell needs."""


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def _named(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_cell(workload: str, root: str = ROOT):
    """(cell entry, configuration, traffic mix, limits) of a workload."""
    bench = benchmark(root)
    cell = _named(bench["workloads"], workload, "workload")
    conf = _named(bench["configs"], cell["config"], "configuration")
    here = os.path.join(root, "bench")
    cfg = _load_json(os.path.join(root, conf["file"]))
    mix = _load_json(os.path.join(here, "traffic", cell["traffic"] + ".json"))
    limits = _load_json(os.path.join(here, "limits", workload + ".json"))
    return cell, cfg, mix, limits


def cell_metrics(bench: dict, workload: str, trace: bool) -> List[dict]:
    """The metrics a run of the cell reports: its end-to-end metrics, or
    with ``trace`` its per-layer ones."""
    def has(m):
        return "workloads" not in m or workload in m["workloads"]
    e2e = [m for m in bench["end_to_end"] if has(m)]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (has(m) if "workloads" in m else m["moves"] in names)]


def metric_reader(name: str, root: str = ROOT) -> Callable:
    """``read`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _devices(chips: int):
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no accelerator: {e}") from e
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return devs


def _compile_cache() -> str:
    import jax
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return d


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_process: Optional[float] = None, require_chip: bool = True,
             hooks: Optional[Dict[str, Callable]] = None,
             cell=None):
    """Run one cell; returns (result dict, record).  ``cell`` replaces
    what :func:`load_cell` would read (the tests' small configurations);
    ``require_chip=False`` skips the look for a TPU."""
    t_process = time.perf_counter() if t_process is None else t_process
    bench = benchmark()
    entry, cfg, mix, limits = cell or load_cell(workload)
    arch.of(cfg)                     # a missing architecture fails here
    metrics = cell_metrics(bench, workload, trace)
    readers = {m["name"]: metric_reader(m["name"]) for m in metrics}
    import jax
    devs = _devices(entry["chips"]) if require_chip else jax.devices()
    if require_chip:
        _compile_cache()
    kind = devs[0].device_kind
    from bench.drivers import common
    ctx = common.Context(workload, cfg, mix, limits, int(seed),
                         float(seconds), bool(trace), t_process,
                         device_kind=kind if require_chip else "",
                         hooks=hooks or {})
    driver = importlib.import_module(f"bench.drivers.{mix['kind']}")
    rec = driver.run(ctx)

    out = {}
    for m in metrics:
        v = readers[m["name"]](rec)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": rec.memory_peak_bytes}
    result = {"correct": rec.correct, "attempted": int(rec.attempted),
              "failed": int(rec.failed), "metrics": out, "device": device}
    if rec.trace is not None:
        device["busy_s"] = rec.trace.busy_s
        device["window_s"] = rec.trace.window_s
        result["breakdown"] = {"device_ops": [list(x) for x in
                                              rec.trace.device_ops],
                               "idle_gaps": [list(x) for x in
                                             rec.trace.idle_gaps]}
    result["window_compiles"] = ctx.window_compiles
    result["notes"] = rec.notes
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in rec.checks.items()}
    return result, rec


def main(argv=None, t_process: Optional[float] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--served-out", help="write the served requests and "
                    "their gaps here as JSON, for calibrating limits")
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    try:
        result, rec = run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), t_process=t_process)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    if args.served_out:
        with open(args.served_out, "w") as f:
            json.dump(rec.served_json(), f)
    for name, c in result["checks"].items():
        lim = "none" if c["limit"] is None else repr(c["limit"])
        ok = c["limit"] is None or (math.isfinite(c["value"])
                                    and c["value"] <= c["limit"])
        print(f"check {name} {c['value']!r} limit {lim} "
              f"{'ok' if ok else 'FAIL'}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
