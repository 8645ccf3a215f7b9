"""What every driver shares: the run's context and record, weights, the
profiler window and the compiled programs' contractions."""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import shutil
import tempfile
from typing import Callable, Dict, List, Optional, Tuple

import jax

from bench import hlo, trace, weights

__all__ = ["Context", "Record", "Window", "judge", "make_weights",
           "Profiler", "window", "memory_peak", "programs"]

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass
class Context:
    """One run of one cell."""
    workload: str
    cfg: dict
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    t_process: float                   # perf_counter at process start
    device_kind: str = ""
    hooks: Dict[str, Callable] = dataclasses.field(default_factory=dict)
    max_fill_ticks: int = 100000
    setup_s: float = 0.0
    window_compiles: int = 0
    _counting: bool = False

    def __post_init__(self):
        def listen(event, duration, **kw):
            if event == BACKEND_COMPILE_EVENT and self._counting:
                self.window_compiles += 1
        jax.monitoring.register_event_duration_secs_listener(listen)

    def count_compiles(self, on: bool) -> None:
        """Count programs compiled (or loaded from the cache) while on."""
        self._counting = on


@dataclasses.dataclass
class Window:
    """Samples a serving window collects, all of them."""
    tokens: int = 0
    gaps: List[float] = dataclasses.field(default_factory=list)
    ttft: List[float] = dataclasses.field(default_factory=list)
    lateness: List[float] = dataclasses.field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    errors: List[str] = dataclasses.field(default_factory=list)


def judge(limits: dict, values: Dict[str, float]):
    """({name: (value, limit)}, correct) for numbers compared against the
    limits of a cell's limits file.  A number without a limit is reported,
    not judged; a number that is not finite fails its limit."""
    import math
    checks = {name: (float(v), limits.get("limits", {}).get(name))
              for name, v in values.items()}
    ok = bool(checks) and all(lim is None or (math.isfinite(v) and v <= lim)
                              for v, lim in checks.values())
    return checks, ok


class Record:
    """What a run measured; the metric readers read it."""

    def __init__(self, ctx: Context, kind: str):
        self.ctx = ctx
        self.kind = kind
        self.cfg = ctx.cfg
        self.window_s = 0.0
        self.memory_peak_bytes = 0
        self.programs: Optional[Dict[str, Tuple[hlo.Program, int]]] = None
        self.trace: Optional[trace.Reduction] = None
        self.checks: Dict[str, Tuple[float, Optional[float]]] = {}
        self.notes: Dict[str, object] = {}     # printed with the result
        self.served: list = []       # (prompt, served tokens) compared
        self.gaps = None             # their per-token gaps

    def served_json(self) -> dict:
        """What the run served and how each token compared, for
        ``bench/calibrate.py --served`` to read the control at."""
        return {"workload": self.ctx.workload, "seed": self.ctx.seed,
                "served": [[p.tolist(), o.tolist()] for p, o in self.served],
                "gaps": [float(g) for g in self.gaps]}

    @property
    def setup_s(self) -> float:
        return self.ctx.setup_s

    def compare(self, values: Dict[str, float]) -> None:
        """Record each compared number beside its limit (:func:`judge`)."""
        checks, _ = judge(self.ctx.limits, values)
        self.checks.update(checks)

    @property
    def correct(self) -> bool:
        return judge(self.ctx.limits,
                     {k: v for k, (v, _) in self.checks.items()})[1]


def make_weights(cfg: dict, key):
    """The whole parameter tree, on the device, from one jitted call."""
    tree = jax.jit(functools.partial(weights.init, cfg))(key)
    return jax.block_until_ready(tree)


class Profiler:
    """The profiler, on for the window of a ``--trace 1`` run, writing to
    a temporary directory that is removed once read."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.on = True

    def stop(self) -> None:
        if self.on:
            jax.profiler.stop_trace()
            self.on = False

    def reduce(self, progs: Dict[str, Tuple[hlo.Program, int]]):
        self.stop()
        kernel_of: Dict[str, str] = {}
        for prog, _ in progs.values():
            kernel_of.update(prog.kernel_of)
        try:
            events = trace.load(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return trace.reduce(events, kernel_of)


@contextlib.contextmanager
def window(prof: Optional[Profiler]):
    """The measured window, marked for the trace reduction; the profiler
    stops when it closes."""
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            yield None
    finally:
        if prof is not None:
            prof.stop()


def memory_peak() -> int:
    """Peak bytes in use on the fullest chip of the process."""
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def programs(texts: Dict[str, str], execs: Dict[str, int]):
    """{name: (parsed program, executions in the window)}."""
    return {k: (hlo.parse(t), int(execs.get(k, 0))) for k, t in texts.items()}

