"""Reduction of a profiler trace to device busy time, kernel time and the
longest idle gaps.

The profiler writes an ``.xplane.pb``; :func:`load` flattens it into plain
event records (plane, line, name, start, duration) so that the
reduction below runs the same on a trace just taken and on the small
recorded fixture the tests keep.  Device operations are the events of the
``XLA Ops`` line of each ``/device:TPU:<n>`` plane; host spans are the
harness's own ``bench.*`` annotations, on the same clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Tuple

__all__ = ["Event", "load", "Reduction", "reduce", "union_ns", "window_of",
           "WINDOW"]

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
WINDOW = "bench.window"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str                  # a device op's name is its HLO text
    start_ns: float
    dur_ns: float


def load(log_dir: str) -> List[Event]:
    """Every device op and every ``bench.*`` host span of the trace that
    ``jax.profiler`` wrote under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    out = []
    for path in paths:
        for plane in ProfileData.from_file(path).planes:
            device = _DEVICE_PLANE.match(plane.name) is not None
            for line in plane.lines:
                if device and line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    if not device and not ev.name.startswith(HOST_PREFIX):
                        continue
                    out.append(Event(plane.name, line.name, ev.name,
                                     float(ev.start_ns),
                                     float(ev.duration_ns)))
    return out


def union_ns(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


_INSTR = re.compile(r"^%?([\w.\-]+)(?: = .*? ([a-z][a-z0-9_\-]*)\()?")
#: Ops whose time is their children's: counted in busy time, never as a
#: kernel of their own.
CONTAINERS = ("while", "conditional", "call")


def _op(ev: Event, kernel_of: Dict[str, str]) -> Tuple[str, str]:
    """(name, opcode) of a device op.  The trace names an op by its HLO
    text (``%fusion.12 = f32[..] fusion(...)``); a Pallas call is named by
    its kernel, any other op by its instruction name without the number
    (``fusion.12`` -> ``fusion``)."""
    m = _INSTR.match(ev.name)
    inst, opcode = (m.group(1), m.group(2) or "") if m else (ev.name, "")
    if inst in kernel_of:
        return kernel_of[inst], opcode
    return re.sub(r"(\.\d+)+$", "", inst), opcode


@dataclasses.dataclass
class Reduction:
    devices: int
    busy_s: float                  # union of op intervals, mean over chips
    window_s: float                # length of the traced window
    kernel_s: Dict[str, float]     # summed leaf-op time by name, all chips
    device_ops: List[Tuple[str, float]]   # top 10 of kernel_s
    idle_gaps: List[Tuple[str, float]]    # longest gaps, by host span


def window_of(events: List[Event]) -> Tuple[float, float]:
    """(start, end) of the harness's ``bench.window`` span."""
    spans = [ev for ev in events if ev.name == WINDOW]
    if len(spans) != 1:
        raise ValueError(f"{len(spans)} {WINDOW} spans in the trace")
    return spans[0].start_ns, spans[0].start_ns + spans[0].dur_ns


def reduce(events: List[Event], kernel_of: Dict[str, str]) -> Reduction:
    """Busy time, time per kernel and the ten longest idle gaps of the
    device ops inside the harness's ``bench.window`` span."""
    lo, hi = window_of(events)
    per_dev: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    kernel_s: Dict[str, float] = defaultdict(float)
    host: List[Tuple[float, float, str]] = []
    for ev in events:
        a, b = max(ev.start_ns, lo), min(ev.start_ns + ev.dur_ns, hi)
        if b <= a:
            continue
        if _DEVICE_PLANE.match(ev.plane):
            per_dev[ev.plane].append((a, b))
            name, opcode = _op(ev, kernel_of)
            if opcode not in CONTAINERS:
                kernel_s[name] += (b - a) * 1e-9
        elif ev.name.startswith(HOST_PREFIX) and ev.name != WINDOW:
            host.append((a, b, ev.name[len(HOST_PREFIX):]))
    if not per_dev:
        raise ValueError("the trace holds no device op inside the window")
    busy = {d: union_ns(iv) for d, iv in per_dev.items()}
    busy_s = sum(sum(b - a for a, b in iv) for iv in busy.values()) \
        / len(busy) * 1e-9

    gaps = []
    for iv in busy.values():
        edges = [(lo, lo)] + iv + [(hi, hi)]
        for (_, e0), (s1, _) in zip(edges, edges[1:]):
            if s1 > e0:
                gaps.append((e0, s1))
    named = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        best, who = 0.0, "unattributed"
        for ha, hb, name in host:
            ov = min(b, hb) - max(a, ha)
            if ov > best:
                best, who = ov, name
        named.append((who, (b - a) * 1e-9))
    top = sorted(kernel_s.items(), key=lambda kv: -kv[1])[:10]
    return Reduction(len(busy), busy_s, (hi - lo) * 1e-9, dict(kernel_s), top,
                     named)
