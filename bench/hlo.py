"""Contractions of a compiled program, read from its optimized HLO text.

Every matrix contraction the compiled step executes is either an XLA
``dot``/``convolution`` (the MXU) or a Pallas kernel (``tpu_custom_call``).
This module lists them with their operand shapes and how many times one
execution of the program runs each (while-loop bodies, such as a scan over
layers, multiply by their trip count), so that the benchmark can count
operations and bytes per kernel from shapes alone.

A Pallas call's kernel is named from its serialized Mosaic body, which
carries the kernel function's name (``<name>_kernel``).  Each kernel
the benchmark counts has a file of its own, ``bench/kernels/<name>.py``,
found by listing that directory: ``KERNEL``, the function's name as the
body carries it, and ``flops(operand_shapes, out_shapes)``, the call's
operations from its operands' and outputs' ``(dtype, dims)``, or None where
they are not the kernel's.  A Pallas call whose kernel has no file, or
whose file cannot read it, is listed in :attr:`Program.unknown` and counts
no operations.
"""
from __future__ import annotations

import base64
import dataclasses
import importlib.util
import math
import os
import re
from collections import defaultdict
from types import ModuleType
from typing import Dict, List, Optional, Tuple

__all__ = ["Contraction", "Program", "parse", "counts", "KERNELS_DIR"]

#: Where the kernels' count files are.
KERNELS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "kernels")

_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s*\(.*\)\s*->\s*.*\{\s*$")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_OPCODE_RE = re.compile(r"\s([a-z][a-z0-9_\-]*)\(")
_ARRAY_RE = re.compile(r"^([a-z0-9]+)\[([0-9,]*)\]")


@dataclasses.dataclass(frozen=True)
class Contraction:
    """One contraction instruction of a program."""
    name: str                        # HLO instruction name
    kind: str                        # "mxu" or a kernel's file name
    flops: float                     # 2 * multiply-adds of one execution
    operands: Tuple[Tuple[int, ...], ...]   # operand shapes
    count: int                       # executions per program execution


@dataclasses.dataclass
class Program:
    contractions: List[Contraction]
    kernel_of: Dict[str, str]        # custom-call instruction -> kernel
    unknown_trip_counts: int = 0     # while loops counted once
    unknown: List[str] = dataclasses.field(default_factory=list)
    #                                  Pallas kernels not counted, by name

    def flops(self, kind: Optional[str] = None) -> float:
        return sum(c.flops * c.count for c in self.contractions
                   if kind is None or c.kind == kind)

    def square_flops(self) -> float:
        return sum(c.flops * c.count for c in self.contractions
                   if c.kind != "mxu")


@dataclasses.dataclass
class _Instr:
    name: str
    opcode: str
    type_: str
    args: str
    operands: List[str]
    attrs: str


def _split_top(s: str) -> Tuple[str, str]:
    """``"a, b(c)), rest"`` -> (text inside the already-open paren, rest)."""
    depth = 1
    for i, ch in enumerate(s):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
            if depth == 0:
                return s[:i], s[i + 1:]
    return s, ""


def _parse_instr(line: str) -> Optional[_Instr]:
    m = _INSTR_RE.match(line)
    if not m:
        return None
    name, rhs = m.group(1), m.group(2)
    om = _OPCODE_RE.search(rhs)
    if not om:
        return None
    type_ = rhs[:om.start()].strip()
    args, attrs = _split_top(rhs[om.end():])
    operands = re.findall(r"%([\w.\-]+)", args)
    return _Instr(name, om.group(1), type_, args, operands, attrs)


def _shape(type_: str) -> Optional[Tuple[str, Tuple[int, ...]]]:
    m = _ARRAY_RE.match(type_)
    if not m:
        return None
    dims = tuple(int(d) for d in m.group(2).split(",") if d)
    return m.group(1), dims


def counts() -> Dict[str, ModuleType]:
    """{kernel name: its count file's module}, one per ``<name>.py``."""
    out = {}
    for f in sorted(os.listdir(KERNELS_DIR)):
        if f.endswith(".py") and not f.startswith("_"):
            name = f[:-3]
            spec = importlib.util.spec_from_file_location(
                f"bench_kernel_{name}", os.path.join(KERNELS_DIR, f))
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            out[name] = mod
    return out


def _kernel_name(attrs: str, kernels: Dict[str, ModuleType]
                 ) -> Tuple[str, bool]:
    """(name, whether a count file names it) of a Pallas call's kernel:
    the file whose ``KERNEL`` its body carries (the longest, should
    several), else the ``*_kernel`` name the body carries."""
    m = re.search(r'"body":"([A-Za-z0-9+/=]+)"', attrs)
    body = base64.b64decode(m.group(1)) if m else b""
    found = [(len(k.KERNEL), name) for name, k in kernels.items()
             if k.KERNEL.encode() in body]
    if found:
        return max(found)[1], True
    named = re.search(rb"[a-z][a-z0-9_]*_kernel", body)
    return (named.group(0).decode() if named else "pallas_other"), False


def _trip_count(cond: List[_Instr]) -> Optional[int]:
    """Trip count of a counted loop: its condition compares the counter
    with a constant (``i < n`` from 0, the form ``lax.scan`` lowers to)."""
    consts = {}
    for ins in cond:
        if ins.opcode == "constant" and re.fullmatch(r"-?\d+", ins.args):
            consts[ins.name] = int(ins.args)
    for ins in cond:
        if ins.opcode == "compare" and "direction=LT" in ins.attrs:
            for op in ins.operands:
                if op in consts:
                    return consts[op]
    return None


def _contraction_flops(ins: _Instr, shapes) -> Optional[float]:
    out = shapes.get(ins.name)
    if out is None:
        return None
    out_n = math.prod(out[1])
    if ins.opcode == "dot":
        lhs = shapes.get(ins.operands[0]) if ins.operands else None
        m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", ins.attrs)
        if lhs is None or m is None:
            return None
        k = math.prod(lhs[1][int(d)] for d in m.group(1).split(",") if d)
        return 2.0 * out_n * k
    if ins.opcode == "convolution":
        # XLA writes batched dots as convolutions whose batch dims are
        # dilated or padded spatial dims: count the window taps that land
        # on real input elements, min(ceil(size / lhs_dilate), input size)
        lhs = shapes.get(ins.operands[0]) if ins.operands else None
        rhs = shapes.get(ins.operands[1]) if len(ins.operands) > 1 else None
        m = re.search(r"dim_labels=(\w+)_(\w+)->", ins.attrs)
        if lhs is None or rhs is None or m is None or "i" not in m.group(2):
            return None
        lab, rlab = m.group(1), m.group(2)
        taps = 1
        spatial = sorted(c for c in lab if c.isdigit())
        size = _window(ins.attrs, "size", len(spatial))
        dil = _window(ins.attrs, "lhs_dilate", len(spatial))
        for s, w, d in zip(spatial, size, dil):
            taps *= min(-(-w // d), lhs[1][lab.index(s)])
        return 2.0 * out_n * rhs[1][rlab.index("i")] * taps
    return None


def _window(attrs: str, key: str, n: int) -> List[int]:
    """One field of a convolution's ``window={...}``, per spatial dim."""
    m = re.search(r"window=\{[^}]*\b" + key + r"=([0-9x]+)", attrs)
    return [int(v) for v in m.group(1).split("x")] if m else [1] * n


def _types(type_: str) -> Tuple[Tuple[str, Tuple[int, ...]], ...]:
    """(dtype, dims) of each array in an array or tuple type."""
    return tuple((m.group(1), tuple(int(d) for d in m.group(2).split(",") if d))
                 for m in re.finditer(r"([a-z][a-z0-9]*)\[([0-9,]*)\]", type_))


def parse(text: str) -> Program:
    """Contractions of one compiled HLO module (``compiled.as_text()``),
    each Pallas call counted by its kernel's count file."""
    kernels = counts()
    comps: Dict[str, List[_Instr]] = {}
    entry = None
    cur: Optional[List[_Instr]] = None
    for line in text.splitlines():
        cm = _COMP_RE.match(line)
        if cm:
            cur = comps.setdefault(cm.group(1), [])
            if line.startswith("ENTRY"):
                entry = cm.group(1)
            continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is not None:
            ins = _parse_instr(line)
            if ins is not None:
                cur.append(ins)
    if entry is None:
        raise ValueError("no ENTRY computation in the HLO text")

    # executions of each computation per execution of the program
    mult: Dict[str, int] = defaultdict(int)
    unknown_trips = 0

    def visit(comp: str, n: int):
        nonlocal unknown_trips
        mult[comp] += n
        for ins in comps.get(comp, []):
            if ins.opcode == "while":
                body = re.search(r"body=%([\w.\-]+)", ins.attrs)
                cond = re.search(r"condition=%([\w.\-]+)", ins.attrs)
                trips = _trip_count(comps.get(cond.group(1), [])) \
                    if cond else None
                if trips is None:
                    unknown_trips += 1
                    trips = 1
                if body:
                    visit(body.group(1), n * trips)
                continue
            for ref in re.findall(r"(?:calls|to_apply)=%([\w.\-]+)",
                                  ins.attrs):
                if ins.opcode in ("fusion", "call", "async-start",
                                  "custom-call"):
                    visit(ref, n)
            bm = re.search(r"branch_computations=\{([^}]*)\}", ins.attrs)
            if bm:
                for ref in re.findall(r"%([\w.\-]+)", bm.group(1)):
                    visit(ref, n)

    visit(entry, 1)

    out: List[Contraction] = []
    kernel_of: Dict[str, str] = {}
    unknown = set()                  # Pallas kernels not counted
    for comp, instrs in comps.items():
        n = mult.get(comp, 0)
        shapes = {}
        for ins in instrs:
            s = _shape(ins.type_)
            if s is not None:
                shapes[ins.name] = s
        for ins in instrs:
            if ins.opcode == "custom-call" and \
                    'custom_call_target="tpu_custom_call"' in ins.attrs:
                kernel, known = _kernel_name(ins.attrs, kernels)
                kernel_of[ins.name] = kernel
                typed = tuple(shapes[o] for o in ins.operands if o in shapes)
                f = kernels[kernel].flops(typed, _types(ins.type_)) \
                    if known else None
                if f is None:
                    unknown.add(kernel)
                elif n:
                    out.append(Contraction(ins.name, kernel, float(f),
                                           tuple(d for _, d in typed), n))
            elif ins.opcode in ("dot", "convolution") and n:
                f = _contraction_flops(ins, shapes)
                if f is not None:
                    ops = tuple(shapes[o][1] for o in ins.operands
                                if o in shapes)
                    out.append(Contraction(ins.name, "mxu", f, ops, n))
    return Program(out, kernel_of, unknown_trips, sorted(unknown))
