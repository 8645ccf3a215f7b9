"""Readers shared by metrics that one quantity splits by cell kind
(``mfu.serve`` / ``mfu.train`` ...).  Each returns None where the run
holds nothing to read: no chip, no trace, or no such kernel."""
from __future__ import annotations

from bench import flops

__all__ = ["mfu", "square_share", "sq_matmul_roofline", "device_idle"]


def mfu(rec):
    if not rec.ctx.device_kind:
        return None
    pk = flops.peaks(rec.ctx.device_kind)
    return 100.0 * rec.model_flops / rec.window_s / pk["bf16_flops_per_s"]


def square_share(rec):
    if not rec.programs:
        return None
    sq = sum(p.square_flops() * n for p, n in rec.programs.values())
    total = sum(p.flops() * n for p, n in rec.programs.values())
    return 100.0 * sq / total if total else None


def sq_matmul_roofline(rec):
    """Each sq_matmul call's least time from its operand shapes, times the
    calls the window made, over the kernel's device time."""
    if not rec.programs or rec.trace is None or not rec.ctx.device_kind:
        return None
    spent = rec.trace.kernel_s.get("sq_matmul")
    if not spent:
        return None
    pk = flops.peaks(rec.ctx.device_kind)
    least = 0.0
    for prog, n in rec.programs.values():
        for c in prog.contractions:
            if c.kind == "sq_matmul":
                a, b = c.operands[0], c.operands[1]
                batch = 1
                for x in a[:-2]:
                    batch *= x
                t, _ = flops.matmul_least_s(a[-2], a[-1], b[-1], pk,
                                            batch=batch)
                least += t * c.count * n
    return 100.0 * least / spent


def device_idle(rec):
    if rec.trace is None:
        return None
    return 100.0 * (1.0 - rec.trace.busy_s / rec.trace.window_s)
