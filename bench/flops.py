"""Operations and bytes the algorithm needs, from a configuration's shapes.

The counts of one architecture are its module's (``bench/arch/<arch>.py``);
this module holds the chip's peaks and sums those counts into least times.

These are the yardstick for ``mfu.*`` and the ``*_roofline`` metrics: they
count what the model's arithmetic requires, whatever implements it, so a
kernel that pads, widens or recomputes pays for that in its own time and
not in a larger numerator.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Iterable, Tuple

from bench import arch

__all__ = ["peaks", "forward_flops", "logits_flops", "matmul_least_s",
           "paged_attn_least_s"]

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip; a device not in the table is an error."""
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def forward_flops(cfg: dict, ctx: int, logits: bool) -> float:
    """Forward operations of one token at context ``ctx`` (itself
    included), with or without its logits: the architecture's count."""
    return arch.of(cfg).token_flops(cfg, ctx, logits)


def logits_flops(cfg: dict) -> float:
    return arch.of(cfg).logits_flops(cfg)


def matmul_least_s(m: int, k: int, n: int, pk: Dict[str, float], *,
                   batch: int = 1, itemsize: int = 2) -> Tuple[float, str]:
    """Least time of a (m, k) x (k, n) product on one chip, and which bound
    sets it: 2mkn over peak FLOP/s, or reading A and B and writing C once
    at ``itemsize`` bytes per element over HBM bandwidth."""
    flops = 2.0 * batch * m * k * n
    moved = float(batch * (m * k + k * n + m * n) * itemsize)
    t_c = flops / pk["bf16_flops_per_s"]
    t_m = moved / pk["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def paged_attn_least_s(cfg: dict, contexts: Iterable[int],
                       pk: Dict[str, float], *,
                       itemsize: int = 2) -> float:
    """Least time of one decode step's attention read in every layer: one
    query per sequence over its live context, reading that context's
    cached K and V once (``itemsize`` bytes a value) and doing its q.k
    and p.v, as the architecture counts them; the larger of the step's
    operations over peak FLOP/s and its bytes over HBM bandwidth."""
    mod = arch.of(cfg)
    flops = moved = 0.0
    for c in contexts:
        f, b = mod.paged_attn_cost(cfg, c, itemsize)
        flops += f
        moved += b
    return max(flops / pk["bf16_flops_per_s"], moved / pk["hbm_bytes_per_s"])
