"""Operations and bytes the algorithm needs, from a configuration's shapes.

These are the yardstick for ``mfu.*`` and the ``*_roofline`` metrics: they
count what the model's arithmetic requires, whatever implements it, so a
kernel that pads, widens or recomputes pays for that in its own time and
not in a larger numerator.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Iterable, Tuple

__all__ = ["peaks", "matmul_params", "forward_flops", "logits_flops",
           "attention_flops", "matmul_least_s", "paged_attn_least_s"]

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip; a device not in the table is an error."""
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def matmul_params(cfg: dict) -> int:
    """Weights each token multiplies through in the layer stack."""
    d, H, KV, hd, f = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                       cfg["head_dim"], cfg["d_ff"])
    ffn = 3 if cfg["activation"] in ("swiglu", "geglu") else 2
    per_layer = d * (H + 2 * KV) * hd + H * hd * d + ffn * d * f
    return per_layer * cfg["n_layers"]


def attention_flops(cfg: dict, ctx: int) -> float:
    """q.k and p.v of one query over ``ctx`` keys, every layer."""
    if cfg.get("window"):
        ctx = min(ctx, cfg["window"])
    return 4.0 * cfg["n_layers"] * cfg["n_heads"] * cfg["head_dim"] * ctx


def logits_flops(cfg: dict) -> float:
    return 2.0 * cfg["d_model"] * cfg["vocab"]


def forward_flops(cfg: dict, ctx: int, logits: bool) -> float:
    """Forward operations of one token at context ``ctx`` (itself
    included)."""
    f = 2.0 * matmul_params(cfg) + attention_flops(cfg, ctx)
    return f + (logits_flops(cfg) if logits else 0.0)


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
    query per sequence over its live context, reading that context's K and
    V once (``itemsize`` bytes) and doing its q.k and p.v, the larger of
    the step's operations over peak FLOP/s and its bytes over HBM
    bandwidth."""
    kv, hd, L = cfg["n_kv_heads"], cfg["head_dim"], cfg["n_layers"]
    flops = moved = 0.0
    for c in contexts:
        if cfg.get("window"):
            c = min(c, cfg["window"])
        flops += attention_flops(cfg, c)
        moved += 2.0 * L * c * kv * hd * itemsize
    return max(flops / pk["bf16_flops_per_s"], moved / pk["hbm_bytes_per_s"])
