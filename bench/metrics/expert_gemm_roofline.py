"""Least time of the routed experts' work over sq_grouped_matmul's summed
device time in the trace (%).

The work is the routed picks that land on this chip's experts, in
expectation: every token the window passed (decode rows and prompt
tokens) times ``num_experts_per_tok * experts_held / n_routed_experts``,
through the three GEMMs of each MoE layer (gate and up, ``d x f``, and
down, ``f x d``).  Prompt tokens are counted as whole ``prefill_chunk``
chunks, an upper bound.  Bytes: each held expert's three matrices read
once per execution of a step program.  Least time is the larger of the
operations over the chip's bf16 peak and the bytes over its HBM
bandwidth.  None where the run has no such kernel, trace or chip, or the
configuration holds no experts.
"""
from bench import flops


def read(rec):
    cfg = rec.cfg
    if rec.trace is None or not rec.ctx.device_kind \
            or not cfg.get("experts_held"):
        return None
    spent = sum(t for k, t in rec.trace.kernel_s.items()
                if k.startswith("sq_grouped_matmul"))
    if not spent:
        return None
    pk = flops.peaks(rec.ctx.device_kind)
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    n_moe = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    held = cfg["experts_held"]
    tokens = rec.decode_slot_steps \
        + rec.prefill_chunks * rec.ctx.traffic["engine"]["prefill_chunk"]
    picks = tokens * cfg["num_experts_per_tok"] * held \
        / cfg["n_routed_experts"]
    ops = 2.0 * picks * 3 * d * f * n_moe
    execs = rec.decode_steps + rec.prefill_chunks
    itemsize = 2 if cfg["dtype"] == "bfloat16" else 4
    moved = float(execs * n_moe * held * 3 * d * f * itemsize)
    least = max(ops / pk["bf16_flops_per_s"], moved / pk["hbm_bytes_per_s"])
    return 100.0 * least / spent
