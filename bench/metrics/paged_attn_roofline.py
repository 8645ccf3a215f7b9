"""Least time to read each decode step's live K/V and do its q.k and p.v,
over sq_paged_attn's summed device time in the trace (%)."""


def read(rec):
    t = rec.trace.kernel_s.get("sq_paged_attn") if rec.trace else None
    if not t or rec.paged_attn_least_s is None:
        return None
    return 100.0 * rec.paged_attn_least_s / t
