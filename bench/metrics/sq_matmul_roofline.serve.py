"""Least time of the contractions that sq_matmul computes (operations
over peak FLOP/s or bytes over HBM bandwidth) over the kernel's summed
device time in the trace (%)."""
from bench.readers import sq_matmul_roofline as read  # noqa: F401
