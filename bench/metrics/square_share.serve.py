"""Share of the compiled step's contraction operations that run inside
Pallas square kernels; XLA dots count as the MXU (%)."""
from bench.readers import square_share as read  # noqa: F401
