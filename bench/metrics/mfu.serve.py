"""Operations the model requires per token (from its shapes) times the
tokens passed per second, over the chip's bf16 peak (%)."""
from bench.readers import mfu as read  # noqa: F401
