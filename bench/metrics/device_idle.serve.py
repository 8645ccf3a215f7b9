"""One minus the union of device op intervals over the traced window (%)."""
from bench.readers import device_idle as read  # noqa: F401
