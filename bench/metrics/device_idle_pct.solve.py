"""Share of the traced window with no op on the device (device layer)."""
from bench.readers import device_idle_pct as read  # noqa: F401
