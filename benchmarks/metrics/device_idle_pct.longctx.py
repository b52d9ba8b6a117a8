"""1 - union of the device's operation intervals over the traced window (device trace)."""
from metric_lib import device_idle_pct as read  # noqa: F401
