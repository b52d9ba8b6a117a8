"""Device time of the prefill and insert programs over the device's busy time in the traced window (device trace)."""
from metric_lib import prefill_share_pct as read  # noqa: F401
