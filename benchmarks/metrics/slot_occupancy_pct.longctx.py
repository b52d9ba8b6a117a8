"""Mean of engine.inflight() / slots, sampled at every turn of the window (program counter)."""
from metric_lib import slot_occupancy_pct as read  # noqa: F401
