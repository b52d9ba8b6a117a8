"""(Every weight once + the live K/V of the active slots) / peak bandwidth, over the decode program's device time a launch (device trace; counters.decode_step_bytes)."""
from metric_lib import decode_hbm_roofline as read  # noqa: F401
