"""Share of its roofline the flash-attention forward kernel reached (device trace; counters.flash_fwd)."""
from metric_lib import kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, "flash_fwd")
