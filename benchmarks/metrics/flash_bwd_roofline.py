"""Share of its roofline the flash-attention backward (dQ and dK/dV kernels together) reached (device trace; counters.flash_bwd)."""
from metric_lib import kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, "flash_bwd")
