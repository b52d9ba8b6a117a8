"""Device time of a PrefillStep launch, one 2,048-token chunk against the slot's cached rows, over its whole launches in the traced window (device trace)."""
from metric_lib import program_ms


def read(ctx):
    return program_ms(ctx, "prefill_step")
