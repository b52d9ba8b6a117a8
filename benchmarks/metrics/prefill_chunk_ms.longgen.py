"""Device time of a PrefillStep launch (a prompt of up to 2,048 tokens in its bucket, or a 2,048-token chunk continuing from the slot's state and cached rows) over its whole launches in the traced window (device trace)."""
from metric_lib import program_ms


def read(ctx):
    return program_ms(ctx, "prefill_step")
