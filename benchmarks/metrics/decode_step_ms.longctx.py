"""Device time of the DecodeStep program over its whole launches in the traced window, all of them (device trace)."""
from metric_lib import program_ms


def read(ctx):
    return program_ms(ctx, "decode_step")
