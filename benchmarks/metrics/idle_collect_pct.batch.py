"""Share of the traced window the device idled while the host read a decode window back and folded it: the engine's phases readback, collect, turn_tail (program spans on the device trace's clock)."""
from phase_lib import COLLECT, idle_under_pct


def read(ctx):
    return idle_under_pct(ctx, COLLECT)
