"""(Every weight once, the tied embedding read whole as the head + each live slot's float32 state and convolution tail read and written + the live K and V rows) / peak bandwidth, over the decode program's device time a launch; the live slots and rows are the means over the traced window's turns (device trace; the family's decode_step_bytes)."""
import numpy as np

from metric_lib import peak, program_ms


def read(ctx):
    ms = program_ms(ctx, "decode_step")
    t = ctx.get("trace")
    if ms is None:
        return None
    on, off = t["host_window"]
    turns = [x for x in ctx["pump"]["turns"] if on <= x[0] <= off]
    if not turns:
        return None
    live_slots = float(np.mean([x[1] for x in turns])) * ctx["slots"]
    nbytes = ctx["family"].decode_step_bytes(
        ctx["sizes"], float(np.mean([x[3] for x in turns])), live_slots)
    return 100.0 * (nbytes / peak(ctx)["hbm_bytes_per_s"]) / (ms / 1e3)
