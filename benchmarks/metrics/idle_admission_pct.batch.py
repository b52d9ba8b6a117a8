"""Share of the traced window the device idled while the host admitted a request: the engine's phases prefill_chunk, admit, slot_cache, prefill, first_token, insert, first_token_read (program spans on the device trace's clock)."""
from phase_lib import ADMISSION, idle_under_pct


def read(ctx):
    return idle_under_pct(ctx, ADMISSION)
